"""Mixture-of-Experts layer: top-k token-choice routing with capacity.

Classic dispatch/combine formulation (Shazeer et al.): tokens pick their
top-k experts, each expert processes at most C = ceil(k·T/E·cf) tokens,
overflow is dropped (residual passes through).  The dispatch is expressed as
scatter/gather so the expert dimension shards cleanly on the "model" mesh
axis (expert parallelism) — the pattern the paper's content/RPC substrate is
built to feed.

Router gating (softmax → top-k → renormalize) has a Pallas kernel in
``repro.kernels.moe_gating``; the jnp path below doubles as its oracle.

A layer told which experts it holds (``cfg.experts_held``: expert
parallelism, one device's share) runs :func:`expert_share` instead: the
router scores every expert, and the layer computes, exactly and dropping
nothing, its own experts' part of the result for the tokens routed to
them.  What the absent experts add comes from the devices that hold them.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import dense_init, init_mlp, run_mlp
from .config import ModelConfig

Params = Dict[str, Any]


def _constrain_groups(x: jax.Array, cfg: ModelConfig, dim: int = 0,
                      model_dim: Optional[int] = None) -> jax.Array:
    """Pin dim ``dim`` of a dispatch buffer to the data axes: dim=0 (G) is
    the token-group layout, dim=1 (E) is the expert-parallel layout; a
    constraint flip between them lowers to one all-to-all.  ``model_dim``
    additionally keeps that dim sharded on the TP axis (so the F-contracted
    down-projection reduce-scatters instead of all-reducing to full D)."""
    if cfg.moe_groups <= 1 or not cfg.act_batch_axes:
        return x
    from jax.sharding import PartitionSpec as P

    axes: Any = (cfg.act_batch_axes if len(cfg.act_batch_axes) > 1
                 else cfg.act_batch_axes[0])
    spec: list = [None] * x.ndim
    spec[dim] = axes
    if model_dim is not None and cfg.act_model_axis:
        if x.shape[model_dim] % 16 == 0:
            spec[model_dim] = cfg.act_model_axis
    return jax.lax.with_sharding_constraint(x, P(*spec))


def init_moe(cfg: ModelConfig, key: jax.Array, dtype: Any) -> Params:
    """Router over all ``n_experts``; weights of the ``n_held`` held."""
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_exp
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": dense_init(ks[0], (D, E), jnp.float32, scale=0.02),
        "w_gate": dense_init(ks[1], (cfg.n_held, D, F), dtype),
        "w_up": dense_init(ks[2], (cfg.n_held, D, F), dtype),
        "w_down": dense_init(ks[3], (cfg.n_held, F, D), dtype),
    }
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], D, cfg.n_shared_experts * F, dtype)
    return p


def topk_gating(logits: jax.Array, k: int, renormalize: bool = True,
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Softmax over experts, keep the top k, and (``renormalize``) scale
    the kept probabilities to sum to 1; without it each token's weights
    are its experts' softmax probabilities as they stand (DeepSeek-V2's
    ``norm_topk_prob`` false).

    logits: (T, E) float32.  Returns (weights (T,k), experts (T,k), probs (T,E)).
    This is the reference implementation; ``repro.kernels.moe_gating``
    provides the fused TPU kernel (renormalizing only).
    """
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, k)
    if renormalize:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    return weights, experts, probs


def expert_share(p: Params, cfg: ModelConfig, xt: jax.Array,
                 ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of a MoE layer for tokens ``xt (T, D)``.

    The router's float32 logits (at ``highest`` precision) score all
    ``n_experts``; each token keeps its top ``moe_top_k``.  Every held
    expert then runs on every token with the token's gate weight for it,
    0 where the token did not pick it: capacity ``T`` per expert, so no
    token is ever dropped, in prefill and decode alike.  Returns ``(y (T,
    D), hits (T, n_held) bool)``: the routed part of the output (shared
    experts not included) and which held experts each token picked.
    """
    logits = jnp.dot(xt.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    weights, experts, _ = topk_gating(logits, cfg.moe_top_k,
                                      cfg.norm_topk_prob)
    held = jnp.asarray(cfg.experts_held, jnp.int32)
    pick = experts[:, :, None] == held                       # (T, K, n_held)
    gate = jnp.sum(jnp.where(pick, weights[:, :, None], 0.0), axis=1)
    h = jax.nn.silu(jnp.einsum("td,edf->etf", xt, p["w_gate"]))
    h = h * jnp.einsum("td,edf->etf", xt, p["w_up"])
    eo = jnp.einsum("etf,efd->etd", h, p["w_down"])
    y = jnp.einsum("etd,te->td", eo, gate.astype(eo.dtype))
    return y, jnp.any(pick, axis=1)


def run_moe(p: Params, cfg: ModelConfig, x: jax.Array,
            use_kernel: bool = False, no_drop: bool = False,
            rows_out: Optional[List[jax.Array]] = None,
            live: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) → (y, aux_loss).

    ``no_drop=True`` (decode/serving): per-expert capacity covers the worst
    case so no token is ever dropped mid-generation.  Training keeps the
    capacity-factor drop semantics (the aux loss pushes the router toward
    balance).

    With ``cfg.experts_held`` the layer is :func:`expert_share` plus the
    shared experts, dropless whatever ``no_drop`` says, with no aux loss;
    ``rows_out``, when given, gets the rows routed to each held expert
    ``(n_held,)`` int32, counting only the ``live`` tokens ``(B*S,)``.
    """
    B, S, D = x.shape
    E, K, F = cfg.n_experts, cfg.moe_top_k, cfg.d_exp
    T = B * S
    xt = x.reshape(T, D)
    if cfg.experts_held:
        y, hits = expert_share(p, cfg, xt)
        if "shared" in p:
            y = y + run_mlp(p["shared"], xt)
        if rows_out is not None:
            if live is not None:
                hits = hits & live[:, None]
            rows_out.append(jnp.sum(hits, axis=0, dtype=jnp.int32))
        return y.reshape(B, S, D), jnp.zeros((), jnp.float32)
    logits = xt.astype(jnp.float32) @ p["router"]
    if use_kernel:
        from repro.kernels.ops import moe_gating
        weights, experts, probs = moe_gating(logits, K)
    else:
        weights, experts, probs = topk_gating(logits, K)

    # token groups: at scale G = number of data shards, so each group's
    # dispatch buffer stays local and experts see a (G, E, C, D) batch that
    # shards group-dim on data and expert/ffn dims on model (all-to-all
    # traffic emerges from the G×E resharding — the MoE pattern the paper's
    # substrate is built to carry across clusters)
    G = cfg.moe_groups if cfg.moe_groups > 1 and T % cfg.moe_groups == 0 else 1
    Tg = T // G
    if no_drop:
        # serving: cover the worst case exactly for small token counts
        # (decode), and a 2x load-imbalance margin for large ones (prefill) —
        # capacity = Tg at 1M prefill tokens would be a terabyte-scale buffer
        if Tg <= 512:
            capacity = Tg
        else:
            capacity = min(int(2 * K * Tg / E) + 1, Tg)
    else:
        capacity = int(max(K * Tg * cfg.capacity_factor / E, K))
        capacity = min(capacity, Tg)

    xg = _constrain_groups(xt.reshape(G, Tg, D), cfg, dim=0)
    wg = _constrain_groups(weights.reshape(G, Tg, K), cfg, dim=0)
    eg = _constrain_groups(experts.reshape(G, Tg, K), cfg, dim=0)

    def dispatch_combine(xg1, wg1, eg1):
        """One group's scatter → expert buffers → gather."""
        flat_exp = eg1.reshape(-1)                          # (Tg*K,)
        onehot = jax.nn.one_hot(flat_exp, E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1
        pos_in_exp = jnp.take_along_axis(pos, flat_exp[:, None], axis=1)[:, 0]
        keep = pos_in_exp < capacity
        slot = flat_exp * capacity + jnp.where(keep, pos_in_exp, 0)
        flat_w = wg1.reshape(-1) * keep
        token_idx = jnp.repeat(jnp.arange(Tg), K)
        buf = jnp.zeros((E * capacity, D), x.dtype)
        contrib = jnp.where(keep[:, None], xg1[token_idx], 0)
        buf = buf.at[slot].add(contrib)
        return buf.reshape(E, capacity, D), (slot, flat_w, keep, token_idx)

    eb, combine_info = jax.vmap(dispatch_combine)(xg, wg, eg)  # (G,E,C,D)
    # expert-parallel layout when E divides the group count (dbrx: 16/16):
    # dispatch buffers flip from G-sharded to E-sharded — ONE explicit
    # all-to-all instead of XLA's fallback gather of the whole buffer —
    # compute runs where the expert weights live, then flip back
    ep_layout = G > 1 and E % G == 0
    eb = _constrain_groups(eb, cfg, dim=0)   # scatter completes G-local...
    if ep_layout:
        eb = _constrain_groups(eb, cfg, dim=1)   # ...then ONE relayout to E

    # expert FFN (batched over experts — shards on expert/model axes)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", eb, p["w_gate"]))
    h = h * jnp.einsum("gecd,edf->gecf", eb, p["w_up"])
    eo = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    eo = _constrain_groups(eo, cfg, dim=0)

    def combine(eo1, info):
        slot, flat_w, keep, token_idx = info
        flat = eo1.reshape(E * capacity, D)
        gathered = flat[slot] * flat_w[:, None].astype(x.dtype)
        return jnp.zeros((Tg, D), x.dtype).at[token_idx].add(
            jnp.where(keep[:, None], gathered, 0))

    y = jax.vmap(combine)(eo, combine_info).reshape(T, D)

    if "shared" in p:
        y = y + run_mlp(p["shared"], xt)

    # load-balance auxiliary loss (Switch-style)
    me = jnp.mean(probs, axis=0)                              # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(experts[:, 0], E, dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * E * cfg.router_aux_weight
    return y.reshape(B, S, D), aux
