"""DeepSeek-V2 family (arXiv:2405.04434; the published ``config.json`` and
modelling code): the benchmark's weight maker, plain reference and
operation and byte counts.

Imports nothing of the system under test.  The weights are the
benchmark's input: made from the seed on the device, in one jitted call,
in the tree layout the system's pipeline shards read.  Layers differ in
kind, so ``blocks`` is a list with one tree per layer::

    {"blocks": [{"ln1", "ln2",
                 "attn": {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"},
                 "mlp": {"w_gate", "w_up", "w_down"}         # dense layers
                 or "moe": {"router" (D, E), "w_gate", "w_up", "w_down"
                            (held experts stacked), "shared": {...}}}, ...],
     "embed" (first shard), "final_norm", "lm_head" (last shard)}

The reference is the published block written out in ``jax.numpy``:
pre-norm RMSNorm; latent attention without q-LoRA, keys and values made
per head from the normed latent (``kv_a_layernorm(c) @ kv_b_proj``, not
the absorbed form the served decode uses); rope on the interleaved pairs
of the rope dimensions with YaRN's inverse frequencies and softmax scale
``(nope + rope) ** -0.5 * mscale ** 2``; the leading dense SwiGLU layers;
then MoE layers: a float32 softmax router over all ``E`` published
experts, the top ``num_experts_per_tok`` kept without renormalisation
(``norm_topk_prob`` false) and scaled by ``routed_scaling_factor``, this
chip's held experts (ids ``0 .. n_routed_experts - 1``) each weighted by
its gate, plus the shared experts as one SwiGLU; final RMSNorm and the
untied head.  What the absent experts would add is left out, as in the
program.  No cache, no batching of sessions, no kernels; float32 at
``highest`` matmul precision unless a control asks for less.
"""

from __future__ import annotations

import functools
import json
import math
import sys
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# ------------------------------------------------------------------ shapes
def router_width(c: Dict[str, Any]) -> int:
    """Experts the router scores: the published count (``reduced`` gives
    it when ``n_routed_experts`` counts the experts held here)."""
    cut = c.get("reduced", {}).get("n_routed_experts")
    return cut[0] if cut else c["n_routed_experts"]


def dims(c: Dict[str, Any]) -> Dict[str, int]:
    """The sizes the weight maker, the reference and the counts read."""
    return {"D": c["hidden_size"], "H": c["num_attention_heads"],
            "C": c["kv_lora_rank"], "N": c["qk_nope_head_dim"],
            "R": c["qk_rope_head_dim"], "Vd": c["v_head_dim"],
            "Fd": c["intermediate_size"], "Fe": c["moe_intermediate_size"],
            "E": router_width(c), "X": c["n_routed_experts"],
            "K": c["num_experts_per_tok"], "Sh": c["n_shared_experts"],
            "V": c["vocab_size"], "L": c["num_hidden_layers"],
            "first": c["first_k_dense_replace"]}


def plan(n_layers: int, n_shards: int) -> List[Tuple[int, int]]:
    """Contiguous layer ranges, as even as possible, earlier shards first."""
    base, rem = divmod(n_layers, n_shards)
    out, lo = [], 0
    for i in range(n_shards):
        hi = lo + base + (1 if i < rem else 0)
        out.append((lo, hi))
        lo = hi
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, beyond 32 bits too."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


def is_dense(c: Dict[str, Any], layer: int) -> bool:
    return layer < c["first_k_dense_replace"]


# ------------------------------------------------------------------- counts
# Operations and bytes from the config's shapes: what the work needs, not
# what the program happens to compute (padded rows and slots, and held
# experts that no token picked, count nothing).  A multiply-add is 2
# operations.  ``prefill_flops``, ``decode_flops`` and ``fused_step`` are
# what the harness reads of every family.
_BYTES = {"float32": 4, "bfloat16": 2}


def attention_params(c: Dict[str, Any]) -> int:
    """Latent attention's weights: q, the latent and rope-key projection,
    the latent's norm, the per-head key/value up-projection, o."""
    d = dims(c)
    D, H, C, N, R, Vd = d["D"], d["H"], d["C"], d["N"], d["R"], d["Vd"]
    return (D * H * (N + R) + D * (C + R) + C + C * H * (N + Vd)
            + H * Vd * D)


def expert_params(c: Dict[str, Any]) -> int:
    d = dims(c)
    return 3 * d["D"] * d["Fe"]


def layer_params(c: Dict[str, Any], layer: int) -> int:
    """Weights held of one block: attention, two norms, and the dense
    SwiGLU, or the router, the held experts and the shared experts."""
    d = dims(c)
    D = d["D"]
    base = attention_params(c) + 2 * D
    if is_dense(c, layer):
        return base + 3 * D * d["Fd"]
    return (base + D * d["E"] + d["X"] * expert_params(c)
            + d["Sh"] * expert_params(c))


def layer_touched(c: Dict[str, Any], layer: int) -> float:
    """Weights one token uses in a block: everything but the held
    experts, of which it reaches ``K · X / E`` on average."""
    d = dims(c)
    if is_dense(c, layer):
        return layer_params(c, layer)
    return (layer_params(c, layer) - d["X"] * expert_params(c)
            + d["K"] * d["X"] / d["E"] * expert_params(c))


def params(c: Dict[str, Any]) -> int:
    d = dims(c)
    return (sum(layer_params(c, l) for l in range(d["L"]))
            + 2 * d["V"] * d["D"] + d["D"])


def head_flops(c: Dict[str, Any]) -> int:
    d = dims(c)
    return 2 * d["D"] * d["V"]


def prefill_attention_flops(c: Dict[str, Any], pos: int) -> int:
    """Per layer, a query at ``pos`` against ``pos + 1`` keys with keys
    and values per head: scores over ``nope + rope``, values of ``v``."""
    d = dims(c)
    return 2 * d["H"] * (d["N"] + d["R"] + d["Vd"]) * (pos + 1)


def decode_attention_flops(c: Dict[str, Any], pos: int) -> int:
    """Per layer, the absorbed form of the served decode: scores over the
    ``pos + 1`` latent rows (``C + R``) and the weighted sum of their
    latents (``C``), per head."""
    d = dims(c)
    return 2 * d["H"] * (2 * d["C"] + d["R"]) * (pos + 1)


def _layers(c: Dict[str, Any], n_layers: int, first: bool) -> List[int]:
    """Global indices of a shard's layers, as far as their kind goes: the
    first shard starts at layer 0, any other holds MoE layers only (no
    shard but the first reaches the leading dense layers)."""
    lo = 0 if first else c["first_k_dense_replace"]
    return list(range(lo, lo + n_layers))


def token_flops(c: Dict[str, Any], pos: int, decode: bool,
                layers: Sequence[int] = ()) -> float:
    """One token at position ``pos`` through ``layers`` (default: all)."""
    layers = list(layers) or list(range(c["num_hidden_layers"]))
    att = (decode_attention_flops if decode else prefill_attention_flops)(c, pos)
    return 2 * sum(layer_touched(c, l) for l in layers) + len(layers) * att


def prefill_flops(c: Dict[str, Any], prompt_len: int) -> float:
    """A prompt through the whole model, and the head once at its end:
    ``sum(token_flops(c, p, False) for p in range(prompt_len))`` plus the
    head, in closed form."""
    S, L = prompt_len, c["num_hidden_layers"]
    return (S * token_flops(c, -1, False)
            + L * prefill_attention_flops(c, 0) * S * (S + 1) // 2
            + head_flops(c))


def decode_flops(c: Dict[str, Any], pos: int) -> float:
    """The token fed at position ``pos``, through the whole model and the
    head: the operations behind one served token after the first."""
    return token_flops(c, pos, True) + head_flops(c)


def kv_bytes_per_token(c: Dict[str, Any], n_layers: int) -> int:
    """One latent row (``C + R`` numbers) per layer."""
    d = dims(c)
    return n_layers * (d["C"] + d["R"]) * _BYTES[c["serving"]["kv_dtype"]]


def fused_step(c: Dict[str, Any], n_layers: int, first: bool, last: bool,
               lengths: Sequence[int], param_bytes: int) -> Dict[str, float]:
    """Operations and HBM bytes of one shard's batched decode step over
    the live rows with ``lengths`` cached tokens each: every weight of
    the shard's blocks but the held experts read once; of each MoE
    layer's held experts, the expected number that at least one of the
    ``B`` rows picks, ``X · (1 - (1 - K/E) ** B)``; the head once if
    last, the embedding rows if first; each row's cached latent rows read
    and its new one written.  ``param_bytes`` is the served weights'
    bytes per number."""
    d = dims(c)
    D, V, pb = d["D"], d["V"], param_bytes
    rows = len(lengths)
    layers = _layers(c, n_layers, first)
    flops = (rows * token_flops(c, -1, True, layers)
             + n_layers * sum(decode_attention_flops(c, n) for n in lengths))
    reached = d["X"] * (1.0 - (1.0 - d["K"] / d["E"]) ** rows)
    weights = 0.0
    for l in layers:
        weights += layer_params(c, l)
        if not is_dense(c, l):
            weights -= (d["X"] - reached) * expert_params(c)
    weights *= pb
    if last:
        flops += rows * head_flops(c)
        weights += (D * V + D) * pb
    if first:
        weights += rows * D * pb
    kv = kv_bytes_per_token(c, n_layers) * (sum(lengths) + rows)
    return {"flops": flops, "bytes": weights + kv}


# ------------------------------------------------------------------ weights
def _leaf(key: jax.Array, shape: Tuple[int, ...], std: float,
          dtype: Any) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _swiglu(key: jax.Array, D: int, F: int, dtype: Any) -> Params:
    k = jax.random.split(key, 3)
    return {"w_gate": _leaf(k[0], (D, F), D ** -0.5, dtype),
            "w_up": _leaf(k[1], (D, F), D ** -0.5, dtype),
            "w_down": _leaf(k[2], (F, D), F ** -0.5, dtype)}


def _layer(c: Dict[str, Any], layer: int, key: jax.Array, dtype: Any) -> Params:
    d = dims(c)
    D, H, C, N, R, Vd = d["D"], d["H"], d["C"], d["N"], d["R"], d["Vd"]
    k = jax.random.split(key, 8)
    ones = jnp.ones((D,), dtype)
    p: Params = {
        "ln1": ones, "ln2": ones,
        "attn": {"wq": _leaf(k[0], (D, H * (N + R)), D ** -0.5, dtype),
                 "wkv_a": _leaf(k[1], (D, C + R), D ** -0.5, dtype),
                 "kv_norm": jnp.ones((C,), dtype),
                 "wkv_b": _leaf(k[2], (C, H * (N + Vd)), C ** -0.5, dtype),
                 "wo": _leaf(k[3], (H * Vd, D), (H * Vd) ** -0.5, dtype)}}
    if is_dense(c, layer):
        p["mlp"] = _swiglu(k[4], D, d["Fd"], dtype)
        return p
    # expert e always comes from the same key, whichever experts are held
    experts = jax.vmap(lambda e: _swiglu(jax.random.fold_in(k[5], e), D,
                                         d["Fe"], dtype))(jnp.arange(d["X"]))
    p["moe"] = dict(experts,
                    router=_leaf(k[6], (D, d["E"]), D ** -0.5, dtype),
                    shared=_swiglu(k[7], D, d["Sh"] * d["Fe"], dtype))
    return p


def _frozen(c: Dict[str, Any]) -> str:
    """The config as a hashable static argument (nested groups too)."""
    return json.dumps(c, sort_keys=True)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _build(key: jax.Array, fc: str, layer_plan: Tuple, dtype: Any):
    c = json.loads(fc)
    d = dims(c)
    k_embed, k_head, k_layers = jax.random.split(key, 3)
    shards = [{"blocks": [_layer(c, l, jax.random.fold_in(k_layers, l), dtype)
                          for l in range(lo, hi)]}
              for lo, hi in layer_plan]
    shards[0]["embed"] = _leaf(k_embed, (d["V"], d["D"]), 0.02, dtype)
    shards[-1]["final_norm"] = jnp.ones((d["D"],), dtype)
    shards[-1]["lm_head"] = _leaf(k_head, (d["D"], d["V"]), d["D"] ** -0.5,
                                  dtype)
    return shards


def make_shards(c: Dict[str, Any], seed: int,
                layer_plan: Sequence[Tuple[int, int]],
                dtype: Any = jnp.float32) -> List[Params]:
    """Every shard's weights from ``seed``, in one jitted call on the
    default device.  Layer ``l`` always comes from the same key, so the
    values do not depend on the plan."""
    if c.get("tie_word_embeddings"):
        raise ValueError("the DeepSeek-V2 weight maker has an untied head")
    return _build(seed_key(seed), _frozen(c), tuple(map(tuple, layer_plan)),
                  jnp.dtype(dtype))


# ---------------------------------------------------------------- reference
def _rms(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _mscale(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(c: Dict[str, Any]) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    rs = c.get("rope_scaling") or {}
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    if not rs:
        return extra
    f, orig = rs["factor"], rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (extra / f * (1 - mask) + extra * mask).astype(np.float32)


def attention_scale(c: Dict[str, Any]) -> float:
    rs = c.get("rope_scaling") or {}
    s = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    if rs.get("mscale_all_dim"):
        m = _mscale(rs["factor"], rs["mscale_all_dim"])
        s *= m * m
    return s


def _rope(x: jax.Array, inv_freq: np.ndarray) -> jax.Array:
    """x (B, S, ..., r) at positions 0..S-1: the published layout, pairs
    (2i, 2i+1) de-interleaved into halves and the halves rotated."""
    S = x.shape[1]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    b, h = xf[..., 0::2], xf[..., 1::2]
    return jnp.concatenate([b * cos - h * sin, h * cos + b * sin],
                           -1).astype(x.dtype)


def _attention(c: Dict[str, Any], a: Params, h: jax.Array) -> jax.Array:
    d = dims(c)
    B, S, _ = h.shape
    H, C, N, R, Vd = d["H"], d["C"], d["N"], d["R"], d["Vd"]
    inv = yarn_inv_freq(c)
    q = (h @ a["wq"]).reshape(B, S, H, N + R)
    q_nope, q_pe = q[..., :N], _rope(q[..., N:], inv)
    kv = h @ a["wkv_a"]
    lat = _rms(kv[..., :C], a["kv_norm"], c["rms_norm_eps"])
    k_pe = _rope(kv[..., C:], inv)                               # (B, S, R)
    kvb = (lat @ a["wkv_b"]).reshape(B, S, H, N + Vd)
    k_nope, v = kvb[..., :N], kvb[..., N:]
    s = (jnp.einsum("bshn,bthn->bhst", q_nope, k_nope).astype(jnp.float32)
         + jnp.einsum("bshr,btr->bhst", q_pe, k_pe).astype(jnp.float32))
    s = s * attention_scale(c)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhst,bthv->bshv", w, v).reshape(B, S, H * Vd)
    return o @ a["wo"]


def _ffn(p: Params, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def _route(c: Dict[str, Any], m: Params, h: jax.Array,
           precision: Any = None):
    """Float32 router over all experts: top-k ids ``(B, S, K)`` and their
    weights (softmax probabilities, renormalised only if the config says
    so, times ``routed_scaling_factor``).  ``precision`` is the router
    matmul's (default: the surrounding default precision)."""
    logits = jnp.dot(h.astype(jnp.float32), m["router"].astype(jnp.float32),
                     precision=precision)
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = jax.lax.top_k(probs, c["num_experts_per_tok"])
    if c.get("norm_topk_prob"):
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return ids, w * c.get("routed_scaling_factor", 1.0)


def _moe(c: Dict[str, Any], m: Params, h: jax.Array) -> jax.Array:
    ids, w = _route(c, m, h)
    held = jnp.arange(c["n_routed_experts"])
    gate = jnp.sum(jnp.where(ids[..., None] == held, w[..., None], 0.0),
                   axis=-2)                                       # (B,S,X)
    ex = jax.vmap(lambda e: _ffn(e, h))({k: m[k] for k in
                                         ("w_gate", "w_up", "w_down")})
    routed = jnp.einsum("xbsd,bsx->bsd", ex, gate.astype(ex.dtype))
    return routed + _ffn(m["shared"], h)


def _block(c: Dict[str, Any], layer: int, p: Params, x: jax.Array) -> jax.Array:
    eps = c["rms_norm_eps"]
    x = x + _attention(c, p["attn"], _rms(x, p["ln1"], eps))
    h = _rms(x, p["ln2"], eps)
    return x + (_ffn(p["mlp"], h) if is_dense(c, layer) else _moe(c, p["moe"], h))


def _hidden(c: Dict[str, Any], shards: List[Params],
            tokens: jax.Array) -> jax.Array:
    x = jnp.take(shards[0]["embed"], tokens, axis=0)
    layer = 0
    for p in shards:
        for bp in p["blocks"]:
            x = _block(c, layer, bp, x)
            layer += 1
    return x


def _forward_rows(c: Dict[str, Any], shards: List[Params], tokens: jax.Array,
                  rows: jax.Array) -> jax.Array:
    """Logits ``(B, R, V)`` at sequence positions ``rows (B, R)``."""
    x = jnp.take_along_axis(_hidden(c, shards, tokens), rows[..., None],
                            axis=1)
    x = _rms(x, shards[-1]["final_norm"], c["rms_norm_eps"])
    return (x @ shards[-1]["lm_head"]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0,))
def _compare(fc: str, shards: List[Params], tokens: jax.Array,
             rows: jax.Array, scored: jax.Array,
             logits: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """At each position, in standard deviations of the reference's own
    row: the gap by which the scored token's logit lies below the row's
    best, and the root-mean-square and the largest deviation of
    ``logits`` from the row."""
    with jax.default_matmul_precision("highest"):
        z = _forward_rows(json.loads(fc), shards, tokens, rows)
    sd = z.std(-1)
    picked = jnp.take_along_axis(z, scored[..., None], axis=-1)[..., 0]
    d = logits - z
    return ((z.max(-1) - picked) / sd,
            jnp.sqrt(jnp.mean(d * d, axis=-1)) / sd,
            jnp.max(jnp.abs(d), axis=-1) / sd)


@functools.partial(jax.jit, static_argnums=(0,))
def _logits_at(fc: str, shards: List[Params], tokens: jax.Array,
               rows: jax.Array) -> jax.Array:
    return _forward_rows(json.loads(fc), shards, tokens, rows)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _routes(fc: str, shards: List[Params], tokens: jax.Array,
            highest: bool) -> jax.Array:
    """Every MoE layer's top-k expert ids ``(L_moe, B, S, K)``, sorted;
    the router's matmul at ``highest`` precision, the rest at ``highest``
    or at the default precision."""
    c = json.loads(fc)
    with jax.default_matmul_precision("highest" if highest else "default"):
        x = jnp.take(shards[0]["embed"], tokens, axis=0)
        out, layer = [], 0
        eps = c["rms_norm_eps"]
        for p in shards:
            for bp in p["blocks"]:
                if not is_dense(c, layer):
                    h = _rms(x + _attention(c, bp["attn"],
                                            _rms(x, bp["ln1"], eps)),
                             bp["ln2"], eps)
                    out.append(jnp.sort(_route(c, bp["moe"], h,
                                               jax.lax.Precision.HIGHEST)[0],
                                        axis=-1))
                x = _block(c, layer, bp, x)
                layer += 1
    return jnp.stack(out)


def _blocks(n: int, size: int):
    for lo in range(0, n, size):
        yield lo, lo + size


def _pad(a: np.ndarray, size: int) -> np.ndarray:
    """Rows repeated up to a multiple of ``size``: every block one shape."""
    extra = -len(a) % size
    return np.concatenate([a, np.repeat(a[:1], extra, axis=0)]) if extra else a


def reference_compare(c: Dict[str, Any], seed: int, n_shards: int,
                      tokens: np.ndarray, rows: np.ndarray,
                      candidates: Dict[str, Tuple[np.ndarray, np.ndarray]],
                      batch: int) -> Dict[str, Tuple[np.ndarray, ...]]:
    """Float32 reference at ``highest`` precision over ``tokens (N, T)``,
    read at positions ``rows (N, R)``, against each candidate's ``(tokens
    (N, R), logits (N, R, V))``: per position its token's gap and its
    logits' RMS and largest deviation (see ``_compare``).  The weights are
    made again from the seed; sequences go through in blocks of
    ``batch``."""
    shards = make_shards(c, seed, plan(c["num_hidden_layers"], n_shards))
    n, fc = len(tokens), _frozen(c)
    tokens, rows = _pad(tokens, batch), _pad(rows, batch)
    out = {}
    for name, (scored, logits) in candidates.items():
        scored, logits = _pad(scored, batch), _pad(logits, batch)
        parts = [_compare(fc, shards, jnp.asarray(tokens[lo:hi]),
                          jnp.asarray(rows[lo:hi]), jnp.asarray(scored[lo:hi]),
                          jnp.asarray(logits[lo:hi]))
                 for lo, hi in _blocks(len(tokens), batch)]
        out[name] = tuple(np.concatenate([np.asarray(p[i]) for p in parts])[:n]
                          for i in range(3))
    del shards
    return out


def routing_flips(c: Dict[str, Any], seed: int, n_shards: int,
                  tokens: np.ndarray, rows: np.ndarray,
                  batch: int) -> Dict[str, float]:
    """Share of (token, MoE layer) routings, over the positions the
    served requests fed (up to each one's last read row), whose top-k set
    differs from the float32 reference's: ``program`` for the reference
    at the program's precision (float32 weights and activations, matmuls
    at the default precision, the router's at highest), ``control`` for
    the bfloat16 control."""
    fc, lay = _frozen(c), plan(c["num_hidden_layers"], n_shards)
    fed = np.arange(tokens.shape[1])[None] <= rows.max(axis=1)[:, None]
    ids = {}
    for name, dtype, highest in (("ref", jnp.float32, True),
                                 ("program", jnp.float32, False),
                                 ("control", jnp.bfloat16, False)):
        shards = make_shards(c, seed, lay, dtype=dtype)
        ids[name] = np.concatenate(
            [np.asarray(_routes(fc, shards, jnp.asarray(t), highest))
             for t in (_pad(tokens, batch)[lo:hi]
                       for lo, hi in _blocks(len(tokens), batch))],
            axis=1)[:, :len(tokens)]
        del shards
    return {name: float(np.any(ids["ref"] != ids[name], axis=-1)[:, fed].mean())
            for name in ("program", "control")}


def control_logits(c: Dict[str, Any], seed: int, n_shards: int,
                   tokens: np.ndarray, rows: np.ndarray,
                   batch: int) -> np.ndarray:
    """The control: the reference computed in bfloat16 (weights and
    activations; norms, the router and softmax statistics in float32),
    read at the same positions of the same prompts and served tokens.
    Logs to standard error the share of routings that it, and the
    reference at the program's precision, flip against the float32
    reference (``routing_flips``)."""
    shards = make_shards(c, seed, plan(c["num_hidden_layers"], n_shards),
                         dtype=jnp.bfloat16)
    n, fc = len(tokens), _frozen(c)
    tokens_p, rows_p = _pad(tokens, batch), _pad(rows, batch)
    out = [np.asarray(_logits_at(fc, shards, jnp.asarray(tokens_p[lo:hi]),
                                 jnp.asarray(rows_p[lo:hi])))
           for lo, hi in _blocks(len(tokens_p), batch)]
    del shards
    flips = routing_flips(c, seed, n_shards, tokens, rows, batch)
    print(f"[deepseek_v2] routing flips against the float32 reference, "
          f"share of (token, MoE layer) routings: {flips!r}",
          file=sys.stderr, flush=True)
    return np.concatenate(out)[:n]
