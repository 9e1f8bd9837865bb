"""Unified decoder-only model covering dense / moe / ssm / hybrid / vlm.

Homogeneous stacks (dense, moe, hybrid, vlm) scan over stacked per-layer
params (MaxText-style) so lowering stays fast at 64 layers; heterogeneous
stacks (the xLSTM mLSTM/sLSTM interleave, and MoE models whose leading
layers are dense) keep a list of per-layer params and use a python loop.

Three entry points per architecture:
  * ``forward``      — full-sequence logits (training / teacher forcing)
  * ``prefill``      — full-sequence + returns a decode-ready cache
  * ``decode_step``  — ONE token against the cache (the serve_step of the
                       decode_32k / long_500k shapes)
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import (Params, causal_mask, constrain_batch,
                     constrain_batch_seq, dense_init, init_attention,
                     init_mlp, rms_norm, run_attention, run_mlp)
from .config import ModelConfig
from .mla import init_mla, run_mla
from .moe import init_moe, run_moe
from .ssm import init_mamba, init_mlstm, init_slstm, run_mamba, run_mlstm, run_slstm

CONV_K = 4


# ======================================================================
# init
# ======================================================================

def blocks_listed(cfg: ModelConfig) -> bool:
    """Whether the layers' params are a list (kinds differ by layer)
    rather than one tree stacked by layer."""
    return cfg.arch == "ssm" or cfg.first_dense_layers > 0


def init_block(cfg: ModelConfig, key: jax.Array, dtype: Any,
               layer: int = 0) -> Params:
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": jnp.ones((cfg.d_model,), dtype)}
    if cfg.arch in ("dense", "vlm", "moe", "hybrid", "audio"):
        p["attn"] = (init_mla(cfg, ks[0], dtype) if cfg.mla
                     else init_attention(cfg, ks[0], dtype))
        p["ln2"] = jnp.ones((cfg.d_model,), dtype)
        if cfg.is_moe_layer(layer):
            p["moe"] = init_moe(cfg, ks[1], dtype)
        else:
            p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, dtype)
        if cfg.arch == "hybrid":
            p["mamba"] = init_mamba(cfg, ks[2], dtype)
    elif cfg.arch == "ssm":
        p["mlstm"] = init_mlstm(cfg, ks[0], dtype)
        if cfg.slstm_every:
            p["slstm"] = init_slstm(cfg, ks[1], dtype)
    else:
        raise ValueError(cfg.arch)
    return p


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: Any = jnp.float32) -> Params:
    return init_stage(cfg, key, 0, cfg.n_layers, True, True, dtype)


def init_stage(cfg: ModelConfig, key: jax.Array, lo: int, hi: int,
               embed: bool, head: bool, dtype: Any = jnp.float32) -> Params:
    """Layers ``[lo, hi)`` of :func:`init_params`, plus the embedding and
    the final norm + output head when asked, without making the rest of
    the model.  One key per layer, so every leaf is bitwise equal to the
    same slice of the whole init (run eagerly, as the whole init is: a
    jitted init may round differently)."""
    ks = jax.random.split(key, 4)
    params: Params = {}
    if embed:
        params["embed"] = dense_init(ks[0], (cfg.vocab, cfg.d_model), dtype,
                                     scale=0.02)
    if head:
        params["final_norm"] = jnp.ones((cfg.d_model,), dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab),
                                           dtype)
    layer_keys = jax.random.split(ks[2], cfg.n_layers)[lo:hi]
    if blocks_listed(cfg):
        params["blocks"] = [init_block(cfg, k, dtype, lo + i)
                            for i, k in enumerate(layer_keys)]
    else:
        params["blocks"] = jax.vmap(
            lambda k: init_block(cfg, k, dtype))(layer_keys)
    return params


def _is_slstm(cfg: ModelConfig, layer: int) -> bool:
    return bool(cfg.slstm_every) and (layer % cfg.slstm_every == cfg.slstm_every - 1)


# ======================================================================
# block application
# ======================================================================

def run_block(cfg: ModelConfig, p: Params, x: jax.Array,
              positions: jax.Array,
              cache: Optional[Dict[str, jax.Array]] = None,
              cache_len: Optional[jax.Array] = None,
              layer_idx: int = 0,
              rows_out: Optional[list] = None,
              ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]], jax.Array]:
    """One transformer-ish block.  Returns (x, new_cache, aux_loss).
    ``rows_out`` collects a MoE layer's rows per held expert (``run_moe``)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: Optional[Dict[str, jax.Array]] = None
    seq_par = (cfg.arch == "ssm" and cfg.seq_segments > 1 and x.shape[1] > 1
               and x.shape[1] % (cfg.seq_segments * 256) == 0
               and not _is_slstm(cfg, layer_idx))
    x = constrain_batch_seq(x, cfg) if seq_par else constrain_batch(x, cfg)
    if cfg.arch in ("dense", "vlm", "moe", "hybrid", "audio"):
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if cfg.mla:
            attn_out, new_ckv = run_mla(
                p["attn"], cfg, h, positions,
                cache["ckv"] if cache is not None else None, cache_len)
        else:
            kv = (cache["k"], cache["v"]) if cache is not None else None
            attn_out, new_kv = run_attention(p["attn"], cfg, h, positions,
                                             kv, cache_len)
        if cfg.arch == "hybrid":
            mstate = ((cache["h"], cache["conv"]) if cache is not None else None)
            ssm_out, new_mstate = run_mamba(p["mamba"], cfg, h, mstate)
            attn_out = 0.5 * (attn_out + ssm_out)
        x = x + attn_out
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.is_moe_layer(layer_idx):
            ffn_out, aux = run_moe(p["moe"], cfg, h,
                                   use_kernel=cfg.use_flash_kernel,
                                   no_drop=cache is not None,
                                   rows_out=rows_out)
        else:
            ffn_out = run_mlp(p["mlp"], h)
        x = x + ffn_out
        if cache is not None and cfg.mla:
            new_cache = {"ckv": new_ckv}
        elif cache is not None:
            new_cache = {"k": new_kv[0], "v": new_kv[1]}
            if cfg.arch == "hybrid":
                new_cache["h"], new_cache["conv"] = new_mstate
    elif cfg.arch == "ssm":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        if _is_slstm(cfg, layer_idx):
            st = ((cache["sc"], cache["sn"], cache["sh"], cache["sm"])
                  if cache is not None else None)
            out, new_st = run_slstm(p["slstm"], cfg, h, st)
            if cache is not None:
                new_cache = dict(cache)
                new_cache.update(zip(("sc", "sn", "sh", "sm"), new_st))
        else:
            st = ((cache["C"], cache["n"], cache["m"])
                  if cache is not None else None)
            out, new_st = run_mlstm(p["mlstm"], cfg, h, st)
            if cache is not None:
                new_cache = dict(cache)
                new_cache.update(zip(("C", "n", "m"), new_st))
        x = x + out
    else:
        raise ValueError(cfg.arch)
    return x, new_cache, aux


# ======================================================================
# full-sequence forward (train / prefill body)
# ======================================================================

def _embed(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
           ) -> Tuple[jax.Array, jax.Array]:
    """Returns (x (B,S,D), positions)."""
    tokens = batch["tokens"]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.arch == "vlm" and "vision_embeds" in batch:
        # stubbed modality frontend: precomputed patch embeddings are
        # prepended to the text sequence (the carve-out in the task spec)
        x = jnp.concatenate([batch["vision_embeds"].astype(x.dtype), x], axis=1)
    B, S = x.shape[0], x.shape[1]
    if cfg.mrope:
        positions = batch.get("positions3")
        if positions is None:
            base = jnp.arange(S)[None].astype(jnp.int32)
            positions = jnp.broadcast_to(base, (3, B, S))
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None].astype(jnp.int32), (B, S))
    return x, positions


def apply_blocks(cfg: ModelConfig, blocks: Any, x: jax.Array,
                 positions: jax.Array, first_layer: int = 0,
                 ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence pass of ``x`` through a stack of blocks (all of the
    model's, or one pipeline stage's starting at ``first_layer``).
    Returns (x, aux_loss)."""
    if blocks_listed(cfg):
        aux = jnp.zeros((), jnp.float32)
        for i, bp in enumerate(blocks):
            x, _, a = run_block(cfg, bp, x, positions,
                                layer_idx=first_layer + i)
            aux = aux + a
        return x, aux

    def body(carry, bp):
        x, aux = carry
        fn = run_block
        if cfg.remat:
            fn = jax.checkpoint(
                functools.partial(run_block), static_argnums=(0,))
        x, _, a = fn(cfg, bp, x, positions)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), blocks)
    return x, aux


def forward(params: Params, cfg: ModelConfig,
            batch: Dict[str, jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence logits.  Returns (logits (B,S,V), aux_loss)."""
    x, positions = _embed(cfg, params, batch)
    x, aux = apply_blocks(cfg, params["blocks"], x, positions)
    x = rms_norm(constrain_batch(x, cfg), params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = x @ head
    if cfg.arch == "vlm" and "vision_embeds" in batch:
        logits = logits[:, batch["vision_embeds"].shape[1]:]
    return logits, aux


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  ) -> Tuple[jax.Array, jax.Array]:
    """Fused CE: never materializes an f32 log-softmax of the full vocab —
    the label logit comes from a one-hot reduction (fuses to iota-compare-
    select-reduce, stays sharded on the vocab axis) and the normalizer is a
    streaming logsumexp."""
    V = logits.shape[-1]
    valid = labels >= 0
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    onehot = jax.nn.one_hot(jnp.maximum(labels, 0), V, dtype=jnp.float32)
    label_logit = jnp.sum(lf * onehot, axis=-1)
    ll = label_logit - lse
    n_valid = jnp.maximum(jnp.sum(valid), 1)
    return -jnp.sum(ll * valid) / n_valid, n_valid


def loss_fn(params: Params, cfg: ModelConfig,
            batch: Dict[str, jax.Array]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = forward(params, cfg, batch)
    ce, n_valid = cross_entropy(logits, batch["labels"])
    loss = ce + aux
    return loss, {"ce": ce, "aux": aux, "n_tokens": n_valid}


# ======================================================================
# decode path
# ======================================================================

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype: Any = jnp.float32) -> Dict[str, Any]:
    """Cache pytree.  For sliding-window archs the KV store is a ring buffer
    of size ``window`` — this is what makes long_500k O(window) not O(seq)."""
    L, Hk, hd, H = cfg.n_layers, cfg.n_kv_heads, cfg.hd, cfg.n_heads
    kv_len = min(max_len, cfg.window) if cfg.window else max_len
    cache: Dict[str, Any] = {"len": jnp.zeros((), jnp.int32)}

    def per_layer() -> Dict[str, jax.Array]:
        c: Dict[str, jax.Array] = {}
        if cfg.mla:
            c["ckv"] = jnp.zeros((batch, kv_len, cfg.latent_dim), dtype)
        elif cfg.arch in ("dense", "vlm", "moe", "hybrid", "audio"):
            c["k"] = jnp.zeros((batch, kv_len, Hk, hd), dtype)
            c["v"] = jnp.zeros((batch, kv_len, Hk, hd), dtype)
        if cfg.arch == "hybrid":
            c["h"] = jnp.zeros((batch, cfg.d_in, cfg.ssm_state), jnp.float32)
            c["conv"] = jnp.zeros((batch, CONV_K - 1, cfg.d_in), dtype)
        if cfg.arch == "ssm":
            d_in = 2 * cfg.d_model
            hd_m = d_in // H
            hd_s = cfg.d_model // H
            c["C"] = jnp.zeros((batch, H, hd_m, hd_m), jnp.float32)
            c["n"] = jnp.zeros((batch, H, hd_m), jnp.float32)
            c["m"] = jnp.zeros((batch, H), jnp.float32)
            c["sc"] = jnp.zeros((batch, H, hd_s), jnp.float32)
            c["sn"] = jnp.zeros((batch, H, hd_s), jnp.float32) + 1e-6
            c["sh"] = jnp.zeros((batch, H, hd_s), jnp.float32)
            c["sm"] = jnp.zeros((batch, H, hd_s), jnp.float32)
        return c

    if cfg.arch == "ssm":
        cache["layers"] = [per_layer() for _ in range(L)]
    else:
        cache["layers"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (L,) + a.shape).copy(), per_layer())
    return cache


def layer_cache(cfg: ModelConfig, layers: Any, i: int) -> Any:
    """Layer ``i``'s cache: an entry of the recurrent stacks' list, else a
    slice of the caches stacked by layer (every attention layer's cache
    has one shape, whatever its params' kind)."""
    if cfg.arch == "ssm":
        return layers[i]
    return jax.tree.map(lambda a: a[i], layers)


def stack_layer_caches(cfg: ModelConfig, caches: list) -> Any:
    """Inverse of :func:`layer_cache` over every layer."""
    if cfg.arch == "ssm":
        return caches
    return jax.tree.map(lambda *xs: jnp.stack(xs), *caches)


def _apply_layers_cached(params: Params, cfg: ModelConfig, x: jax.Array,
                         positions: jax.Array, cache: Dict[str, Any],
                         ) -> Tuple[jax.Array, Dict[str, Any]]:
    cache_len = cache["len"]
    if blocks_listed(cfg):
        new_layers = []
        for i, bp in enumerate(params["blocks"]):
            x, nc, _ = run_block(cfg, bp, x, positions,
                                 layer_cache(cfg, cache["layers"], i),
                                 cache_len, layer_idx=i)
            new_layers.append(nc)
        new_cache: Dict[str, Any] = {"layers": stack_layer_caches(cfg,
                                                                  new_layers)}
    else:
        def body(carry, inputs):
            x = carry
            bp, lc = inputs
            x, nc, _ = run_block(cfg, bp, x, positions, lc, cache_len)
            return x, nc

        x, new_layer_caches = jax.lax.scan(
            body, x, (params["blocks"], cache["layers"]))
        new_cache = {"layers": new_layer_caches}
    new_cache["len"] = cache_len + x.shape[1]
    return x, new_cache


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
            cache: Dict[str, Any]) -> Tuple[jax.Array, Dict[str, Any]]:
    """Run the prompt through the model, filling the cache.
    Returns (last-position logits (B,V), cache)."""
    x, positions = _embed(cfg, params, batch)
    x, cache = _apply_layers_cached(params, cfg, x, positions, cache)
    x = rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return (x @ head)[:, 0], cache


def decode_step(params: Params, cfg: ModelConfig, token: jax.Array,
                cache: Dict[str, Any]) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step: token (B,) int32 → (logits (B,V), cache)."""
    B = token.shape[0]
    x = jnp.take(params["embed"], token[:, None], axis=0)
    pos = jnp.broadcast_to(cache["len"][None, None], (B, 1)).astype(jnp.int32)
    if cfg.mrope:
        positions = jnp.broadcast_to(pos[None], (3, B, 1))
    else:
        positions = pos
    x, cache = _apply_layers_cached(params, cfg, x, positions, cache)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return (x @ head)[:, 0], cache
