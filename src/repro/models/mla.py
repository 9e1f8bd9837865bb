"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434) with YaRN.

Per token the block projects one latent row: ``c`` (``kv_lora_rank``
numbers, RMS-normed) and one rope key ``k_pe`` (``qk_rope_head_dim``
numbers) shared by every head.  The cache keeps that row and nothing
else: ``latent_dim = kv_lora_rank + qk_rope_head_dim`` numbers per token
and layer.  Each head's key is ``[c @ W_UK, k_pe]`` and its value
``c @ W_UV``, where ``W_UK``/``W_UV`` are the two halves of ``wkv_b``.

Two forms of the same attention:

* ``run_mla`` (prefill, dense cache): keys and values are materialised
  per head from the latent rows, as the published modelling code does.
* ``absorbed_query`` / ``absorbed_output`` (decode against the page
  pool): ``W_UK`` is folded into the query and ``W_UV`` applied after the
  weighted sum, so scores and values are taken over the latent rows
  themselves (``repro.kernels.paged_attention.paged_latent_attention_jnp``).

Rope follows the published code's layout: the rope dimensions come in
interleaved pairs ``(2i, 2i+1)``, which it de-interleaves before rotating
halves; ``apply_rope_interleaved`` does both in one.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import dense_init, rms_norm
from .config import ModelConfig

Params = Dict[str, Any]


# ------------------------------------------------------------------- YaRN

def yarn_mscale(scale: float, mscale: float) -> float:
    """``0.1 · mscale · ln(scale) + 1`` (1 when not scaled)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _correction_dim(rotations: float, dim: int, base: float,
                    max_pos: int) -> float:
    return (dim * math.log(max_pos / (rotations * 2 * math.pi))
            / (2 * math.log(base)))


def rope_inv_freq(cfg: ModelConfig) -> jax.Array:
    """Inverse frequencies ``(qk_rope_head_dim / 2,)``: plain rope below
    the correction range, position interpolation by ``rope_factor`` above
    it, and a linear ramp between (YaRN)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if cfg.rope_factor <= 1:
        return extra
    low = max(math.floor(_correction_dim(cfg.yarn_beta_fast, dim, base,
                                         cfg.rope_orig_max_pos)), 0)
    high = min(math.ceil(_correction_dim(cfg.yarn_beta_slow, dim, base,
                                         cfg.rope_orig_max_pos)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                     # 1 where the frequency is kept
    return extra / cfg.rope_factor * (1.0 - keep) + extra * keep


def softmax_scale(cfg: ModelConfig) -> float:
    """``(qk_nope + qk_rope) ** -0.5 · mscale²`` (YaRN's attention
    temperature, applied to the scores)."""
    m = yarn_mscale(cfg.rope_factor, cfg.yarn_mscale)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def apply_rope_interleaved(x: jax.Array, positions: jax.Array,
                           inv_freq: jax.Array) -> jax.Array:
    """x ``(B, S, ..., r)``, positions ``(B, S)``: pairs ``(2i, 2i+1)``
    de-interleaved into halves, then the halves rotated by
    ``position · inv_freq[i]``; the output keeps the halves' layout."""
    ang = positions.astype(jnp.float32)[..., None] * inv_freq    # (B,S,r/2)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# -------------------------------------------------------------- projections

def init_mla(cfg: ModelConfig, key: jax.Array, dtype: Any) -> Params:
    D, H, C = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {"wq": dense_init(ks[0], (D, H * (nope + rope)), dtype),
            "wkv_a": dense_init(ks[1], (D, C + rope), dtype),
            "kv_norm": jnp.ones((C,), dtype),
            "wkv_b": dense_init(ks[2], (C, H * (nope + vd)), dtype),
            "wo": dense_init(ks[3], (H * vd, D), dtype)}


def queries(p: Params, cfg: ModelConfig, h: jax.Array, positions: jax.Array,
            ) -> Tuple[jax.Array, jax.Array]:
    """h ``(B, S, D)`` -> ``q_nope (B, S, H, nope)``, roped ``q_pe (B, S,
    H, rope)``."""
    B, S, _ = h.shape
    nope = cfg.qk_nope_head_dim
    q = (h @ p["wq"]).reshape(B, S, cfg.n_heads, nope + cfg.qk_rope_head_dim)
    return q[..., :nope], apply_rope_interleaved(q[..., nope:], positions,
                                                 rope_inv_freq(cfg))


def latent_rows(p: Params, cfg: ModelConfig, h: jax.Array,
                positions: jax.Array) -> jax.Array:
    """The cached row of each token, ``(B, S, latent_dim)``: the normed
    latent ``c`` then the roped shared key ``k_pe``."""
    C = cfg.kv_lora_rank
    kv = h @ p["wkv_a"]
    c = rms_norm(kv[..., :C], p["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope_interleaved(kv[..., C:], positions, rope_inv_freq(cfg))
    return jnp.concatenate([c, k_pe], axis=-1)


def _split_kv_b(p: Params, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """``W_UK (C, H, nope)`` and ``W_UV (C, H, v)``."""
    w = p["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads,
                           cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


# ---------------------------------------------------------------- prefill

def run_mla(p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
            cache: Optional[jax.Array] = None,
            cache_len: Optional[jax.Array] = None,
            ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Latent attention with keys and values materialised per head.
    Without a cache: causal self-attention over ``x (B, S, D)``.  With a
    dense cache of latent rows ``(B, T, latent_dim)``: write this block's
    rows at ``cache_len`` and attend over the cache.  Returns ``(y,
    new_cache)``."""
    B, S, _ = x.shape
    H, C = cfg.n_heads, cfg.kv_lora_rank
    q_nope, q_pe = queries(p, cfg, x, positions)
    rows = latent_rows(p, cfg, x, positions)
    qpos = jnp.arange(S)
    if cache is not None:
        rows = jax.lax.dynamic_update_slice(cache, rows.astype(cache.dtype),
                                            (0, cache_len, 0))
        qpos = qpos + cache_len
    T = rows.shape[1]
    ok = jnp.arange(T)[None, :] <= qpos[:, None]                 # (S, T)
    w_uk, w_uv = _split_kv_b(p, cfg)
    c, k_pe = rows[..., :C], rows[..., C:]
    k_nope = jnp.einsum("btc,chn->bthn", c, w_uk)
    v = jnp.einsum("btc,chv->bthv", c, w_uv)
    s = (jnp.einsum("bshn,bthn->bhst", q_nope.astype(jnp.float32),
                    k_nope.astype(jnp.float32))
         + jnp.einsum("bshr,btr->bhst", q_pe.astype(jnp.float32),
                      k_pe.astype(jnp.float32))) * softmax_scale(cfg)
    s = s + jnp.where(ok, 0.0, -1e9).astype(jnp.float32)[None, None]
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhst,bthv->bshv", probs, v.astype(jnp.float32))
    y = out.astype(x.dtype).reshape(B, S, H * cfg.v_head_dim) @ p["wo"]
    return y, (rows if cache is not None else None)


# ----------------------------------------------------------------- decode

def absorbed_query(p: Params, cfg: ModelConfig, h: jax.Array,
                   positions: jax.Array) -> jax.Array:
    """h ``(B, D)`` at ``positions (B, 1)`` -> the query in latent space,
    ``(B, H, latent_dim)``: ``q_nope`` folded through ``W_UK``, then
    ``q_pe``; its product with a cached row is the head's score."""
    q_nope, q_pe = queries(p, cfg, h[:, None], positions)
    w_uk, _ = _split_kv_b(p, cfg)
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_uk)
    return jnp.concatenate([q_lat, q_pe[:, 0].astype(q_lat.dtype)], -1)


def absorbed_output(p: Params, cfg: ModelConfig, o_lat: jax.Array) -> jax.Array:
    """The heads' weighted latent sums ``(B, H, kv_lora_rank)`` unfolded
    through ``W_UV`` and projected out: ``(B, D)``."""
    _, w_uv = _split_kv_b(p, cfg)
    o = jnp.einsum("bhc,chv->bhv", o_lat, w_uv)
    return o.reshape(o.shape[0], -1) @ p["wo"]
