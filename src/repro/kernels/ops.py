"""jit'd public wrappers around the Pallas kernels.

Model code calls these; layout transposes and interpret-mode selection
(CPU = interpret, TPU = compiled Mosaic) live here, so the kernels stay
pure grid/BlockSpec code.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_bhsd
from .mlstm_scan import mlstm_scan_bhsd
from .moe_gating import moe_gating_tokens
from .paged_attention import paged_attention_jnp, paged_attention_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0) -> jax.Array:
    """q,k,v: (B, S, H, hd) (kv already head-repeated) → (B, S, H, hd)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               interpret=_interpret())
    return jnp.swapaxes(out, 1, 2)


@functools.partial(jax.jit, static_argnames=("k",))
def moe_gating(logits: jax.Array, k: int,
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """logits: (T, E) → (weights (T,k), experts (T,k) int32, probs (T,E))."""
    return moe_gating_tokens(logits.astype(jnp.float32), k,
                             interpret=_interpret())


@jax.jit
def _paged_pallas(q, k_pool, v_pool, block_tables, lengths, k_new, v_new):
    return paged_attention_pallas(q, k_pool, v_pool, block_tables, lengths,
                                  k_new, v_new, interpret=_interpret())


@jax.jit
def _paged_jnp(q, k_pool, v_pool, block_tables, lengths, k_new, v_new):
    return paged_attention_jnp(q, k_pool, v_pool, block_tables, lengths,
                               k_new, v_new)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lengths,
                           k_new, v_new):
    """Single-query decode attention over a paged KV pool.

    q (M,H,hd); pools (P,page,Hk,hd);
    block_tables (M,NP) int32; lengths (M,) cached tokens; k/v_new
    (M,Hk,hd) the current token (attended at position ``lengths``).
    On TPU this runs the Pallas kernel (block-table scalar prefetch);
    on CPU the vectorized gather formulation — interpret-mode pallas is
    orders of magnitude too slow for a serving hot loop.
    """
    fn = _paged_pallas if jax.default_backend() == "tpu" else _paged_jnp
    return fn(q, k_pool, v_pool, block_tables, lengths, k_new, v_new)


@functools.partial(jax.jit, static_argnames=("chunk",))
def mlstm_scan(q, k, v, log_i, log_f, C0, n0, m0, *, chunk: int = 256):
    """Chunked mLSTM over (B,H,S,hd) inputs (k pre-scaled by 1/sqrt(hd))."""
    return mlstm_scan_bhsd(q, k, v, log_i, log_f, C0, n0, m0,
                           chunk=chunk, interpret=_interpret())
