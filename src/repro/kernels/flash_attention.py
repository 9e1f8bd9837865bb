"""Flash attention as a Pallas TPU kernel.

TPU adaptation of the streaming-softmax algorithm: the grid is
(batch, heads, q_blocks, k_blocks) with the k dimension innermost and
sequential; running (acc, m, l) live in VMEM scratch across k steps, so HBM
traffic is one pass over K/V per q block and the S×S matrix never exists.
Block shapes are MXU-aligned (q/k blocks multiples of 128 on the lane dim,
head_dim on the sublane dim).

Validated with interpret=True on CPU against ``ref.attention_ref``
(this container has no TPU); on TPU the same pallas_call lowers to Mosaic.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ = 256
DEFAULT_BK = 256


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  causal: bool, window: int, bq: int, bk: int, nk: int,
                  q_offset: int, scale: float):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (bq, bk)
    if causal:
        iq = pl.program_id(2)
        qpos = (q_offset + iq * bq
                + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = kpos <= qpos
        if window > 0:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                               # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                      # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = (acc_ref[...] * alpha
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                         interpret: bool) -> jax.Array:
    """q,k,v: (B, H, S, hd).  Returns (B, H, Sq, hd)."""
    B, H, Sq, hd = q.shape
    Sk = k.shape[2]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    nq, nk = Sq // bq, Sk // bk
    q_offset = Sk - Sq if causal else 0
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, bq=bq, bk=bk, nk=nk,
        q_offset=q_offset, scale=scale)
    grid = (B, H, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, hd), jnp.float32),     # acc
            pltpu.VMEM((bq, 1), jnp.float32),      # running max m
            pltpu.VMEM((bq, 1), jnp.float32),      # running sum l
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
