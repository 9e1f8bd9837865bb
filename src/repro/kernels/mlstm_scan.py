"""Chunkwise mLSTM recurrence as a Pallas TPU kernel.

The xLSTM matrix-memory cell, tiled for VMEM: the grid is
(batch, heads, chunks) with the chunk dimension sequential; the running
state (C: hd×hd f32, n: hd, m: scalar) lives in VMEM scratch across chunk
steps, so HBM sees one pass over q/k/v/gates and one (W, hd) output tile
per chunk — never the (S, S) decay matrix (it exists only per-chunk, W×W,
in VMEM).  All gate math is done in log-space with the exp(-m) scaling
convention, matching the decode recurrence bit-for-bit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
DEFAULT_CHUNK = 256


def _mlstm_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, C0_ref, n0_ref, m0_ref,
                  h_ref, Cout_ref, nout_ref, mout_ref,
                  C_s, n_s, m_s, *, W: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        C_s[...] = C0_ref[0, 0].astype(jnp.float32)
        n_s[...] = n0_ref[0, 0].astype(jnp.float32).reshape(n_s.shape)
        m_s[...] = m0_ref[0].astype(jnp.float32).reshape(m_s.shape)

    q = q_ref[0, 0].astype(jnp.float32)                  # (W, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    li = li_ref[0, 0].astype(jnp.float32).reshape(W, 1)  # (W,1)
    lf = lf_ref[0, 0].astype(jnp.float32).reshape(W, 1)

    Cp = C_s[...]
    np_ = n_s[...]                                       # (1, hd)
    mp = m_s[...]                                        # (1, 1)

    F = jnp.cumsum(lf, axis=0)                           # (W,1)
    logD = F - F.reshape(1, W) + li.reshape(1, W)        # (W,W): F_t - F_s + i_s
    row = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
    logD = jnp.where(col <= row, logD, NEG)
    m_intra = jnp.max(logD, axis=1, keepdims=True)       # (W,1)
    b_inter = F + mp                                     # (W,1)
    m_t = jnp.maximum(m_intra, b_inter)
    Dm = jnp.exp(logD - m_t)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * Dm
    num = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())))
    den = jnp.sum(scores, axis=1, keepdims=True)         # (W,1)
    w_int = jnp.exp(b_inter - m_t)                       # (W,1)
    num = num + w_int * jax.lax.dot_general(
        q, Cp, (((1,), (0,)), ((), ())))                 # (W,hd)
    den = den + w_int * jnp.sum(q * np_, axis=1, keepdims=True)
    norm = jnp.maximum(jnp.abs(den), jnp.exp(-m_t))
    h_ref[0, 0] = (num / norm).astype(h_ref.dtype)

    # ---- state update ------------------------------------------------------
    Ft = F[W - 1:W]                                      # (1,1)
    inc = Ft - F + li                                    # (W,1): F_T - F_s + i_s
    m_next = jnp.maximum(mp + Ft, jnp.max(inc, axis=0, keepdims=True))
    wk = jnp.exp(inc - m_next)                           # (W,1)
    carry = jnp.exp(mp + Ft - m_next)                    # (1,1)
    C_s[...] = carry * Cp + jax.lax.dot_general(
        k * wk, v, (((0,), (0,)), ((), ())))             # (hd,hd)
    n_s[...] = carry * np_ + jnp.sum(k * wk, axis=0, keepdims=True)
    m_s[...] = m_next

    @pl.when(ic == nc - 1)
    def _final():
        Cout_ref[0, 0] = C_s[...]
        nout_ref[0, 0] = n_s[...].reshape(nout_ref.shape[2:])
        mout_ref[0] = m_s[...].reshape(mout_ref.shape[1:])


def mlstm_scan_bhsd(q: jax.Array, k: jax.Array, v: jax.Array,
                    log_i: jax.Array, log_f: jax.Array,
                    C0: jax.Array, n0: jax.Array, m0: jax.Array, *,
                    chunk: int = DEFAULT_CHUNK, interpret: bool):
    """q,k,v: (B,H,S,hd) with k pre-scaled; log_i/log_f: (B,H,S);
    C0: (B,H,hd,hd), n0: (B,H,hd), m0: (B,H).
    Returns (h (B,H,S,hd), C_T, n_T, m_T)."""
    B, H, S, hd = q.shape
    W = min(chunk, S)
    assert S % W == 0, (S, W)
    nc = S // W
    kernel = functools.partial(_mlstm_kernel, W=W, nc=nc)
    grid = (B, H, nc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, W, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, W, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, W, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, W), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, 1, W), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, h, c: (b, h, 0)),
            pl.BlockSpec((1, 1), lambda b, h, c: (b, h)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, W, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, h, c: (b, h, 0)),
            pl.BlockSpec((1, 1), lambda b, h, c: (b, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((hd, hd), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, log_i, log_f, C0, n0, m0)
