"""Paged single-query decode attention as a Pallas TPU kernel.

The continuous-batching engine (``serving/batch.py``) keeps every slot's
KV cache in a shared page pool: ``k_pool``/``v_pool`` are ``(P, page,
Hk, hd)`` and each slot owns a list of page ids (its *block table*).
One decode step is then single-query attention per slot over that slot's
pages — the PagedAttention formulation.  The grid is ``(slot, kv_page)``
with the page dimension innermost and sequential; a running
``(acc, m, l)`` online-softmax state lives in VMEM scratch across pages.

Block tables are data-dependent indices, so the pool BlockSpecs index
through a scalar-prefetch operand (``PrefetchScalarGridSpec``): the
index map reads ``block_tables[slot, page]`` and the pipeline fetches
exactly the pages each slot owns — never the whole pool.

The *current* token's ``k/v`` (freshly projected this step, not yet
written back to the pool) is folded into the softmax at page 0 by
initialising the running state with its contribution: ``m = s_self``,
``l = 1``, ``acc = v_new``.  Pool positions ``>= length`` are masked, so
stale page contents (including the just-allocated page the engine will
write this token into *after* the call) never leak into the output.

``paged_latent_attention_jnp`` is the same gather-based decode over
latent-attention (MLA) pools, whose pages hold one row per token: the
row is the key and its first ``value_dim`` numbers the value, so each
cached row is read once.  Where the row's widths allow, the pool keeps
a row's latent and its rope key in two arrays of whole lane tiles
(``rope_pool``), so the engine writes them in place.

``paged_attention_jnp`` is the gather-based reference formulation used
on CPU (Pallas interpret mode is far too slow for the serving hot loop)
and by tests; it reproduces ``models/common.attention_scores`` decode
numerics exactly (same additive -1e9 mask, fp32 einsum, softmax) so
greedy decode through the paged path matches the dense-cache path
token-for-token.

Validated with interpret=True on CPU against ``ref.attention_ref``
(this container has no TPU); on TPU the same pallas_call lowers to
Mosaic.
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


# ======================================================================
# jnp reference path (CPU serving + test oracle)
# ======================================================================

def paged_attention_jnp(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        block_tables: jax.Array, lengths: jax.Array,
                        k_new: jax.Array, v_new: jax.Array) -> jax.Array:
    """Gather-based paged decode attention.

    q:            (M, H, hd)   one query per slot
    k/v_pool:     (P, page, Hk, hd)
    block_tables: (M, NP) int32 pool page ids (padded entries masked out)
    lengths:      (M,) int32   cached tokens per slot (query position)
    k/v_new:      (M, Hk, hd)  this step's k/v, attended at position
                  ``lengths`` (the engine writes it to the pool after)

    Returns (M, H, hd).  Matches the dense-cache decode path of
    ``models/common.run_attention`` bit-for-bit: the
    gathered cache is laid out exactly like the dense cache (new token
    scattered at index ``lengths``), masked additively with -1e9, and
    reduced with the same fp32 einsum/softmax contractions.
    """
    M, H, hd = q.shape
    P, page, Hk, _ = k_pool.shape
    NP = block_tables.shape[1]
    T = NP * page
    kg = k_pool[block_tables]                      # (M, NP, page, Hk, hd)
    vg = v_pool[block_tables]
    kg = kg.reshape(M, T, Hk, hd).astype(jnp.float32)
    vg = vg.reshape(M, T, Hk, hd).astype(jnp.float32)
    # place the current token at its true cache index so the layout (and
    # therefore the reduction order) matches the dense decode path
    scatter = jax.vmap(
        lambda c, n, l: jax.lax.dynamic_update_slice(c, n[None], (l, 0, 0)))
    kg = scatter(kg, k_new.astype(jnp.float32), lengths)
    vg = scatter(vg, v_new.astype(jnp.float32), lengths)
    kpos = jnp.arange(T, dtype=jnp.int32)
    amask = jnp.where(kpos[None] <= lengths[:, None], 0.0,
                      -1e9).astype(jnp.float32)    # (M, T)
    rep = H // Hk
    kk = jnp.repeat(kg, rep, axis=2)               # (M, T, H, hd)
    vv = jnp.repeat(vg, rep, axis=2)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("mhd,mthd->mht", q.astype(jnp.float32), kk) * scale
    logits = logits + amask[:, None, :]
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("mht,mthd->mhd", probs, vv)
    return out.astype(q.dtype)


def paged_latent_attention_jnp(q: jax.Array, pool: jax.Array,
                               block_tables: jax.Array, lengths: jax.Array,
                               row_new: jax.Array, value_dim: int,
                               scale: float,
                               rope_pool: Optional[jax.Array] = None,
                               ) -> jax.Array:
    """Gather-based paged decode attention over latent rows (MLA).

    q:            (M, H, W)    one latent-space query per slot and head
    pool:         (P, page, W) fp32 latent rows ``[c, k_pe]``
    block_tables: (M, NP) int32 pool page ids (padded entries masked out)
    lengths:      (M,) int32   cached tokens per slot (query position)
    row_new:      (M, W)       this step's row, attended at ``lengths``

    Each cached row is gathered once and serves as key (all ``W``
    numbers) and as value (its first ``value_dim``).  Returns ``(M, H,
    value_dim)``: per head the softmax-weighted sum of the rows' latents.

    With ``rope_pool`` the rows come split, in whole lane tiles: ``pool``
    holds the latents ``c`` as ``(P, page, value_dim // 128, 128)`` and
    ``rope_pool`` the rope keys ``k_pe`` as ``(P, page // g, 2, 128)``, g
    tokens a slab in order.  The logits are then ``q_c · c + q_pe · k_pe``,
    and the value ``c``: the two parts are never joined into one row, and
    ``c`` keeps its tiles, since a ``(…, 512)`` view of the gathered rows
    would be a copy of them.
    """
    if rope_pool is not None:
        return _split_latent_attention(q, pool, rope_pool, block_tables,
                                       lengths, row_new, value_dim, scale)
    M, H, W = q.shape
    page = pool.shape[1]
    T = block_tables.shape[1] * page
    rows = pool[block_tables].reshape(M, T, W).astype(jnp.float32)
    rows = jax.vmap(lambda c, n, l: jax.lax.dynamic_update_slice(
        c, n[None], (l, 0)))(rows, row_new.astype(jnp.float32), lengths)
    kpos = jnp.arange(T, dtype=jnp.int32)
    amask = jnp.where(kpos[None] <= lengths[:, None], 0.0,
                      -1e9).astype(jnp.float32)    # (M, T)
    logits = jnp.einsum("mhw,mtw->mht", q.astype(jnp.float32), rows) * scale
    probs = jax.nn.softmax(logits + amask[:, None, :], axis=-1)
    out = jnp.einsum("mht,mtc->mhc", probs, rows[..., :value_dim])
    return out.astype(q.dtype)


def _split_latent_attention(q: jax.Array, pool: jax.Array,
                            rope_pool: jax.Array, block_tables: jax.Array,
                            lengths: jax.Array, row_new: jax.Array,
                            value_dim: int, scale: float) -> jax.Array:
    """``paged_latent_attention_jnp`` over a split pool.  The new row
    joins the softmax as a term of its own: written into the gathered
    rows, it would be a loop of small operations, slot by slot."""
    M, H, W = q.shape
    T = block_tables.shape[1] * pool.shape[1]
    tile = pool.shape[2:]                          # (value_dim // 128, 128)
    qf, new = q.astype(jnp.float32), row_new.astype(jnp.float32)
    q_c = qf[..., :value_dim].reshape((M, H) + tile)
    q_pe = qf[..., value_dim:]
    c_new = new[:, :value_dim].reshape((M,) + tile)
    c = pool[block_tables].reshape((M, T) + tile)
    pe = rope_pool[block_tables].reshape(M, T, W - value_dim)
    kpos = jnp.arange(T, dtype=jnp.int32)
    cached = (jnp.einsum("mhkl,mtkl->mht", q_c, c)
              + jnp.einsum("mhr,mtr->mht", q_pe, pe)) * scale
    cached = cached + jnp.where(kpos[None] < lengths[:, None], 0.0,
                                -1e9).astype(jnp.float32)[:, None, :]
    own = (jnp.einsum("mhkl,mkl->mh", q_c, c_new)
           + jnp.einsum("mhr,mr->mh", q_pe, new[:, value_dim:])) * scale
    top = jnp.maximum(cached.max(axis=-1), own)
    p, p_own = jnp.exp(cached - top[..., None]), jnp.exp(own - top)
    out = (jnp.einsum("mht,mtkl->mhkl", p, c)
           + p_own[..., None, None] * c_new[:, None]
           ) / (p.sum(axis=-1) + p_own)[..., None, None]
    return out.reshape(M, H, value_dim).astype(q.dtype)


# ======================================================================
# Pallas kernel
# ======================================================================

def _paged_kernel(bt_ref, len_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, page: int, n_pages: int,
                  rep: int, scale: float):
    im = pl.program_id(0)
    ip = pl.program_id(1)
    Hk, hd = k_ref.shape[2], k_ref.shape[3]

    q = q_ref[0].astype(jnp.float32) * scale       # (H, hd)
    q3 = q.reshape(Hk, rep, hd)

    @pl.when(ip == 0)
    def _init():
        # fold the current token in as the initial online-softmax state:
        # it is always attended (query position == lengths[im])
        kn = kn_ref[0].astype(jnp.float32)         # (Hk, hd)
        vn = vn_ref[0].astype(jnp.float32)
        m_ref[...] = jnp.sum(q3 * kn[:, None, :], axis=-1)   # (Hk, rep)
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(vn[:, None, :], acc_ref.shape)

    k = k_ref[0].astype(jnp.float32)               # (page, Hk, hd)
    v = v_ref[0].astype(jnp.float32)
    kT = jnp.transpose(k, (1, 0, 2))               # (Hk, page, hd)
    vT = jnp.transpose(v, (1, 0, 2))
    s = jax.lax.dot_general(q3, kT,
                            (((2,), (2,)), ((0,), (0,))))  # (Hk, rep, page)
    length = len_ref[im]
    kpos = ip * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    s = jnp.where(kpos < length, s, NEG_INF)

    m_prev = m_ref[...]                            # (Hk, rep)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * alpha[..., None]
                    + jax.lax.dot_general(p, vT,
                                          (((2,), (1,)), ((0,), (0,)))))
    m_ref[...] = m_new

    @pl.when(ip == n_pages - 1)
    def _finalize():
        out = acc_ref[...] / l_ref[...][..., None]           # (Hk, rep, hd)
        o_ref[0] = out.reshape(Hk * rep, hd).astype(o_ref.dtype)


def paged_attention_pallas(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, *, interpret: bool) -> jax.Array:
    """Same contract as :func:`paged_attention_jnp`, as a pallas_call."""
    M, H, hd = q.shape
    _, page, Hk, _ = k_pool.shape
    NP = block_tables.shape[1]
    rep = H // Hk
    assert rep * Hk == H, (H, Hk)
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(
        _paged_kernel, page=page, n_pages=NP, rep=rep, scale=scale)
    in_specs = [
        pl.BlockSpec((1, H, hd), lambda m, p, bt, ln: (m, 0, 0)),       # q
        pl.BlockSpec((1, Hk, hd), lambda m, p, bt, ln: (m, 0, 0)),      # k_new
        pl.BlockSpec((1, Hk, hd), lambda m, p, bt, ln: (m, 0, 0)),      # v_new
        pl.BlockSpec((1, page, Hk, hd),
                     lambda m, p, bt, ln: (bt[m, p], 0, 0, 0)),         # k page
        pl.BlockSpec((1, page, Hk, hd),
                     lambda m, p, bt, ln: (bt[m, p], 0, 0, 0)),         # v page
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(M, NP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, hd), lambda m, p, bt, ln: (m, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hk, rep, hd), jnp.float32),   # acc
            pltpu.VMEM((Hk, rep), jnp.float32),       # running max m
            pltpu.VMEM((Hk, rep), jnp.float32),       # running sum l
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, H, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_new, v_new, k_pool, v_pool)
