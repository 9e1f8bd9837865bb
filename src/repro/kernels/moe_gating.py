"""Fused MoE router gating as a Pallas TPU kernel.

softmax → top-k select → renormalize in one VMEM pass over a token block:
the (T, E) logits are read once from HBM and the (T, E) probability matrix
is produced alongside the (T, K) routing decision without re-reading.  The
top-k loop is a K-step argmax-and-mask (K ≤ 8 statically), written
iota-compare style so it maps onto TPU vector units rather than a sort.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BT = 256
NEG = -1e30


def _gating_kernel(logits_ref, w_ref, idx_ref, probs_ref, *, K: int, E: int):
    x = logits_ref[...].astype(jnp.float32)              # (bt, E)
    m = jnp.max(x, axis=1, keepdims=True)
    p = jnp.exp(x - m)
    denom = jnp.sum(p, axis=1, keepdims=True)
    probs = p / denom
    probs_ref[...] = probs

    lane = jax.lax.broadcasted_iota(jnp.int32, probs.shape, 1)
    sel = probs
    total = jnp.zeros((probs.shape[0], 1), jnp.float32)
    ws = []
    ids = []
    for _ in range(K):
        cur = jnp.max(sel, axis=1, keepdims=True)        # (bt,1)
        is_max = sel >= cur                               # ties: take first
        first = jnp.min(jnp.where(is_max, lane, E), axis=1, keepdims=True)
        ws.append(cur)
        ids.append(first)
        sel = jnp.where(lane == first, NEG, sel)
        total = total + cur
    w = jnp.concatenate(ws, axis=1)                      # (bt,K)
    w_ref[...] = w / jnp.maximum(total, 1e-9)
    idx_ref[...] = jnp.concatenate(ids, axis=1).astype(jnp.int32)


def moe_gating_tokens(logits: jax.Array, k: int, *, bt: int = DEFAULT_BT,
                      interpret: bool):
    """logits: (T, E) → (weights (T,k), experts (T,k) int32, probs (T,E))."""
    T, E = logits.shape
    bt = min(bt, T)
    assert T % bt == 0, (T, bt)
    kernel = functools.partial(_gating_kernel, K=k, E=E)
    return pl.pallas_call(
        kernel,
        grid=(T // bt,),
        in_specs=[pl.BlockSpec((bt, E), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            pl.BlockSpec((bt, k), lambda i: (i, 0)),
            pl.BlockSpec((bt, E), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, k), jnp.float32),
            jax.ShapeDtypeStruct((T, k), jnp.int32),
            jax.ShapeDtypeStruct((T, E), jnp.float32),
        ],
        interpret=interpret,
    )(logits)
