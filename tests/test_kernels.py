"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.mlstm_scan import mlstm_scan_bhsd
from repro.kernels.moe_gating import moe_gating_tokens
from repro.kernels.ref import attention_ref, mlstm_chunk_ref, moe_gating_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOL[jnp.bfloat16 if dtype == jnp.bfloat16 else jnp.float32]


# ------------------------------------------------------------ flash attention

@pytest.mark.parametrize("B,H,Sq,Sk,hd", [
    (1, 1, 128, 128, 64),
    (2, 3, 256, 256, 64),
    (1, 2, 256, 512, 128),     # cross: more keys than queries (cached-ish)
    (2, 2, 512, 512, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 128])
def test_flash_attention_sweep(B, H, Sq, Sk, hd, dtype, window):
    ks = jax.random.split(jax.random.PRNGKey(B * 7 + Sq), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, H, Sk, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, H, Sk, hd), jnp.float32).astype(dtype)
    out = flash_attention_bhsd(q, k, v, causal=True, window=window,
                               bq=128, bk=128, interpret=True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    out = flash_attention_bhsd(q, k, v, causal=False, bq=128, bk=128,
                               interpret=True)
    ref = attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# --------------------------------------------------------------- MoE gating

@pytest.mark.parametrize("T,E,K", [(256, 16, 4), (512, 60, 4), (256, 8, 2),
                                   (1024, 64, 8)])
def test_moe_gating_sweep(T, E, K):
    logits = jax.random.normal(jax.random.PRNGKey(T + E), (T, E)) * 2
    w, idx, p = moe_gating_tokens(logits, K, bt=256, interpret=True)
    wr, ir, pr = moe_gating_ref(logits, K)
    np.testing.assert_allclose(np.asarray(p), np.asarray(pr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w), np.asarray(wr), atol=1e-6)
    assert (np.asarray(idx) == np.asarray(ir)).all()
    # weights sum to 1 and indices are distinct per token
    np.testing.assert_allclose(np.asarray(w).sum(1), 1.0, atol=1e-5)
    assert all(len(set(row)) == K for row in np.asarray(idx))


# ---------------------------------------------------------------- mLSTM scan

@pytest.mark.parametrize("B,H,S,hd,chunk", [
    (1, 1, 128, 64, 64),
    (2, 2, 256, 64, 64),
    (1, 2, 256, 128, 128),
    (2, 1, 512, 256, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mlstm_scan_sweep(B, H, S, hd, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + hd), 5)
    q = jax.random.normal(ks[0], (B, H, S, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, hd), jnp.float32) / np.sqrt(hd)
    v = jax.random.normal(ks[2], (B, H, S, hd), jnp.float32)
    li = jax.random.normal(ks[3], (B, H, S))
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, S)) + 2.0)
    C0 = jnp.zeros((B, H, hd, hd))
    n0 = jnp.zeros((B, H, hd))
    m0 = jnp.full((B, H), -1e30)
    h, C, n, m = mlstm_scan_bhsd(q.astype(dtype), k.astype(dtype),
                                 v.astype(dtype), li, lf, C0, n0, m0,
                                 chunk=chunk, interpret=True)
    hr, Cr, nr, mr = mlstm_chunk_ref(q, k, v, li, lf, C0, n0, m0)
    tol = _tol(dtype) * 8
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(hr, np.float32), atol=tol, rtol=tol)
    # states match in TRUE scale (C·exp(m)) — per-impl m may differ slightly
    np.testing.assert_allclose(
        np.asarray(C * jnp.exp(m)[..., None, None]),
        np.asarray(Cr * jnp.exp(mr)[..., None, None]), atol=tol, rtol=tol)


def test_mlstm_scan_nonzero_initial_state():
    """Chunked scan continuing from a warm state == one long oracle run."""
    B, H, S, hd = 1, 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (B, H, S, hd))
    k = jax.random.normal(ks[1], (B, H, S, hd)) / np.sqrt(hd)
    v = jax.random.normal(ks[2], (B, H, S, hd))
    li = jax.random.normal(ks[3], (B, H, S))
    lf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, S)) + 2.0)
    zero = jnp.zeros((B, H, hd, hd)), jnp.zeros((B, H, hd)), jnp.full((B, H), -1e30)
    # oracle over the full sequence
    hr, *_ = mlstm_chunk_ref(q, k, v, li, lf, *zero)
    # kernel: first half, then second half from the carried state
    h1, C1, n1, m1 = mlstm_scan_bhsd(
        q[:, :, :128], k[:, :, :128], v[:, :, :128],
        li[:, :, :128], lf[:, :, :128], *zero, chunk=64,
        interpret=True)
    h2, *_ = mlstm_scan_bhsd(
        q[:, :, 128:], k[:, :, 128:], v[:, :, 128:],
        li[:, :, 128:], lf[:, :, 128:], C1, n1, m1, chunk=64,
        interpret=True)
    got = jnp.concatenate([h1, h2], axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(hr),
                               atol=1e-4, rtol=1e-4)
