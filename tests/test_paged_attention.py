"""Paged single-query decode attention: jnp path vs the dense oracle,
and Pallas interpret vs jnp across head layouts."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import paged_decode_attention
from repro.kernels.paged_attention import (paged_attention_jnp,
                                           paged_attention_pallas)
from repro.kernels.ref import attention_ref

PAGE = 8
NP = 4          # pages per slot: up to NP*PAGE - 1 cached tokens
HK, REP, HD = 2, 2, 16
HQ = HK * REP

#: ragged slot lengths covering the edge cases: empty cache, one byte
#: short of a page boundary, exactly one full page, and mid-pool
LENGTHS = [0, PAGE - 1, PAGE, 2 * PAGE + 5]


def _pallas_interpret(*args, **kw):
    return paged_attention_pallas(*args, interpret=True, **kw)


def _problem(seed=0, lengths=LENGTHS, pool_pages=None, hk=HK, rep=REP):
    rng = np.random.default_rng(seed)
    M = len(lengths)
    P = pool_pages or (NP * M + 3)
    kp = rng.normal(size=(P, PAGE, hk, HD)).astype(np.float32)
    vp = rng.normal(size=(P, PAGE, hk, HD)).astype(np.float32)
    bt = rng.permutation(P)[: NP * M].reshape(M, NP).astype(np.int32)
    q = rng.normal(size=(M, hk * rep, HD)).astype(np.float32)
    kn = rng.normal(size=(M, hk, HD)).astype(np.float32)
    vn = rng.normal(size=(M, hk, HD)).astype(np.float32)
    return (q, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(bt),
            jnp.asarray(np.asarray(lengths, np.int32)), jnp.asarray(kn),
            jnp.asarray(vn))


def _dense_oracle(q, kp, vp, bt, lengths, kn, vn):
    """Per-slot naive attention over the dense cache each slot *would*
    hold: its pool pages flattened up to ``length`` plus the new token."""
    kp, vp, bt = np.asarray(kp), np.asarray(vp), np.asarray(bt)
    out = np.zeros((len(lengths), HQ, HD), np.float32)
    for m, L in enumerate(np.asarray(lengths)):
        kd = np.concatenate(
            [kp[bt[m]].reshape(-1, HK, HD)[:L], np.asarray(kn)[m][None]], 0)
        vd = np.concatenate(
            [vp[bt[m]].reshape(-1, HK, HD)[:L], np.asarray(vn)[m][None]], 0)
        kd = np.repeat(kd, REP, axis=1)            # GQA share
        vd = np.repeat(vd, REP, axis=1)
        ref = attention_ref(
            jnp.asarray(np.asarray(q)[m][None, :, None, :]),  # (1, HQ, 1, HD)
            jnp.asarray(kd.transpose(1, 0, 2)[None]),
            jnp.asarray(vd.transpose(1, 0, 2)[None]), causal=True)
        out[m] = np.asarray(ref)[0, :, 0]
    return out


def test_jnp_matches_dense_oracle_at_ragged_lengths():
    args = _problem(seed=1)
    got = np.asarray(paged_attention_jnp(jnp.asarray(args[0]), *args[1:]))
    want = _dense_oracle(*args)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_pallas_interpret_matches_jnp():
    args = _problem(seed=2)
    jn = np.asarray(paged_attention_jnp(jnp.asarray(args[0]), *args[1:]))
    pa = np.asarray(paged_attention_pallas(jnp.asarray(args[0]), *args[1:],
                                           interpret=True))
    np.testing.assert_allclose(pa, jn, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("hk,rep", [(1, 4), (4, 1), (2, 4)],
                         ids=["one_kv_head", "mha", "gqa4"])
def test_pallas_interpret_matches_jnp_across_head_layouts(hk, rep):
    """One KV head shared by every query head, one KV head per query head,
    and granite's four query heads per KV head."""
    args = _problem(seed=8, hk=hk, rep=rep)
    jn = np.asarray(paged_attention_jnp(jnp.asarray(args[0]), *args[1:]))
    pa = np.asarray(paged_attention_pallas(jnp.asarray(args[0]), *args[1:],
                                           interpret=True))
    np.testing.assert_allclose(pa, jn, rtol=2e-5, atol=2e-6)


def test_dispatch_wrapper_runs_on_cpu():
    args = _problem(seed=3)
    got = np.asarray(paged_decode_attention(jnp.asarray(args[0]), *args[1:]))
    want = _dense_oracle(*args)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_stale_page_contents_never_leak():
    """Positions >= length — including the padded block-table pages and
    the slot's partially-filled last page — must not affect the output,
    no matter how large the garbage there is."""
    args = _problem(seed=4)
    q, kp, vp, bt, lengths, kn, vn = args
    kp, vp = np.asarray(kp).copy(), np.asarray(vp).copy()
    base = np.asarray(paged_attention_jnp(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), bt, lengths,
        kn, vn))
    # poison every pool position beyond each slot's length
    bt_np, ln = np.asarray(bt), np.asarray(lengths)
    for m in range(len(ln)):
        flat_k = kp[bt_np[m]].reshape(-1, HK, HD)
        flat_v = vp[bt_np[m]].reshape(-1, HK, HD)
        flat_k[ln[m]:] = 1e4
        flat_v[ln[m]:] = -1e4
        kp[bt_np[m]] = flat_k.reshape(NP, PAGE, HK, HD)
        vp[bt_np[m]] = flat_v.reshape(NP, PAGE, HK, HD)
    for fn in (paged_attention_jnp, _pallas_interpret):
        poisoned = np.asarray(fn(jnp.asarray(q), jnp.asarray(kp),
                                 jnp.asarray(vp), bt, lengths, kn, vn))
        np.testing.assert_allclose(poisoned, base, rtol=2e-5, atol=2e-6)


def test_single_full_pool_exact_page_multiple():
    """A slot whose cache ends exactly on a page boundary (length == k*PAGE)
    must place the new token at the first slot of the next page."""
    lengths = [NP * PAGE - 1, PAGE, 2 * PAGE, 3 * PAGE]
    args = _problem(seed=7, lengths=lengths)
    got = np.asarray(paged_attention_jnp(jnp.asarray(args[0]), *args[1:]))
    want = _dense_oracle(*args)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
