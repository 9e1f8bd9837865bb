"""The fused path's KV page pool lives on the shard's device and is
written in place: prefill pages and each step's appended rows land where
they belong and nowhere else, int8 pages quantize exactly as the numpy
formulation does, and the append scatter compiles once per pool size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.simnet import Sim
from repro.models import ops_for
from repro.serving import batch
from repro.serving.batch import BatchEngine
from repro.serving.sharded import ShardModule

PAGE = 8


@pytest.fixture(scope="module")
def model():
    cfg = get_config("granite-8b").reduced(n_layers=2, d_model=32, vocab=128)
    return cfg, ops_for(cfg).init(cfg, jax.random.PRNGKey(5))


def engine(model, n_slots=3, kv_dtype="fp32"):
    cfg, params = model
    module = ShardModule(cfg, params, (0, cfg.n_layers), is_first=True,
                         is_last=True)
    return BatchEngine(module, Sim(seed=2), n_slots=n_slots, page_size=PAGE,
                       kv_dtype=kv_dtype)


def prompt(cfg, length, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab, (1, length)).astype(np.int32)


def open_sessions(eng, cfg, lengths):
    outs = {}
    for i, n in enumerate(lengths):
        out, _ = eng.sim.run_process(eng.open(i, prompt(cfg, n, i), 64))
        outs[i] = int(np.argmax(out[0]))
    return outs


def step(eng, toks):
    sids = sorted(toks)
    out, served, _ = eng.step(sids, np.asarray([toks[s] for s in sids],
                                               np.int32))
    assert served == sids
    return {s: int(np.argmax(o)) for s, o in zip(sids, out)}


def quant_ref(x):
    """The numpy formulation of int8 page quantization: per (layer,
    kv-head) absmax/127 scales, round half to even."""
    amax = np.abs(x).max(axis=(-3, -1))
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.rint(x / scale[..., None, :, None]).astype(np.int8)
    return q, scale


def test_pool_is_resident_and_counts_the_bytes_it_writes(model):
    """After prefills and steps the pool arrays are jax.Arrays on the
    params' device, and ``kv_bytes_written`` is the prefill pages plus one
    token's k/v per appended row."""
    cfg, params = model
    eng = engine(model)
    pool = eng._pool
    toks = open_sessions(eng, cfg, [5, 11])
    prefill_pages = (1 + 5 // PAGE) + (1 + 11 // PAGE)
    rows = 0
    for _ in range(4):
        toks = step(eng, toks)
        rows += len(toks)
    dev = next(iter(jax.tree.leaves(params)[0].devices()))
    for a in (pool.kp, pool.vp):
        assert isinstance(a, jax.Array) and a.devices() == {dev}
    token = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.hd * 4
    assert pool.append_bytes == token
    assert eng.stats["kv_bytes_written"] == (
        rows * token + prefill_pages * pool.page_bytes)


def test_an_append_changes_only_its_own_page_offset(model):
    cfg, _ = model
    eng = engine(model)
    toks = open_sessions(eng, cfg, [6, 8])
    before_k = np.asarray(eng._pool.kp)
    before_v = np.asarray(eng._pool.vp)
    at = {s: (st.pages[st.length // PAGE], st.length % PAGE)
          for s, st in eng.by_session.items()}
    step(eng, toks)
    after_k = np.asarray(eng._pool.kp)
    after_v = np.asarray(eng._pool.vp)
    changed = np.zeros(after_k.shape[1:3], bool)          # (P, page)
    for pid, off in at.values():
        changed[pid, off] = True
        # the new token's row was written (not zero, not the old value)
        assert not np.array_equal(after_k[:, pid, off], before_k[:, pid, off])
    # every other (page, offset) is bitwise unchanged
    np.testing.assert_array_equal(after_k[:, ~changed], before_k[:, ~changed])
    np.testing.assert_array_equal(after_v[:, ~changed], before_v[:, ~changed])


def test_int8_quantizer_matches_numpy_bitwise():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((3, 4, PAGE, 2, 16)).astype(np.float32) * 3.0
    x[1, 2, :, 0] = 0.0                                # all-zero kv heads
    x[:, 3, :, 1] = 0.0
    # exact ties: amax 127 gives scale 1.0, so k + 0.5 rounds to even
    x[2, 0, :, 1] = (np.arange(PAGE * 16).reshape(PAGE, 16) % 9 - 4) + 0.5
    x[2, 0, 0, 1, 0] = 127.0
    q, s = jax.jit(batch._quant_page_int8)(jnp.asarray(x))
    q_ref, s_ref = quant_ref(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(q), q_ref)
    np.testing.assert_array_equal(np.asarray(s), s_ref)
    assert np.all(np.asarray(s)[1, 2, 0] == 1.0)


def test_int8_prefill_pages_are_the_fp32_pages_quantized(model):
    """The same prompt prefills the same dense k/v in both pools; the int8
    pool holds exactly its quantization, and the staging master holds the
    partial page in fp32."""
    cfg, _ = model
    fp, q8 = engine(model), engine(model, kv_dtype="int8")
    open_sessions(fp, cfg, [13])
    open_sessions(q8, cfg, [13])
    st_f, st_q = fp.by_session[0], q8.by_session[0]
    k = np.asarray(fp._pool.kp)[:, st_f.pages]         # (L, n, page, Hk, hd)
    v = np.asarray(fp._pool.vp)[:, st_f.pages]
    for pool_q, pool_s, x in ((q8._pool.kp, q8._pool.ks, k),
                              (q8._pool.vp, q8._pool.vs, v)):
        q_ref, s_ref = quant_ref(x)
        np.testing.assert_array_equal(np.asarray(pool_q)[:, st_q.pages], q_ref)
        np.testing.assert_array_equal(np.asarray(pool_s)[:, st_q.pages], s_ref)
    tail_k = np.asarray(q8._tails[0])[st_q.slot]
    np.testing.assert_array_equal(tail_k, k[:, 13 // PAGE])


def test_append_scatter_compiles_once_for_any_number_of_rows(model):
    """One scatter of ``n_slots`` rows serves every step: 1 to ``n_slots``
    live rows, fp32 and int8, never add a program at a fixed pool size."""
    cfg, _ = model
    for kv_dtype in ("fp32", "int8"):
        eng = engine(model, n_slots=3, kv_dtype=kv_dtype)
        eng._pool.free(eng._pool.alloc(32))           # the pool's final size
        toks = open_sessions(eng, cfg, [4])
        toks = step(eng, toks)
        size = batch._append_rows._cache_size()
        for n in (2, 3):
            toks.update(open_sessions(eng, cfg, [4] * n))
            toks = {s: toks[s] for s in range(n)}
            for _ in range(2):
                toks = step(eng, toks)
        eng.close([0])                                # 2 rows again
        toks.pop(0)
        toks = step(eng, toks)
        assert batch._append_rows._cache_size() == size, kv_dtype
