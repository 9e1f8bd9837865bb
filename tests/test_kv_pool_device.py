"""The fused path's KV page pool lives on the shard's device and is
written in place: prefill pages and each step's appended rows land where
they belong and nowhere else, int8 pages quantize exactly as the numpy
formulation does, and the append scatter compiles once per pool size.
A latent (MLA) pool whose widths fit whole lane tiles keeps its rows as
two arrays, latents and rope keys, and holds exactly what the one-array
pool of rows holds after the same writes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.simnet import Sim
from repro.kernels.paged_attention import paged_latent_attention_jnp
from repro.models import ops_for
from repro.models.config import ModelConfig
from repro.serving import batch
from repro.serving.batch import BatchEngine, KVPool
from repro.serving.sharded import ShardModule

PAGE = 8
#: DeepSeek-V2-Lite's latent row: 512 latent numbers, then 64 of rope key
RANK, ROPE, LPAGE = 512, 64, 32
W = RANK + ROPE


@pytest.fixture(scope="module")
def model():
    cfg = get_config("granite-8b").reduced(n_layers=2, d_model=32, vocab=128)
    return cfg, ops_for(cfg).init(cfg, jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def mla_model():
    """A latent-attention model whose rows fit the lane layout (latent
    128, rope 64), at toy depth and widths elsewhere."""
    cfg = ModelConfig(
        name="mla-lanes", arch="dense", n_layers=2, d_model=64, n_heads=2,
        n_kv_heads=2, d_ff=96, vocab=128, kv_lora_rank=128,
        qk_nope_head_dim=16, qk_rope_head_dim=64, v_head_dim=16,
        rope_theta=10000.0, norm_eps=1e-6)
    return cfg, ops_for(cfg).init(cfg, jax.random.PRNGKey(9))


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


def assert_appends_compile_once(eng, cfg):
    """1 to ``n_slots`` live rows, and a slot closed between steps, add no
    ``_append_rows`` program once the pool has its final size."""
    eng._pool.free(eng._pool.alloc(32))               # the pool's final size
    toks = open_sessions(eng, cfg, [4])
    toks = step(eng, toks)
    size = batch._append_rows._cache_size()
    for n in (2, 3):
        toks.update(open_sessions(eng, cfg, [4] * n))
        toks = {s: toks[s] for s in range(n)}
        for _ in range(2):
            toks = step(eng, toks)
    eng.close([0])                                    # 2 rows again
    toks.pop(0)
    toks = step(eng, toks)
    assert batch._append_rows._cache_size() == size


def test_append_scatter_compiles_once_for_any_number_of_rows(model):
    """One scatter of ``n_slots`` rows serves every step: 1 to ``n_slots``
    live rows, fp32 and int8, never add a program at a fixed pool size."""
    cfg, _ = model
    for kv_dtype in ("fp32", "int8"):
        assert_appends_compile_once(engine(model, kv_dtype=kv_dtype), cfg)


def test_lane_latent_append_compiles_once_for_any_number_of_rows(mla_model):
    cfg, _ = mla_model
    eng = engine(mla_model)
    assert eng._pool.vp is not None                   # the lane layout
    assert_appends_compile_once(eng, cfg)


# ------------------------------------------------- the latent lane layout
def latent_pools(n_layers=2):
    """The same latent pool at DeepSeek-V2-Lite's widths in both layouts:
    in lane tiles (told the rope width), and as one array of rows."""
    lanes = KVPool(n_layers, 1, W, LPAGE, latent=True, rope_dim=ROPE)
    rows = KVPool(n_layers, 1, W, LPAGE, latent=True)
    return lanes, rows


def pool_rows(pool, layer):
    """Layer ``layer``'s rows ``(P, page, W)`` of either layout."""
    kp = np.asarray(pool.kp[layer])
    if pool.vp is None:
        return kp[:, :, 0]
    P = kp.shape[0]
    pe = np.asarray(pool.vp[layer]).reshape(P, LPAGE, -1)
    return np.concatenate([kp.reshape(P, LPAGE, -1), pe], axis=-1)


def test_pool_arrays_keep_their_shapes_and_byte_counts():
    """Dense, int8 and latent pools whose widths do not fit lane tiles
    keep their arrays; a lane-layout pool counts the bytes the pool of
    rows counts, since it holds the same numbers without padding."""
    L, P = 3, 8
    dense = KVPool(L, 2, 16, PAGE)
    q8 = KVPool(L, 2, 16, PAGE, quant=True)
    tiny = KVPool(L, 1, 40, 4, latent=True, rope_dim=8)    # latent 32
    lanes, rows = latent_pools(L)
    for p in (dense, q8, tiny, lanes, rows):
        p.free(p.alloc(P))
    assert dense.kp.shape == dense.vp.shape == (L, P, PAGE, 2, 16)
    assert dense.kp.dtype == jnp.float32 and dense.ks is None
    assert q8.kp.shape == q8.vp.shape == (L, P, PAGE, 2, 16)
    assert q8.kp.dtype == jnp.int8 and q8.ks.shape == q8.vs.shape == (L, P, 2)
    assert tiny.kp.shape == (L, P, 4, 1, 40) and tiny.vp is None
    assert rows.kp.shape == (L, P, LPAGE, 1, W) and rows.vp is None
    assert lanes.kp.shape == (L, P, LPAGE, RANK // 128, 128)
    assert lanes.vp.shape == (L, P, LPAGE // 4, 2, 128)
    assert lanes.kp.size + lanes.vp.size == rows.kp.size
    assert lanes.page_bytes == rows.page_bytes == L * LPAGE * W * 4
    assert lanes.append_bytes == rows.append_bytes == L * W * 4
    # widths off the lane tiles: latent not a multiple of 128, rope not
    # dividing 256, a page not holding whole slabs
    for rank, rope, page in ((500, 64, 32), (512, 48, 32), (512, 64, 6)):
        odd = KVPool(L, 1, rank + rope, page, latent=True, rope_dim=rope)
        assert odd.vp is None and odd.kp.shape[3:] == (1, rank + rope)


def test_lane_latent_pool_holds_the_rows_the_row_pool_holds():
    """Prefills, appends with padding rows, appends across slab and page
    boundaries, and a freed slot's pages prefilled again by another
    session leave both layouts holding bitwise the same float32 rows."""
    rng = np.random.default_rng(3)
    L, M = 2, 3
    pools = latent_pools(L)

    def prefill(slot, pages, length):
        k = np.zeros((L, 1, len(pages) * LPAGE, 1, W), np.float32)
        k[:, :, :length] = rng.standard_normal((L, 1, length, 1, W))
        for p in pools:
            p.arrays, _ = batch._write_prefill(
                p.arrays, None, slot, np.asarray(pages, np.int32),
                length // LPAGE, jnp.asarray(k), None)

    def append(at):
        """One step's rows at ``at = [(page, offset), ...]``, padded to
        ``M`` rows with an out-of-range page."""
        pages = np.full((M,), pools[0].n_pages, np.int32)
        offs = np.zeros((M,), np.int32)
        for r, (pid, off) in enumerate(at):
            pages[r], offs[r] = pid, off
        kn = tuple(jnp.asarray(rng.standard_normal((L, 1, W)), jnp.float32)
                   for _ in range(M))
        for p in pools:
            p.arrays, _ = batch._append_rows(
                p.arrays, None, np.arange(M, dtype=np.int32), pages, offs,
                kn, ())

    def same():
        for layer in range(L):
            assert np.array_equal(pool_rows(pools[0], layer),
                                  pool_rows(pools[1], layer)), layer

    a = [p.alloc(3) for p in pools]
    b = [p.alloc(1) for p in pools]
    assert a[0] == a[1] and b[0] == b[1]
    a, b = a[0], b[0]
    prefill(0, a, 2 * LPAGE + 2)
    prefill(1, b, 29)
    same()
    for t in range(5):                 # A at 66-70 crosses a slab at 68
        append([(a[2], 2 + t)] + ([(b[0], 29 + t)] if t < 3 else []))
        same()
    for p in pools:                    # A leaves; C takes its pages
        p.free(a)
    c = [p.alloc(3) for p in pools]
    assert c[0] == c[1] and sorted(c[0]) == sorted(a)
    c = c[0]
    prefill(2, c, 3 * LPAGE - 1)
    same()
    append([(c[2], LPAGE - 1)])        # the last row of C's last page
    append([])                         # padding rows alone
    same()


def test_split_latent_attention_matches_the_row_form():
    rng = np.random.default_rng(4)
    M, H, P, NP = 3, 4, 10, 3
    pool = rng.standard_normal((P, LPAGE, W)).astype(np.float32)
    bt = rng.permutation(P)[:M * NP].reshape(M, NP).astype(np.int32)
    lengths = np.array([0, 45, NP * LPAGE - 1], np.int32)
    q = jnp.asarray(rng.standard_normal((M, H, W)), jnp.float32)
    row_new = jnp.asarray(rng.standard_normal((M, W)), jnp.float32)
    args = (jnp.asarray(bt), jnp.asarray(lengths), row_new, RANK, 0.07)
    want = paged_latent_attention_jnp(q, jnp.asarray(pool), *args)
    c = pool[..., :RANK].reshape(P, LPAGE, RANK // 128, 128)
    pe = pool[..., RANK:].reshape(P, LPAGE // 4, 2, 128)
    got = paged_latent_attention_jnp(q, jnp.asarray(c), *args,
                                     rope_pool=jnp.asarray(pe))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6 * float(np.abs(want).max()))


def test_lane_latent_engine_decodes_as_the_row_layout(mla_model, monkeypatch):
    """The same prompts and tokens through the lane-layout pool and
    through the pool of rows, with a slot freed and taken by another
    session midway, give logits equal within float32 rounding and the
    same byte counts."""
    cfg, _ = mla_model
    lanes = engine(mla_model)
    monkeypatch.setattr(batch, "_latent_lanes", lambda *a: 0)
    rows = engine(mla_model)
    assert lanes._pool.vp is not None and rows._pool.vp is None
    engs = (lanes, rows)
    toks = [open_sessions(e, cfg, [5, 11, 30]) for e in engs][0]
    for i in range(10):
        if i == 4:                     # session 1 leaves, 3 takes its slot
            toks.pop(1)
            for e in engs:
                e.close([1])
                out, _ = e.sim.run_process(e.open(3, prompt(cfg, 9, 3), 64))
            toks[3] = int(np.argmax(out[0]))
        sids = sorted(toks)
        x = np.asarray([toks[s] for s in sids], np.int32)
        out, got = [e.step(sids, x)[0] for e in engs]
        np.testing.assert_allclose(out, got, rtol=1e-5, atol=1e-5)
        toks = {s: int(np.argmax(o)) for s, o in zip(sids, out)}
    assert lanes.stats["kv_bytes_written"] == rows.stats["kv_bytes_written"]
