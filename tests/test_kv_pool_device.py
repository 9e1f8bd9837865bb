"""The fused path's KV page pool lives on the shard's device and is
written in place: prefill pages and each step's appended rows land where
they belong and nowhere else, for the dense pool and both latent
layouts, and the append scatter compiles once per pool size.
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


def engine(model, n_slots=3):
    cfg, params = model
    module = ShardModule(cfg, params, (0, cfg.n_layers), is_first=True,
                         is_last=True)
    return BatchEngine(module, Sim(seed=2), n_slots=n_slots, page_size=PAGE)


@pytest.fixture(params=["dense", "lane_latent", "row_latent"])
def pool_model(request, monkeypatch):
    """The model whose engine keeps each pool layout: the dense k/v pool,
    the latent pool in lane tiles, and the latent pool of rows."""
    if request.param == "dense":
        return request.getfixturevalue("model")
    if request.param == "row_latent":
        monkeypatch.setattr(batch, "_latent_lanes", lambda *a: 0)
    return request.getfixturevalue("mla_model")


def token_rows(pool):
    """Everything ``pool`` holds per token, ``(L, P, page, n)``: k then v,
    or a latent row (in the lane layout, its latent then its rope key)."""
    L, P = pool.kp.shape[:2]
    parts = [np.asarray(pool.kp).reshape(L, P, pool.page, -1)]
    if pool.vp is not None:
        parts.append(np.asarray(pool.vp).reshape(L, P, pool.page, -1))
    return np.concatenate(parts, axis=-1)


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


@pytest.mark.parametrize("name", ["model", "mla_model"])
def test_pool_is_resident_and_counts_the_bytes_it_writes(request, name):
    """After prefills and steps the pool arrays are jax.Arrays on the
    params' device, and ``kv_bytes_written`` is the prefill pages plus one
    token's k/v (or latent row) per appended row."""
    model = request.getfixturevalue(name)
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
    token = 4 * cfg.n_layers * (cfg.latent_dim if cfg.mla
                                else 2 * cfg.n_kv_heads * cfg.hd)
    assert pool.append_bytes == token
    assert pool.page_bytes == PAGE * token
    assert eng.stats["kv_bytes_written"] == (
        rows * token + prefill_pages * pool.page_bytes)


def test_an_append_changes_only_its_own_page_offset(pool_model):
    cfg, _ = pool_model
    eng = engine(pool_model)
    toks = open_sessions(eng, cfg, [6, 8])
    before = token_rows(eng._pool)
    at = {s: (st.pages[st.length // PAGE], st.length % PAGE)
          for s, st in eng.by_session.items()}
    step(eng, toks)
    after = token_rows(eng._pool)
    changed = np.zeros(after.shape[1:3], bool)            # (P, page)
    for pid, off in at.values():
        changed[pid, off] = True
        # the new token's row was written (not zero, not the old value)
        assert not np.array_equal(after[:, pid, off], before[:, pid, off])
    # every other (page, offset) is bitwise unchanged
    np.testing.assert_array_equal(after[:, ~changed], before[:, ~changed])


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
    live rows never add a program at a fixed pool size."""
    cfg, _ = model
    assert_appends_compile_once(engine(model), cfg)


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


def test_pool_arrays_keep_their_shapes_and_byte_counts():
    """Dense pools and latent pools whose widths do not fit lane tiles
    keep their arrays; a lane-layout pool counts the bytes the pool of
    rows counts, since it holds the same numbers without padding."""
    L, P = 3, 8
    dense = KVPool(L, 2, 16, PAGE)
    tiny = KVPool(L, 1, 40, 4, latent=True, rope_dim=8)    # latent 32
    lanes, rows = latent_pools(L)
    for p in (dense, tiny, lanes, rows):
        p.free(p.alloc(P))
    assert dense.kp.shape == dense.vp.shape == (L, P, PAGE, 2, 16)
    assert dense.kp.dtype == jnp.float32 and len(dense.arrays) == 2
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

    def prefill(pages, length):
        k = np.zeros((L, 1, len(pages) * LPAGE, 1, W), np.float32)
        k[:, :, :length] = rng.standard_normal((L, 1, length, 1, W))
        for p in pools:
            p.arrays = batch._write_prefill(
                p.arrays, np.asarray(pages, np.int32), jnp.asarray(k), None)

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
            p.arrays = batch._append_rows(p.arrays, pages, offs, kn, ())

    def same():
        assert np.array_equal(token_rows(pools[0]), token_rows(pools[1]))

    a = [p.alloc(3) for p in pools]
    b = [p.alloc(1) for p in pools]
    assert a[0] == a[1] and b[0] == b[1]
    a, b = a[0], b[0]
    prefill(a, 2 * LPAGE + 2)
    prefill(b, 29)
    same()
    for t in range(5):                 # A at 66-70 crosses a slab at 68
        append([(a[2], 2 + t)] + ([(b[0], 29 + t)] if t < 3 else []))
        same()
    for p in pools:                    # A leaves; C takes its pages
        p.free(a)
    c = [p.alloc(3) for p in pools]
    assert c[0] == c[1] and sorted(c[0]) == sorted(a)
    c = c[0]
    prefill(c, 3 * LPAGE - 1)
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
