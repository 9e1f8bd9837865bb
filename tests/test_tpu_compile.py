"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Each test lowers one kernel at a published model's head layout for a
described (not attached) ``v5e:2x2`` topology and compiles it with the
TPU compiler, which refuses what interpret mode accepts: blocks that break
the tiling rule, VMEM overuse, unsupported in-kernel ops.  Nothing runs,
so these say nothing about results or times.  The KV pool's write
programs are compiled the same way at the serving shapes of
granite-8b-9l's shard and DeepSeek-V2-Lite, and the latent attention at
DeepSeek-V2-Lite's: the compiler's memory analysis must find no copy of
the pool, and the attention over the split pool must read no more than
over one array of rows.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every test
worker imports every test file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.moe_gating import moe_gating_tokens
from repro.kernels.paged_attention import (paged_attention_pallas,
                                           paged_latent_attention_jnp)
from repro.serving import batch

#: (name, n_heads, n_kv_heads, head_dim) of the configs the paged path serves
PAGED_LAYOUTS = [("minicpm-2b", 36, 36, 64), ("granite-8b", 32, 8, 128)]
PAGE, SLOTS, POOL_PAGES, TABLE_PAGES = 32, 8, 64, 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable is written to the persistent cache but
    # cannot be read back without a chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *specs) -> str:
    return jax.jit(fn).lower(*specs).compile().as_text()


@pytest.mark.parametrize("layout", PAGED_LAYOUTS, ids=lambda l: l[0])
def test_paged_decode_compiles(one_chip, layout):
    _, H, Hk, hd = layout
    pool_dt = jnp.float32
    specs = [
        _spec(one_chip, (SLOTS, H, hd), jnp.float32),                 # q
        _spec(one_chip, (POOL_PAGES, PAGE, Hk, hd), pool_dt),         # k pool
        _spec(one_chip, (POOL_PAGES, PAGE, Hk, hd), pool_dt),         # v pool
        _spec(one_chip, (SLOTS, TABLE_PAGES), jnp.int32),             # tables
        _spec(one_chip, (SLOTS,), jnp.int32),                         # lengths
        _spec(one_chip, (SLOTS, Hk, hd), jnp.float32),                # k new
        _spec(one_chip, (SLOTS, Hk, hd), jnp.float32),                # v new
    ]

    def fn(*args):
        return paged_attention_pallas(*args, interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, *specs)


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_flash_attention_compiles(one_chip, hd, dtype):
    spec = _spec(one_chip, (1, 8, 1024, hd), dtype)

    def fn(q, k, v):
        return flash_attention_bhsd(q, k, v, causal=True, interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, spec, spec, spec)


def test_moe_gating_compiles(one_chip):
    """qwen2-moe-a2.7b router: 60 experts, top-4."""
    spec = _spec(one_chip, (512, 60), jnp.float32)

    def fn(logits):
        return moe_gating_tokens(logits, 4, interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, spec)


def _pool_write_temps(one_chip, pool, P, M, program):
    """Temporary bytes of ``pool``'s append (``M`` rows) or prefill (a
    2,048-token prompt: 65 pages) program, compiled with ``P`` pages."""
    L, page, Hk, hd = pool.L, pool.page, pool.Hk, pool.hd
    arrays = tuple(None if a is None else
                   _spec(one_chip, (L, P) + a.shape[2:], a.dtype)
                   for a in pool.arrays)
    i32, f32 = jnp.int32, jnp.float32
    row = _spec(one_chip, (L, Hk, hd), f32)
    if program == "append":
        fn = batch._append_rows
        args = (arrays, _spec(one_chip, (M,), i32),
                _spec(one_chip, (M,), i32), (row,) * M,
                () if pool.latent else (row,) * M)
    else:
        n = 65
        fn = batch._write_prefill
        cache = _spec(one_chip, (L, 1, n * page, Hk, hd), f32)
        args = (arrays, _spec(one_chip, (n,), i32), cache,
                None if pool.latent else cache)
    return fn.lower(*args).compile().memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("program", ["append", "prefill"])
def test_latent_pool_writes_compile_in_place(one_chip, program):
    """DeepSeek-V2-Lite's pool at its serving shapes (7 layers, 3,840
    pages of 32 rows of 512 + 64 numbers, 48 slots): the donated append
    and prefill scatters write in place.  A pool laid out anew around the
    scatter shows as temporaries the size of the pool (2.5 GB)."""
    pool = batch.KVPool(7, 1, 576, 32, latent=True, rope_dim=64)
    assert pool.vp is not None                       # the lane layout
    temps = _pool_write_temps(one_chip, pool, 3840, 48, program)
    assert temps < 64 << 20, temps


@pytest.mark.parametrize("program", ["append", "prefill"])
def test_dense_pool_writes_compile_in_place(one_chip, program):
    """granite-8b-9l's second shard at its serving shapes (5 layers, 8
    KV heads of 128, 264 pages of 32, 8 slots): the donated append and
    prefill scatters write k and v in place, far below the pool's
    346 MB."""
    pool = batch.KVPool(5, 8, 128, 32)
    temps = _pool_write_temps(one_chip, pool, 264, 8, program)
    assert temps < 16 << 20, temps


def test_split_latent_attention_reads_no_more_than_rows(one_chip):
    """48 slots of block tables padded to 128 pages of 32 over 3,840
    pages: by the compiler's cost analysis the split pool's attention
    accesses fewer bytes, and keeps fewer temporaries, than the one-array
    form.  Reading the gathered latents as rows of 512 would relayout
    them (about 10% more bytes than the one-array form)."""
    P, page, M, H, NP, r, rope = 3840, 32, 48, 16, 128, 512, 64
    i32 = jnp.int32
    common = [_spec(one_chip, (M, NP), i32), _spec(one_chip, (M,), i32),
              _spec(one_chip, (M, r + rope), jnp.float32)]
    q = _spec(one_chip, (M, H, r + rope), jnp.float32)

    def rows(q, pool, bt, n, new):
        return paged_latent_attention_jnp(q, pool, bt, n, new, r, 0.1)

    def split(q, c, pe, bt, n, new):
        return paged_latent_attention_jnp(q, c, bt, n, new, r, 0.1,
                                          rope_pool=pe)

    def cost(fn, *specs):
        c = jax.jit(fn).lower(*specs).compile()
        ca = c.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        return ca["bytes accessed"], c.memory_analysis().temp_size_in_bytes

    want = cost(rows, q, _spec(one_chip, (P, page, r + rope), jnp.float32),
                *common)
    got = cost(split, q, _spec(one_chip, (P, page, r // 128, 128),
                               jnp.float32),
               _spec(one_chip, (P, page // 4, 2, 128), jnp.float32), *common)
    assert got[0] <= want[0] and got[1] <= want[1], (got, want)
