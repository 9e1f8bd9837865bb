"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Each test lowers one kernel at a published model's head layout for a
described (not attached) ``v5e:2x2`` topology and compiles it with the
TPU compiler, which refuses what interpret mode accepts: blocks that break
the tiling rule, VMEM overuse, unsupported in-kernel ops.  Nothing runs,
so these say nothing about results or times.

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
from repro.kernels.paged_attention import paged_attention_pallas

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


@pytest.mark.parametrize("kv_dtype", ["fp32", "int8"])
@pytest.mark.parametrize("layout", PAGED_LAYOUTS, ids=lambda l: l[0])
def test_paged_decode_compiles(one_chip, layout, kv_dtype):
    _, H, Hk, hd = layout
    pool_dt = jnp.int8 if kv_dtype == "int8" else jnp.float32
    specs = [
        _spec(one_chip, (SLOTS, H, hd), jnp.float32),                 # q
        _spec(one_chip, (POOL_PAGES, PAGE, Hk, hd), pool_dt),         # k pool
        _spec(one_chip, (POOL_PAGES, PAGE, Hk, hd), pool_dt),         # v pool
        _spec(one_chip, (SLOTS, TABLE_PAGES), jnp.int32),             # tables
        _spec(one_chip, (SLOTS,), jnp.int32),                         # lengths
        _spec(one_chip, (SLOTS, Hk, hd), jnp.float32),                # k new
        _spec(one_chip, (SLOTS, Hk, hd), jnp.float32),                # v new
    ]
    if kv_dtype == "int8":
        specs += [_spec(one_chip, (POOL_PAGES, Hk), jnp.float32)] * 2

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
