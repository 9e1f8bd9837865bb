"""Launch-layer pure helpers: HLO parsing, sharding rules, roofline math.

(The dry-run itself needs a 512-device process and is exercised by
``python -m repro.launch.dryrun``; these tests cover the logic that
doesn't need the big mesh.)
"""

import numpy as np
import pytest

from repro.launch.hlo_stats import CollectiveStats, op_histogram, parse_collectives


HLO = """
HloModule test, num_partitions=16
  %all-reduce.1 = f32[256]{0} all-reduce(%x), channel_id=2, replica_groups=[16,32]<=[512], to_apply=%sum
  %all-gather.2 = bf16[1024,64]{1,0} all-gather(%y), replica_groups=[32,16]<=[512], dimensions={0}
  %rs = f32[128]{0} reduce-scatter(%z), replica_groups=[64,8]<=[512], to_apply=%sum
  %ata = bf16[64,64]{1,0} all-to-all(%w), replica_groups=[128,4]<=[512]
  %cp = f32[32]{0} collective-permute(%v), source_target_pairs={{0,1}}
  %ar-start = f32[16]{0} all-reduce-start(%u), replica_groups=[16,32]<=[512]
  %ar-done = f32[16]{0} all-reduce-done(%ar-start)
"""


def test_parse_collectives_counts_and_bytes():
    st = parse_collectives(HLO)
    assert st.counts["all-reduce"] == 2          # incl. the -start, not -done
    assert st.counts["all-gather"] == 1
    assert st.counts["reduce-scatter"] == 1
    assert st.counts["all-to-all"] == 1
    assert st.counts["collective-permute"] == 1
    # all-reduce of f32[256] in groups of 32: 2*1024*(31/32)
    assert st.bytes_by_op["all-reduce"] == pytest.approx(
        2 * 256 * 4 * 31 / 32 + 2 * 16 * 4 * 31 / 32)
    # all-gather bf16[1024,64] groups of 16: size*(g-1)/g
    assert st.bytes_by_op["all-gather"] == pytest.approx(
        1024 * 64 * 2 * 15 / 16)
    assert st.total_bytes > 0


def test_op_histogram():
    hist = dict(op_histogram(HLO))
    assert hist.get("all-reduce", 0) >= 1


def test_roofline_analyzer():
    from benchmarks.roofline import analyze_record, suggest

    rec = {
        "arch": "granite-8b", "shape": "train_4k", "kind": "train",
        "n_devices": 256, "active_params": 8.1e9,
        "hlo_flops_per_dev": 1.5e12, "hlo_bytes_per_dev": 5e10,
        "collective_bytes_per_dev": 3e9,
        "bytes_args_per_dev": 3e8, "bytes_temp_per_dev": 8e9,
        "bytes_out_per_dev": 3e8, "collective_counts": {"all-reduce": 3},
    }
    row = analyze_record(rec)
    assert row["dominant"] in ("compute", "memory", "collective")
    assert row["compute_s"] == pytest.approx(1.5e12 / 197e12)
    assert row["memory_s"] == pytest.approx(5e10 / 819e9)
    assert row["collective_s"] == pytest.approx(3e9 / 50e9)
    # 6·N·D train model flops
    assert row["model_flops_per_dev"] == pytest.approx(
        6 * 8.1e9 * 256 * 4096 / 256)
    assert isinstance(suggest(row), str) and len(suggest(row)) > 10
    assert analyze_record({"skipped": "x"}) is None


def test_param_spec_rules():
    import jax
    from jax.sharding import PartitionSpec as P

    if jax.device_count() < 4:
        import dataclasses

        from repro.configs import get_config
        from repro.launch.shardings import param_spec

        class FakeMesh:
            shape = {"data": 16, "model": 16}
            axis_names = ("data", "model")

        mesh = FakeMesh()
        cfg = get_config("granite-8b")
        # attention: in-dim FSDP, out-dim TP
        assert param_spec("blocks/attn/wq", (36, 4096, 4096), mesh, cfg,
                          "train") == P(None, "data", "model")
        # serve mode: no FSDP
        assert param_spec("blocks/attn/wq", (36, 4096, 4096), mesh, cfg,
                          "serve") == P(None, None, "model")
        # embeddings: vocab on model, but replicated if not divisible
        assert param_spec("embed", (49152, 4096), mesh, cfg, "serve") == \
            P("model", None)
        cfgw = get_config("whisper-small")
        assert param_spec("embed", (51865, 768), mesh, cfgw, "serve") == \
            P(None, None)       # 51865 % 16 != 0 -> replicate
        # norms replicate
        assert param_spec("blocks/ln1", (36, 4096), mesh, cfg, "train") == \
            P(None, None)
        # xlstm serve under seq-parallelism: weights replicate (the model
        # axis carries segments); plain serve/decode keeps TP sharding
        cfgx = get_config("xlstm-1.3b")
        cfgx_sp = dataclasses.replace(cfgx, seq_segments=16,
                                      act_seq_axis="model")
        assert param_spec("blocks/mlstm/wq", (4096, 4096), mesh, cfgx_sp,
                          "serve") == P(None, None)
        assert param_spec("blocks/mlstm/wq", (4096, 4096), mesh, cfgx,
                          "serve") == P(None, "model")
        assert param_spec("blocks/mlstm/wq", (4096, 4096), mesh, cfgx,
                          "train") == P("data", "model")


def test_cache_spec_rules():
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.launch.shardings import cache_spec

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    mesh = FakeMesh()
    cfg = get_config("glm4-9b")          # Hk=2: heads don't divide 16
    spec = cache_spec("layers/k", (40, 128, 32768, 2, 128), mesh, cfg)
    assert spec == P(None, "data", "model", None, None)   # T-dim sharded
    cfg2 = get_config("qwen2-moe-a2.7b")  # Hk=16: heads divide
    spec2 = cache_spec("layers/k", (24, 128, 32768, 16, 128), mesh, cfg2)
    assert spec2 == P(None, "data", None, "model", None)


def test_compile_cache_follows_env_var_else_repo_dir(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set;
    otherwise the cache is the checkout's fixed ``.jax_cache``."""
    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, "/elsewhere/cache")
        assert compile_cache.configure_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.configure_compile_cache()
        assert path == str(compile_cache.REPO_ROOT / ".jax_cache")
        assert (compile_cache.REPO_ROOT / "chip_smoke.py").exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
