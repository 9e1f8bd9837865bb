"""The DeepSeek-V2 family (``models/deepseek_v2.py``): its counts against
hand counts at the cell's widths, a tiny DeepSeek-shaped cell through
``run.run`` on the CPU, which reads correct, two planted faults, which
read not correct, and the cell's three readers, silent without their
records."""

import copy
import dataclasses
import os
import time

import pytest

import chipbench_tiny
from chipbench import record, spec
from chipbench import run as bench_run

ds, ds_program = spec.family({"family": "deepseek_v2"})
CELL = "deepseek-v2-lite-14l.chat_full"
NEW_READERS = ("latent_moe_step_ms", "latent_moe_step_roofline",
               "expert_rows_per_step")


def _cfg():
    return spec.load_json(os.path.join(chipbench_tiny.ROOT, "chipbench",
                                       "configs", "deepseek-v2-lite-14l.json"))


# ------------------------------------------------------------------ counts
ATTN = (2048 * 16 * 192 + 2048 * 576 + 512 + 512 * 16 * 256
        + 16 * 128 * 2048)                                 # 13,763,072
EXPERT = 3 * 2048 * 1408                                   # 8,650,752
MOE_HELD = ATTN + 2 * 2048 + 2048 * 64 + 8 * EXPERT + 2 * EXPERT
MOE_TOUCHED = ATTN + 2 * 2048 + 2048 * 64 + 2 * EXPERT + 6 * 8 / 64 * EXPERT
DENSE = ATTN + 2 * 2048 + 3 * 2048 * 10944
HEAD = 2 * 2048 * 102400


def test_hand_counts_of_the_weights():
    c = _cfg()
    assert ATTN == 13_763_072 and MOE_TOUCHED == 37_687_808
    assert DENSE == 81_007_104
    assert ds.layer_params(c, 0) == DENSE
    assert ds.layer_params(c, 1) == MOE_HELD
    assert ds.layer_touched(c, 5) == MOE_TOUCHED
    assert ds.params(c) == DENSE + 13 * MOE_HELD + 2 * 102400 * 2048 + 2048
    assert ds.params(c) == c["params"] == 1_805_714_432
    assert ds.kv_bytes_per_token(c, 14) == 14 * 576 * 4 == c["kv_bytes_per_token"]
    # the system's own count of the same configuration
    assert ds_program.program_config(c).param_count() == c["params"]


def test_hand_counts_of_prefill_and_decode():
    c = _cfg()
    weights = DENSE + 13 * MOE_TOUCHED
    # prefill of 3 tokens: positions 0..2 attend 1..3 keys; per head the
    # scores take nope + rope = 192 and the values 128 numbers
    want = 3 * 2 * weights + 14 * 2 * 16 * (192 + 128) * (1 + 2 + 3) + HEAD
    assert ds.prefill_flops(c, 3) == pytest.approx(want, rel=1e-12)
    assert ds.prefill_flops(c, 3) == pytest.approx(
        sum(ds.token_flops(c, p, False) for p in range(3)) + HEAD, rel=1e-12)
    # decode at position 99, absorbed: 100 rows of 576 scored, 512 summed
    want = 2 * weights + 14 * 2 * 16 * (576 + 512) * 100 + HEAD
    assert ds.decode_flops(c, 99) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize("rows", [1, 48])
def test_fused_step_floor_of_each_shard(rows):
    c = _cfg()
    lengths = [100 + 10 * i for i in range(rows)]
    reached = 8 * (1 - (58 / 64) ** rows)
    moe_bytes = MOE_HELD - (8 - reached) * EXPERT
    latent = 576 * 4 * 7 * (sum(lengths) + rows)
    att = 2 * 16 * (576 + 512) * 7 * sum(n + 1 for n in lengths)
    first = ds.fused_step(c, 7, first=True, last=False, lengths=lengths,
                          param_bytes=4)
    assert first["bytes"] == pytest.approx(
        (DENSE + 6 * moe_bytes) * 4 + rows * 2048 * 4 + latent, rel=1e-12)
    assert first["flops"] == pytest.approx(
        rows * 2 * (DENSE + 6 * MOE_TOUCHED) + att, rel=1e-12)
    last = ds.fused_step(c, 7, first=False, last=True, lengths=lengths,
                         param_bytes=4)
    assert last["bytes"] == pytest.approx(
        7 * moe_bytes * 4 + (2048 * 102400 + 2048) * 4 + latent, rel=1e-12)
    assert last["flops"] == pytest.approx(
        rows * 2 * 7 * MOE_TOUCHED + att + rows * HEAD, rel=1e-12)


def test_the_system_refuses_what_it_cannot_run():
    c = _cfg()
    for key, value in (("q_lora_rank", 1536), ("topk_method", "group_limited_greedy"),
                       ("n_group", 8), ("scoring_func", "sigmoid"),
                       ("routed_scaling_factor", 16.0)):
        with pytest.raises(ValueError, match=key):
            ds_program.program_config(dict(c, **{key: value}))


# ------------------------------------------------------------- tiny cell
TINY = {
    "name": "tinyds", "family": "deepseek_v2", "num_hidden_layers": 3,
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "q_lora_rank": None, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 2, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "first_k_dense_replace": 1,
    "norm_topk_prob": False, "routed_scaling_factor": 1, "n_group": 1,
    "topk_group": 1, "topk_method": "greedy", "scoring_func": "softmax",
    "hidden_act": "silu", "vocab_size": 2048, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "tie_word_embeddings": False,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64},
    "reduced": {"n_routed_experts": [8, 2]},
    "serving": {"shards": 2, "page_size": 8, "kv_dtype": "float32"}}
LIMIT = 0.002


def _cell():
    return {"name": "tinyds.tinychat", "chips": 1, "config": copy.deepcopy(TINY),
            "traffic": copy.deepcopy(chipbench_tiny.MIX),
            "limits": {"worst_gap_sd": {"limit": LIMIT},
                       "worst_logit_rms_sd": {"limit": LIMIT}}}


def _bench():
    b = copy.deepcopy(spec.load_benchmark())
    b["configs"].append({"name": "tinyds", "source": "test", "file": "-",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tinyds.tinychat", "config": "tinyds",
                           "traffic": "tinychat", "chips": 1, "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tinyds.tinychat")
    return b


def _run(seed, traced=False, seconds=0.5):
    return bench_run.run(_cell(), _bench(), seed=seed, seconds=seconds,
                         traced=traced, peaks=chipbench_tiny.PEAKS,
                         t_start=time.perf_counter())


def test_tiny_deepseek_cell_reads_correct_with_its_expert_rows():
    line = _run(2 ** 31 + 91, traced=True, seconds=1.0)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["check_detail"]["tokens_checked"] >= 10
    m = line["metrics"]
    # the CPU has no device plane: the device-trace readers stay silent
    assert "latent_moe_step_ms" not in m and "latent_moe_step_roofline" not in m
    rows = m["expert_rows_per_step"]["value"]
    assert 0 < rows <= chipbench_tiny.MIX["n_slots"]
    for name in ("mfu", "decode_rows_per_step", "host_other_share"):
        assert m[name]["value"] > 0, name


def _renormalised(monkeypatch):
    real = ds_program.program_config
    monkeypatch.setattr(ds_program, "program_config", lambda c: dataclasses.replace(
        real(c), norm_topk_prob=True))


def _rope_on_halves(monkeypatch):
    """Rope on the halves of the rope dimensions, not on their pairs."""
    import jax.numpy as jnp
    from repro.models import mla

    def halves(x, positions, inv_freq):
        ang = positions.astype(jnp.float32)[..., None] * inv_freq
        ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                                x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)
    monkeypatch.setattr(mla, "apply_rope_interleaved", halves)


@pytest.mark.parametrize("plant", [_renormalised, _rope_on_halves])
def test_a_planted_fault_reads_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    line = _run(17)
    assert line["correct"] is False
    assert line["checks"]["worst_logit_rms_sd"]["value"] > 10 * LIMIT


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_are_silent_without_their_records(metric):
    run = record.Run(config={}, traffic={}, chips=1, peaks={},
                     window=(0.0, 1.0), setup_s=0.0, requests=[])
    assert spec.reader(metric)(run) is None


def test_routing_flips_of_the_program_precision_and_the_control():
    """On the CPU the default matmul precision is float32's own, so the
    reference at the program's precision routes as the reference does;
    bfloat16 weights and activations move some of the top-k sets."""
    import numpy as np
    tokens = (np.arange(2 * 40).reshape(2, 40) * 37 % 2048).astype(np.int32)
    rows = np.asarray([[30, 35], [36, 39]], np.int32)
    flips = ds.routing_flips(TINY, 5, 2, tokens, rows, 2)
    assert flips["program"] == 0.0
    assert 0.0 < flips["control"] < 0.5
