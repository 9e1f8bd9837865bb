"""``chip_smoke.py``'s phases at a reduced size on the CPU, and its refusal
to report anything without a TPU.  The phases are the same functions the
script runs on the chip at published widths; only ``main`` checks the
platform."""

import sys
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402
from repro.configs import get_config  # noqa: E402


def _small(arch, n_layers=4):
    return get_config(arch).reduced(n_layers=n_layers, d_model=64, vocab=128)


def test_main_refuses_a_host_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    for argv in ([], ["--four-chips"]):
        assert chip_smoke.main(argv) != 0
        out, err = capsys.readouterr()
        assert "'cpu'" in err
        assert '"ok"' not in out


def test_phase_kernels_against_references_in_interpret_mode():
    errs = chip_smoke.phase_kernels(
        _small("minicpm-2b"), get_config("qwen2-moe-a2.7b"), interpret=True,
        seq=128, slots=4, page=8, table_pages=4)
    assert set(errs) == {"paged_fp32", "flash_float32",
                         "flash_bfloat16", "moe_gating_probs",
                         "moe_gating_weights"}
    assert all(np.isfinite(v) for v in errs.values())


def test_phase_serve_checks_every_token_against_the_reference():
    res = chip_smoke.phase_serve(_small("minicpm-2b"), n_requests=3,
                                 prompt_len=12, new_tokens=4)
    assert res["client"]["completed"] == 3
    assert res["client"]["failed_sessions"] == 0
    # fp32 on the CPU: served tokens are the reference's argmax
    assert res["argmax_agree"] == 1.0
    assert res["worst_gap_sd"] <= chip_smoke.LOGIT_TOL
    assert all(ms is not None for ms in res["step_ms"])


def test_phase_serve_four_pipeline_shards():
    res = chip_smoke.phase_serve(_small("granite-8b", 8), n_shards=4,
                                 n_requests=2, prompt_len=12, new_tokens=3,
                                 tag="four_chip_serve")
    assert res["client"]["completed"] == 2
    assert res["argmax_agree"] == 1.0


def test_phase_train_closes_one_round_on_two_workers():
    res = chip_smoke.phase_train(_small("minicpm-2b", 2), seq=32, batch=2)
    assert len(res["losses"]) == 4
    assert all(np.isfinite(res["losses"]))
    # each worker's second inner step hit the jit cache and was timed
    assert all(ms is not None for ms in res["step_ms"])


def test_train_config_keeps_published_widths():
    cfg, full = chip_smoke.train_config(), get_config("minicpm-2b")
    assert cfg.n_layers == chip_smoke.TRAIN_LAYERS < full.n_layers
    assert (cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab) == (
        full.d_model, full.n_heads, full.d_ff, full.vocab)
