"""One cell's driver end to end at a tiny size on the CPU: set-up, warm-up,
a traced window, metrics and the check.  ``main`` refuses the CPU."""

import time

import pytest

import chipbench_tiny
from chipbench import run as bench_run


def test_tiny_cell_runs_and_checks_correct():
    t0 = time.perf_counter()
    line = bench_run.run(chipbench_tiny.cell(), chipbench_tiny.bench(),
                         seed=2 ** 31 + 77, seconds=1.0, traced=True,
                         peaks=chipbench_tiny.PEAKS, t_start=t0)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    checks = line["checks"]
    assert checks["worst_gap_sd"]["value"] <= checks["worst_gap_sd"]["limit"]
    assert line["check_detail"]["tokens_checked"] >= 10
    m = line["metrics"]
    # the CPU has no device plane: device-trace metrics stay out of the line
    assert "fused_step_ms" not in m and "device_idle_share" not in m
    for name in ("host_other_share", "kv_copy_ms", "prefill_ms", "mfu"):
        assert m[name]["value"] > 0, name
    assert 0 < m["host_other_share"]["value"] < 100
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["window_s"] >= 1.0


def test_untraced_run_reports_end_to_end_metrics():
    line = bench_run.run(chipbench_tiny.cell(), chipbench_tiny.bench(),
                         seed=5, seconds=0.5, traced=False,
                         peaks=chipbench_tiny.PEAKS, t_start=time.perf_counter())
    assert set(line["metrics"]) == {"tokens_per_s", "ttft_p95_ms", "setup_s"}
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["correct"] is True


def test_untraced_run_keeps_the_program_tracer_off(monkeypatch):
    """End-to-end metrics are measured with the program's tracer off: an
    untraced run records nothing, and a traced one leaves it off."""
    from chipbench import serve
    from repro.core import tracing

    held = []
    drive = serve.drive

    def keep(*args, **kw):
        out = drive(*args, **kw)
        held.append(out["run"])
        return out

    monkeypatch.setattr(serve, "drive", keep)
    enabled = []
    monkeypatch.setattr(tracing, "enable", lambda: enabled.append(1))
    line = bench_run.run(chipbench_tiny.cell(), chipbench_tiny.bench(),
                         seed=6, seconds=0.5, traced=False,
                         peaks=chipbench_tiny.PEAKS, t_start=time.perf_counter())
    assert line["correct"] is True
    assert held[0].spans == [] and held[0].calls == {}
    assert not enabled and not tracing.TRACER.on


def test_main_refuses_a_host_without_a_tpu(capsys):
    rc = bench_run.main(["--workload", "minicpm-2b.decode", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 1 and out.out == ""
    assert "needs a TPU" in out.err


def test_main_refuses_a_negative_seed():
    with pytest.raises(SystemExit):
        bench_run.main(["--workload", "x", "--seed", "-1", "--seconds", "1"])
