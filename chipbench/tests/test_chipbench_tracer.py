"""The tracer metrics: each reader on small synthetic runs, and the tiny
cell's traced run on the CPU (``run.run``), which carries the program's
records, its synchronous spans in the profiler trace, and each fused
call's live lengths."""

import contextlib
import io
import json
import re
import time

import pytest

import chipbench_tiny
from chipbench import phases, record, serve, spec
from chipbench import run as bench_run
from repro.core import tracing
from repro.core.tracing import Record

MS = 1_000_000        # ns
#: the per-layer metrics that read the program tracer's records
TRACER_METRICS = sorted(m["name"] for m in spec.load_benchmark()["per_layer"]
                        if m["source"] == "program_span")
#: the program's synchronous spans, mirrored into the profiler's trace
PROGRAM_SPANS = ("engine.prefill", "kv.write_prefill", "engine.step",
                 "engine.fused", "kv.append", "client.sample")


def rec(name, t0_ms, t1_ms, rid=None, **attrs):
    return Record(name, 0, None, rid, int(t0_ms * MS), int(t1_ms * MS), attrs)


def run_with(spans, window=(0.0, 1.0)):
    r = record.Run(config={}, traffic={}, chips=1, peaks={}, window=window,
                   setup_s=0.0, requests=[])
    if spans is not None:
        r.spans = spans
    return r


def request(rid, t0, queue, send, admit, prefill, charge, reply, sample):
    """One request's phases over two hops, back to back from ``t0`` ms."""
    steps = [("client.queue", queue)]
    for _ in range(2):
        steps += [("rpc.open.send", send), ("engine.admit_wait", admit),
                  ("engine.prefill", prefill), ("rpc.cpu_charge", charge),
                  ("rpc.open.reply", reply)]
    steps.append(("client.sample", sample))
    out, t = [], t0
    for name, d in steps:
        out.append(rec(name, t, t + d, rid))
        t += d
    return out


def read(metric, run):
    return spec.reader(metric)(run)


@pytest.mark.parametrize("metric", TRACER_METRICS)
def test_reader_is_silent_without_tracer_records(metric):
    assert read(metric, run_with(None)) is None
    assert read(metric, run_with([])) is None


def test_charge_share_and_admit_wait_read_first_token_phases():
    a = request(1, 100, queue=10, send=1, admit=4, prefill=60, charge=300,
                reply=1, sample=2)
    b = request(2, 200, queue=30, send=1, admit=0, prefill=60, charge=100,
                reply=1, sample=2)
    late = request(3, 1500, queue=5, send=1, admit=0, prefill=6, charge=9,
                   reply=1, sample=2)                   # submitted after
    open_ = request(4, 300, queue=5, send=1, admit=0, prefill=6, charge=9,
                    reply=1, sample=2)[:-1]             # no first token
    child = rec("kv.write_prefill", 105, 106, 1)
    run = run_with(a + b + late + open_ + [child])
    assert sorted(phases.first_token_phases(run)) == [1, 2]
    ttft_a = 10 + 2 * (1 + 4 + 60 + 300 + 1) + 2
    ttft_b = 30 + 2 * (1 + 0 + 60 + 100 + 1) + 2
    share = read("ttft_charge_share", run)
    assert share == pytest.approx(100.0 * (600 + 200) / (ttft_a + ttft_b))
    wait = read("admit_wait_ms", run)
    assert wait == pytest.approx(((10 + 8) + (30 + 0)) / 2)


def test_rows_per_step_and_useful_upload_share():
    """``decode_rows_per_step`` over the window's ``engine.step`` spans; the
    upload share's reader went with the pool copy it read."""
    spans = [rec("engine.step", 100, 110, rows=1),
             rec("engine.step", 120, 130, rows=3),
             rec("engine.step", 140, 150, rows=0),
             rec("engine.step", 1200, 1210, rows=4),      # after the window
             rec("kv.append", 101, 105, rows=1, bytes=1000)]
    run = run_with(spans)
    assert read("decode_rows_per_step", run) == pytest.approx(4 / 3)


def test_tiling_matches_phases_to_the_benchmarks_stamps():
    a = request(1, 100, queue=10, send=1, admit=4, prefill=60, charge=300,
                reply=1, sample=2)
    ttft = (a[-1].t1_ns - a[0].t0_ns) * 1e-9
    req = record.Request(prompt=None, n_tokens=1, submitted=0.0999,
                         stamps=[0.0999 + ttft * 1.01])
    run = run_with(a)
    run.requests = [req]
    got = phases.tiling(run)
    assert got["requests"] == got["with_phases"] == got["matched"] == 1
    assert got["worst_rel_gap"] == pytest.approx(0.01 / 1.01, rel=1e-3)


@pytest.fixture(scope="module")
def traced_tiny():
    """One traced run of the tiny cell: its result line, its record and
    what it logged."""
    held = {}
    drive = serve.drive

    def keep(*args, **kw):
        out = drive(*args, **kw)
        held["run"] = out["run"]
        return out

    err = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stderr(err):
        mp.setattr(serve, "drive", keep)
        line = bench_run.run(chipbench_tiny.cell(), chipbench_tiny.bench(),
                             seed=2 ** 31 + 78, seconds=1.0, traced=True,
                             peaks=chipbench_tiny.PEAKS,
                             t_start=time.perf_counter())
    return line, held["run"], err.getvalue()


def test_tiny_cell_reports_the_tracer_metrics(traced_tiny):
    line, run, log = traced_tiny
    assert line["correct"] is True
    m = line["metrics"]
    for name in TRACER_METRICS:
        assert m[name]["value"] > 0, name
    assert 0 < m["ttft_charge_share"]["value"] < 100
    rows = m["decode_rows_per_step"]["value"]
    assert 1 <= rows <= chipbench_tiny.MIX["n_slots"]
    assert "kv_copy_ms" in m and "mfu" in m
    assert run.spans and not tracing.TRACER.on
    got = re.search(r"tracer: (\d+) records, (\d+) dropped; first-token "
                    r"phases against the stamps (\{.*?\});", log)
    assert got and int(got[1]) == len(run.spans) and got[2] == "0"
    til = json.loads(got[3])
    assert til["with_phases"] == til["matched"] == til["requests"] > 0
    assert til["worst_rel_gap"] < 0.02
    assert list(line)[-1] == "checks"


def test_program_spans_reach_the_profiler_trace(traced_tiny):
    _, run, _ = traced_tiny
    names = {n for n, _, _ in run.trace.host}
    for name in PROGRAM_SPANS:
        assert name in names, (name, sorted(names))
    # the profiler runs from the window's start until after the drain, so
    # steps lie in the window or after it, never before
    window = run.trace.window()
    steps = [s for n, s, _ in run.trace.host if n == "engine.step"]
    assert steps and all(window[0] <= s for s in steps)
    assert any(s <= window[1] for s in steps)


def test_fused_calls_carry_their_live_lengths(traced_tiny):
    _, run, _ = traced_tiny
    family, _ = spec.family(run.config)
    calls = run.calls["fused"]
    assert calls
    for c in calls:
        info = c.info
        assert info["lengths"] and all(n > 0 for n in info["lengths"])
        assert len(info["lengths"]) <= chipbench_tiny.MIX["n_slots"]
        recount = family.fused_step(run.config, info["n_layers"],
                                    info["first"], info["last"],
                                    info["lengths"], 4)
        assert {k: info[k] for k in ("flops", "bytes")} == recount
    assert {(c.info["n_layers"], c.info["first"], c.info["last"])
            for c in calls} == {(1, True, False), (1, False, True)}
