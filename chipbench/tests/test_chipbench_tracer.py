"""The tracer metrics: each reader on small synthetic runs, the tiny cell
run through ``traced.py`` on the CPU, and the program's synchronous spans
in a recorded profiler trace."""

import time

import pytest

import chipbench_tiny
from chipbench import phases, record, spec, traced
from chipbench import trace as tr
from repro.core.tracing import Record

MS = 1_000_000        # ns


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


@pytest.mark.parametrize("metric", sorted(traced.TRACER_METRICS))
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
    spans = [rec("engine.step", 100, 110, rows=1),
             rec("engine.step", 120, 130, rows=3),
             rec("engine.step", 140, 150, rows=0),
             rec("engine.step", 1200, 1210, rows=4),      # after the window
             rec("kv.upload", 101, 105, bytes=1000, live_bytes=50),
             rec("kv.upload", 121, 125, bytes=1000, live_bytes=150),
             rec("kv.upload", 1201, 1205, bytes=1000, live_bytes=1000)]
    run = run_with(spans)
    assert read("decode_rows_per_step", run) == pytest.approx(4 / 3)
    assert read("kv_upload_useful_share", run) == pytest.approx(10.0)


def test_tiling_matches_phases_to_the_benchmarks_stamps():
    a = request(1, 100, queue=10, send=1, admit=4, prefill=60, charge=300,
                reply=1, sample=2)
    ttft = (a[-1].t1_ns - a[0].t0_ns) * 1e-9
    req = record.Request(prompt=None, n_tokens=1, submitted=0.0999,
                         stamps=[0.0999 + ttft * 1.01])
    run = run_with(a)
    run.requests = [req]
    got = traced.tiling(run)
    assert got["requests"] == got["with_phases"] == got["matched"] == 1
    assert got["worst_rel_gap"] == pytest.approx(0.01 / 1.01, rel=1e-3)


def test_tiny_cell_reports_the_tracer_metrics():
    line = traced.run_traced(chipbench_tiny.cell(), chipbench_tiny.bench(),
                             seed=2 ** 31 + 78, seconds=1.0,
                             peaks=chipbench_tiny.PEAKS,
                             t_start=time.perf_counter())
    assert line["correct"] is True
    m = line["metrics"]
    for name in traced.TRACER_METRICS:
        assert m[name]["value"] > 0, name
    assert 0 < m["ttft_charge_share"]["value"] < 100
    rows = m["decode_rows_per_step"]["value"]
    assert 1 <= rows <= chipbench_tiny.MIX["n_slots"]
    assert 0 < m["kv_upload_useful_share"]["value"] < 100
    for name in ("tokens_per_s", "ttft_p95_ms", "setup_s", "kv_copy_ms"):
        assert name in m, name
    til = line["tracer"]["tiling"]
    assert til["with_phases"] == til["matched"] == til["requests"] > 0
    assert til["worst_rel_gap"] < 0.02
    assert line["tracer"]["dropped"] == 0
    assert list(line)[-1] == "checks"


def test_program_spans_reach_the_profiler_trace(tmp_path, monkeypatch):
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import tracing
    from repro.core.simnet import Sim
    from repro.models import ops_for
    from repro.serving.batch import BatchEngine
    from repro.serving.sharded import ShardModule

    cfg = get_config("granite-8b").reduced(n_layers=2, d_model=32, vocab=128)
    params = ops_for(cfg).init(cfg, jax.random.PRNGKey(0))
    eng = BatchEngine(ShardModule(cfg, params, (0, 2), True, True), Sim(),
                      n_slots=2, page_size=8)
    x = np.arange(10, dtype=np.int32)[None]

    def drive(sid):
        eng.sim.run_process(eng.open(sid, x, 16))
        eng.step([sid], np.asarray([3], np.int32))

    drive("warm")                                        # compile first
    monkeypatch.setattr(tr, "HOST_SPANS", tr.HOST_SPANS + traced.PROGRAM_SPANS)
    tracing.enable()
    try:
        jax.profiler.start_trace(str(tmp_path))
        with jax.profiler.TraceAnnotation("window"):
            drive("traced")
        jax.profiler.stop_trace()
    finally:
        tracing.disable()
        tracing.drain()
    names = [n for n, _, _ in tr.extract(str(tmp_path)).host]
    for name in ("engine.prefill", "kv.write_prefill", "engine.step",
                 "kv.upload", "engine.fused", "kv.append"):
        assert names.count(name) == 1, (name, names)
