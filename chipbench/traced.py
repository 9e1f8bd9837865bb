#!/usr/bin/env python3
"""Runs one cell's traced window with the program's tracer on, and prints
one JSON line.

    python3 chipbench/traced.py --workload granite-8b-9l.docqa \\
        --seed 7 --seconds 50 --tracer 1

This is ``run.py --trace 1`` (the same set-up, window, trace, metrics and
check, through ``run.run``) with three additions, made from outside the
harness's files:

* with ``--tracer 1``, ``repro.core.tracing`` is enabled when the window
  starts and drained once the window's requests have had their first
  token; the records go to the run as ``run.spans``;
* the program's synchronous span names join the trace's host spans, so
  ``breakdown.idle_gaps`` names them;
* the line's metrics gain the cell's end-to-end metrics and the tracer
  metrics (``TRACER_METRICS``), and ``tracer`` gives the record count, how
  closely each request's phases sum to its first-token wait as the
  benchmark's own stamps measure it, and where that wait went.

``--tracer 0`` makes the same traced run with the tracer off, for the cost
of tracing.  Without a TPU it exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional, Sequence  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

#: the program's synchronous spans (mirrored into the profiler's trace)
PROGRAM_SPANS = ("engine.prefill", "kv.write_prefill", "engine.step",
                 "kv.upload", "engine.fused", "kv.append", "client.sample")
#: the metrics read from the tracer's records, with their units
TRACER_METRICS = {"ttft_charge_share": "%", "admit_wait_ms": "ms",
                  "decode_rows_per_step": "rows",
                  "kv_upload_useful_share": "%"}


def tiling(run: Any) -> Dict[str, Any]:
    """Each request submitted in the window: the sum of its phases against
    its first-token wait by the benchmark's stamps (``record.Request``)."""
    from chipbench import phases

    reqs = sorted((r for r in run.requests
                   if run.in_window(r.submitted) and r.stamps),
                  key=lambda r: r.submitted)
    subs = [r.submitted for r in reqs]
    flows = phases.first_token_phases(run)
    worst = 0.0
    matched = set()
    for ph in flows.values():
        # the benchmark stamps ``submitted`` just before ``submit`` starts
        # the request's first phase
        i = bisect.bisect_right(subs, ph[0].t0_ns * 1e-9) - 1
        if i < 0:
            continue
        matched.add(i)
        total = sum(phases.seconds(r) for r in ph)
        ttft = reqs[i].stamps[0] - reqs[i].submitted
        worst = max(worst, abs(total - ttft) / ttft)
    return {"requests": len(reqs), "with_phases": len(flows),
            "matched": len(matched), "worst_rel_gap": worst}


def where_ttft_goes(run: Any) -> Dict[str, Any]:
    """Host seconds in each phase, summed over the window's requests, and
    per prompt length (the first hop's prefill tokens) the requests' count,
    mean first-token wait and mean time held on the simulated charge."""
    from chipbench import phases

    totals: Dict[str, float] = {}
    by_len: Dict[int, list] = {}
    for ph in phases.first_token_phases(run).values():
        for r in ph:
            totals[r.name] = totals.get(r.name, 0.0) + phases.seconds(r)
        n = next(r.attrs["tokens"] for r in ph if r.name == "engine.prefill")
        row = by_len.setdefault(n, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += (ph[-1].t1_ns - ph[0].t0_ns) * 1e-9
        row[2] += sum(phases.seconds(r) for r in ph
                      if r.name == "rpc.cpu_charge")
    return {"phase_s": totals,
            "by_prompt": {str(n): {"requests": c, "ttft_s": t / c,
                                   "charge_s": q / c}
                          for n, (c, t, q) in sorted(by_len.items())}}


def run_traced(cell: Dict[str, Any], bench: Dict[str, Any], *, seed: int,
               seconds: float, peaks: Dict[str, Any], t_start: float,
               tracer: bool = True) -> Dict[str, Any]:
    """``run.run(..., traced=True)`` with the additions above."""
    from repro.core import tracing

    from chipbench import run as bench_run
    from chipbench import serve, spec, trace as tr

    held: Dict[str, Any] = {}
    instrument, drive = serve.instrument, serve.drive
    host_spans = tr.HOST_SPANS

    def instrument_and_enable(dep, config, clock):
        calls = instrument(dep, config, clock)
        if tracer:
            tracing.enable()
        return calls

    def drive_and_drain(*args, **kw):
        out = drive(*args, **kw)
        tracing.disable()
        out["run"].spans = tracing.drain()
        held["run"] = out["run"]
        return out

    serve.instrument, serve.drive = instrument_and_enable, drive_and_drain
    tr.HOST_SPANS = host_spans + PROGRAM_SPANS
    try:
        line = bench_run.run(cell, bench, seed=seed, seconds=seconds,
                             traced=True, peaks=peaks, t_start=t_start)
    finally:
        serve.instrument, serve.drive, tr.HOST_SPANS = (instrument, drive,
                                                        host_spans)
        tracing.disable()
    run = held["run"]
    units = {m["name"]: m["unit"] for m in spec.metrics(bench, cell["name"],
                                                        traced=False)}
    units.update(TRACER_METRICS)
    for name, unit in units.items():
        v = spec.reader(name)(run)
        if v is not None:
            line["metrics"][name] = {"value": v, "unit": unit}
    checks = line.pop("checks")
    line["tracer"] = {"on": tracer, "records": len(run.spans),
                      "dropped": tracing.TRACER.dropped, "tiling": tiling(run),
                      "first_token": where_ttft_goes(run)}
    line["checks"] = checks
    return line


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--tracer", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    import jax

    from chipbench import run as bench_run
    from chipbench import spec

    devices = jax.devices()
    if devices[0].platform != "tpu":
        bench_run.log(f"needs a TPU; JAX found platform "
                      f"{devices[0].platform!r}")
        return 1
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    if len(devices) < cell["chips"]:
        bench_run.log(f"{args.workload} needs {cell['chips']} TPU devices")
        return 1
    peaks = spec.peaks(devices[0].device_kind)
    bench_run.log(f"device {devices[0].device_kind} x{len(devices)}; compile "
                  f"cache {bench_run.configure_compile_cache()}; tracer "
                  f"{'on' if args.tracer else 'off'}")
    line = run_traced(cell, bench, seed=args.seed, seconds=args.seconds,
                      peaks=peaks, t_start=T_START, tracer=bool(args.tracer))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
