#!/usr/bin/env python3
"""Runs one benchmark cell on the chip and prints one JSON line.

    python3 chipbench/run.py --workload granite-8b-9l.docqa --seed 7 \\
        --seconds 50 --trace 0

The cell (a configuration under a traffic mix) is looked up in
``BENCHMARK.json``.  Set-up (process start to the window: weights made
from the seed on the device, the deployment, warm-up of every shape the
mix uses) is ``setup_s``.  The window runs for ``--seconds`` and nothing
compiles in it; then the served tokens are checked against the plain
reference.  ``--trace 0`` reports the cell's end-to-end metrics, ``--trace
1`` its per-layer metrics from wrapped calls and a profiler trace of the
window, with the program's tracer (``repro.core.tracing``) on from just
before the window until its requests have drained.  The last line of
standard output is the result; the numbers compared, each beside its
limit, are the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional, Sequence  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def configure_compile_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    when set (JAX reads it itself), else ``<checkout>/.jax_cache``, a fixed
    path, since the path is part of the cache's key.  Every program is
    kept, however fast it compiled, so that a warm run compiles nothing."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def run(cell: Dict[str, Any], bench: Dict[str, Any], *, seed: int,
        seconds: float, traced: bool, peaks: Dict[str, Any],
        t_start: float, control: bool = False) -> Dict[str, Any]:
    """One run of a cell on whatever devices JAX has: set-up, window,
    check.  Returns the result line (``main`` makes sure it is a TPU)."""
    import jax

    from repro.core import tracing

    from chipbench import check, phases, serve, spec, trace as tr

    config, mix = cell["config"], cell["traffic"]
    family, program = spec.family(config)
    out = serve.drive(config, mix, seed=seed, seconds=seconds, traced=traced,
                      chips=cell["chips"], peaks=peaks, t_start=t_start,
                      family=family, program=program, log=log)
    run_rec = out["run"]
    log(f"compiles inside the window: {out['compiles']} (should be 0)")

    metrics: Dict[str, Any] = {}
    for m in spec.metrics(bench, cell["name"], traced):
        v = spec.reader(m["name"])(run_rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    devices = jax.devices()
    device: Dict[str, Any] = {"platform": devices[0].platform,
                              "kind": devices[0].device_kind,
                              "count": len(devices),
                              "memory_peak_bytes": out["peak"]}
    result: Dict[str, Any] = {}
    if traced:
        t = run_rec.trace
        win = t.window()
        used = t.ops[:cell["chips"]]
        device["busy_s"] = (sum(tr.busy_s(o, win) for o in used) / len(used)
                            if used else 0.0)
        device["window_s"] = win[1] - win[0]
        result["breakdown"] = {
            "device_ops": tr.top_ops(used[0], win) if used else [],
            "idle_gaps": tr.idle_gaps(used[0] if used else [], t.host, win)}
        log(f"tracer: {len(run_rec.spans)} records, "
            f"{tracing.TRACER.dropped} dropped; first-token phases against "
            f"the stamps {json.dumps(phases.tiling(run_rec))}; where the "
            f"first-token wait went {json.dumps(phases.where_ttft_goes(run_rec))}")

    in_window = [r for r in run_rec.requests
                 if run_rec.window[0] <= r.submitted <= run_rec.window[1]]
    failed = sum(r.failed for r in in_window)
    picked = check.sample(run_rec.requests, mix["check_requests"], seed)
    checks: Dict[str, Dict[str, float]] = {
        "failed_requests": {"value": failed, "limit": 0}}
    if picked:
        cmp = check.compare(picked, config, mix, seed, family, control=control)
        log(f"checked {cmp['tokens_checked']} tokens of "
            f"{cmp['requests_checked']} requests: {cmp}")
        result["check_detail"] = cmp
        for name, lim in cell["limits"].items():
            checks[name] = {"value": cmp[name], "limit": lim["limit"]}
    else:
        log("no finished request to check")
        checks["requests_checked"] = {"value": 0, "limit": 1}
    correct = bool(picked) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    line = {"correct": correct, "attempted": len(in_window), "failed": failed,
            "metrics": metrics, "device": device}
    line.update(result)
    line["checks"] = checks
    return line


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the bfloat16 reference in the program's place "
                         "in the check (the control: reads not correct)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    import jax

    from chipbench import spec

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"needs a TPU; JAX found platform {devices[0].platform!r} "
            f"({len(devices)} device(s))")
        return 1
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    if len(devices) < cell["chips"]:
        log(f"{args.workload} needs {cell['chips']} TPU devices, "
            f"found {len(devices)}")
        return 1
    peaks = spec.peaks(devices[0].device_kind)
    log(f"device {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{configure_compile_cache()}")
    line = run(cell, bench, seed=args.seed, seconds=args.seconds,
               traced=bool(args.trace), peaks=peaks, t_start=T_START,
               control=args.control)
    for name, c in line["checks"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
