"""What the program's tracer (``repro.core.tracing``) recorded in a run, as
the tracer metrics read it.

A traced run (``run.py --trace 1``) turns the tracer on once the engines
are wrapped and keeps its records as ``run.spans``; an untraced run has
none, and every reader gives ``None``.  Times in the records are
``time.perf_counter_ns``, the clock of ``run.window``.
"""

from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional

#: a request's phases from submit to its first token (see repro.serving)
REQUEST_PHASES = ("client.queue", "rpc.open.send", "engine.admit_wait",
                  "engine.prefill", "rpc.cpu_charge", "rpc.open.reply",
                  "client.sample")


def records(run: Any) -> Optional[List[Any]]:
    return run.spans or None


def seconds(rec: Any) -> float:
    return (rec.t1_ns - rec.t0_ns) * 1e-9


def first_token_phases(run: Any) -> Dict[int, List[Any]]:
    """For each request submitted in the window that got its first token,
    its phases from submit to that token, in order."""
    by: Dict[int, List[Any]] = {}
    for r in records(run) or ():
        if r.request_id is not None and r.name in REQUEST_PHASES:
            by.setdefault(r.request_id, []).append(r)
    out: Dict[int, List[Any]] = {}
    for rid, ph in by.items():
        ph.sort(key=lambda r: r.t0_ns)
        if (ph[0].name == "client.queue" and ph[-1].name == "client.sample"
                and run.in_window(ph[0].t0_ns * 1e-9)):
            out[rid] = ph
    return out


def in_window(run: Any, name: str) -> List[Any]:
    """The records named ``name`` that start in the window."""
    return [r for r in records(run) or ()
            if r.name == name and run.in_window(r.t0_ns * 1e-9)]


def tiling(run: Any) -> Dict[str, Any]:
    """Each request submitted in the window: the sum of its phases against
    its first-token wait by the benchmark's stamps (``record.Request``)."""
    reqs = sorted((r for r in run.requests
                   if run.in_window(r.submitted) and r.stamps),
                  key=lambda r: r.submitted)
    subs = [r.submitted for r in reqs]
    flows = first_token_phases(run)
    worst = 0.0
    matched = set()
    for ph in flows.values():
        # the benchmark stamps ``submitted`` just before ``submit`` starts
        # the request's first phase
        i = bisect.bisect_right(subs, ph[0].t0_ns * 1e-9) - 1
        if i < 0:
            continue
        matched.add(i)
        total = sum(seconds(r) for r in ph)
        ttft = reqs[i].stamps[0] - reqs[i].submitted
        worst = max(worst, abs(total - ttft) / ttft)
    return {"requests": len(reqs), "with_phases": len(flows),
            "matched": len(matched), "worst_rel_gap": worst}


def where_ttft_goes(run: Any) -> Dict[str, Any]:
    """Host seconds in each phase, summed over the window's requests, and
    per prompt length (the first hop's prefill tokens) the requests' count,
    mean first-token wait and mean time held on the simulated charge."""
    totals: Dict[str, float] = {}
    by_len: Dict[int, list] = {}
    for ph in first_token_phases(run).values():
        for r in ph:
            totals[r.name] = totals.get(r.name, 0.0) + seconds(r)
        n = next(r.attrs["tokens"] for r in ph if r.name == "engine.prefill")
        row = by_len.setdefault(n, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += (ph[-1].t1_ns - ph[0].t0_ns) * 1e-9
        row[2] += sum(seconds(r) for r in ph if r.name == "rpc.cpu_charge")
    return {"phase_s": totals,
            "by_prompt": {str(n): {"requests": c, "ttft_s": t / c,
                                   "charge_s": q / c}
                          for n, (c, t, q) in sorted(by_len.items())}}
