"""What the program's tracer (``repro.core.tracing``) recorded in a run, as
the tracer metrics read it.

A run carries the tracer's records as ``run.spans`` when its window ran
with the tracer on (``chipbench/traced.py`` attaches them).  A run without
them, such as every run of ``run.py`` today, gives ``None`` to every
reader.  Times in the records are ``time.perf_counter_ns``, the clock of
``run.window``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

#: a request's phases from submit to its first token (see repro.serving)
REQUEST_PHASES = ("client.queue", "rpc.open.send", "engine.admit_wait",
                  "engine.prefill", "rpc.cpu_charge", "rpc.open.reply",
                  "client.sample")


def records(run: Any) -> Optional[List[Any]]:
    return getattr(run, "spans", None) or None


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
