"""Reduction of a profiler trace (``jax.profiler``'s ``.xplane.pb``) to
the numbers the per-layer readers take.

``extract`` turns the file into plain lists; everything after it works on
those lists, so it is checked on small synthetic ones.  Device planes are
``/device:TPU:<n>``: their ``XLA Ops`` line gives busy time, their ``XLA
Modules`` line one event per launched program (``jit_<name>``).  Host
spans are the ``jax.profiler.TraceAnnotation`` names of ``HOST_SPANS``.
Times are in seconds on the trace's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # (name, start, end)

#: host spans the benchmark writes (see serve.py), then the program's
#: synchronous spans, which its tracer mirrors into the trace while it is on
HOST_SPANS = ("window", "client", "prefill", "fused_step", "kv_copy",
              "engine.prefill", "kv.write_prefill", "engine.step",
              "engine.fused", "kv.append", "client.sample")


@dataclasses.dataclass
class Reduced:
    """One traced window: per device its op and module events, and the
    benchmark's host spans."""

    ops: List[List[Event]]
    modules: List[List[Event]]
    host: List[Event]

    def window(self) -> Optional[Interval]:
        w = [(s, e) for n, s, e in self.host if n == "window"]
        return w[0] if w else None


def extract(trace_dir: str) -> Reduced:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}: {paths}")
    data = ProfileData.from_file(paths[0])
    ops: List[List[Event]] = []
    modules: List[List[Event]] = []
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            by_line = {ln.name: ln for ln in plane.lines}
            ops.append(_events(by_line.get("XLA Ops")))
            modules.append(_events(by_line.get("XLA Modules")))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(e for e in _events(ln) if e[0] in HOST_SPANS)
    return Reduced(ops, modules, host)


def _events(line) -> List[Event]:
    if line is None:
        return []
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_s(ops: Sequence[Event], window: Interval) -> float:
    """Seconds of ``window`` in which some operation ran on the device."""
    return sum(e - s for s, e in merge(clip(((s, e) for _, s, e in ops),
                                            window)))


def module_time(modules: Sequence[Event], name: str,
                window: Interval) -> Tuple[float, int]:
    """Total device seconds and count of the launches of program ``name``
    (``jit_<fn>``, any suffix) that start in the window."""
    hits = [(s, e) for n, s, e in modules
            if (n == name or n.startswith(name + "(") or n.startswith(name + "."))
            and window[0] <= s <= window[1]]
    return sum(e - s for s, e in hits), len(hits)


def op_kind(name: str) -> str:
    """An op event's name is its HLO instruction (``%fusion.12 = f32[...]
    fusion(...)``): keep the instruction's name without its number."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def top_ops(ops: Sequence[Event], window: Interval,
            k: int = 10) -> List[List]:
    """The ``k`` kinds of device operation that took the most time."""
    tot: Dict[str, float] = {}
    for n, s, e in ops:
        if window[0] <= s <= window[1]:
            kind = op_kind(n)
            tot[kind] = tot.get(kind, 0.0) + (e - s)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def idle_gaps(ops: Sequence[Event], host: Sequence[Event], window: Interval,
              k: int = 10) -> List[List]:
    """Device idle time in the window, summed by the innermost host span
    open at each gap's midpoint (``none`` where the benchmark had none)."""
    busy = merge(clip(((s, e) for _, s, e in ops), window))
    edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    # the host's spans nest (one thread), so a sweep with a stack of the
    # spans open at each midpoint finds the innermost one
    spans = sorted(((s, -e, n) for n, s, e in host if n != "window"))
    stack: List[Tuple[float, str]] = []
    i = 0
    tot: Dict[str, float] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        while i < len(spans) and spans[i][0] <= mid:
            s, neg_e, n = spans[i]
            while stack and stack[-1][0] < s:
                stack.pop()
            stack.append((-neg_e, n))
            i += 1
        while stack and stack[-1][0] < mid:
            stack.pop()
        name = stack[-1][1] if stack else "none"
        tot[name] = tot.get(name, 0.0) + (b - a)
    return [[n, t] for n, t in sorted(tot.items(), key=lambda x: -x[1])[:k]]


def summary(t: Reduced) -> str:
    """One line on what the trace held, for the run's log."""
    win = t.window()
    parts = [f"trace: window {win}, {len(t.host)} host spans"]
    for i, (ops, mods) in enumerate(zip(t.ops, t.modules)):
        lo = min((s for _, s, _ in ops), default=None)
        hi = max((e for _, _, e in ops), default=None)
        tot: Dict[str, float] = {}
        for n, s, e in mods:
            tot[n] = tot.get(n, 0.0) + (e - s)
        top = sorted(tot.items(), key=lambda x: -x[1])[:6]
        parts.append(f"device {i}: {len(ops)} ops in [{lo}, {hi}], "
                     f"{len(mods)} launches, top programs {top}")
    return "; ".join(parts)
