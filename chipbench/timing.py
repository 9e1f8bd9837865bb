"""Host-side clocks the benchmark puts around the system's calls.

``CompileClock`` and ``Timed`` are copied from ``chip_smoke.py`` (the
bring-up run), which checked them on the chip; ``Timed`` here also keeps
each call's start and a record of its arguments, and both waits are named
spans in the profiler's trace.  ``Spanned`` wraps any host call.  The
wrappers that add a sync (``Timed``) go on only in traced runs.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import jax


class CompileClock:
    """Sums JAX's compile events (trace, lowering, backend compile or cache
    read) while installed; ``count`` tells a call that compiled from one
    that did not.  Only lowering and backend compiles count: a trace event
    also fires, taking no time, on a call that hits the jit cache."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_: Any) -> None:
        if event in self.EVENTS:
            self.seconds += secs
            self.count += event != self.EVENTS[0]

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


class Call:
    """One wrapped call: host-clock start and end, and what the wrapper
    noted about it."""

    __slots__ = ("start", "end", "info")

    def __init__(self, start: float, end: float, info: Dict[str, Any]):
        self.start, self.end, self.info = start, end, info


class Timed:
    """Wraps a jitted function.  Each call first waits for its arguments
    (host-to-device copies still in flight) and then for its outputs
    (``block_until_ready``); both wall times are kept when no compile
    happened inside the call.  ``note(args)`` says what to keep of the
    arguments."""

    def __init__(self, fn: Callable, clock: CompileClock,
                 note: Callable[..., Dict[str, Any]], wait_span: str,
                 calls: List[Call]):
        self.fn = fn
        self.clock = clock
        self.note = note
        self.wait_span = wait_span
        self.calls = calls

    def __call__(self, *args: Any) -> Any:
        n = self.clock.count
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(self.wait_span):
            jax.block_until_ready(args)
        t1 = time.perf_counter()
        out = jax.block_until_ready(self.fn(*args))
        t2 = time.perf_counter()
        if self.clock.count == n:
            info = self.note(*args)
            info["input_wait"] = t1 - t0
            info["compute_wait"] = t2 - t1
            self.calls.append(Call(t0, t2, info))
        return out


class Spanned:
    """Wraps a host call in a named trace span and keeps its wall time."""

    def __init__(self, fn: Callable, span: str, calls: List[Call],
                 note: Optional[Callable[..., Dict[str, Any]]] = None):
        self.fn = fn
        self.span = span
        self.calls = calls
        self.note = note

    def __call__(self, *args: Any, **kw: Any) -> Any:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(self.span):
            out = self.fn(*args, **kw)
        info = self.note(*args, **kw) if self.note else {}
        self.calls.append(Call(t0, time.perf_counter(), info))
        return out
