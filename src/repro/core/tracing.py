"""Host-time spans of the serving plane, for operators and benchmarks.

One tracer per process, off by default.  Turn it on around the interval to
explain, then take what it recorded::

    from repro.core import tracing

    tracing.enable()
    ...                              # serve
    records = tracing.drain()        # returns the records and clears them
    tracing.disable()

Every record has one shape, :class:`Record` ``(name, span_id, parent_id,
request_id, t0_ns, t1_ns, attrs)``, timed by ``time.perf_counter_ns``: the
host's clock, never the simulator's.  The program never reads a record
back, so tracing cannot change what the simulation does: a
``Sim(sanitize=True)`` run's trace digest is the same with it on or off.

There are two kinds of span:

* ``span(name, **attrs)`` is a context manager around synchronous host
  work.  Such spans nest on the one host thread, and the innermost open one
  is the parent of the next.  While the tracer is on, each also opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
  shows it on the device trace's clock.
* ``begin(name, ...)`` / ``end(token)`` bracket a phase that crosses a
  simulator ``yield``, while other sessions' work interleaves.  These stay
  in memory only: as annotations they would not nest.

A *flow* chains the phases of one piece of work, such as one request from
submit to its first token, or one decode round across the pipeline hops,
so that they tile its interval with no gap and no overlap.
``phase(flow, name)`` ends the flow's current phase and begins ``name`` at
the same instant.  ``span(name, flow=...)`` puts a synchronous span in the
chain.  Code on the serving side names a flow by a session id; the client
``link``s its sessions to the flows it starts.

Off, ``span`` returns one shared no-op, ``begin`` and ``start_flow`` return
``None``, and every other call returns at once: each site costs one test of
a boolean.  At most ``cap`` records are kept; ``dropped`` counts the rest.
"""

from __future__ import annotations

import itertools
import time
from typing import Any, Dict, Hashable, List, NamedTuple, Optional


class Record(NamedTuple):
    """One finished span.  Times are ``time.perf_counter_ns``."""

    name: str
    span_id: int
    parent_id: Optional[int]
    request_id: Optional[int]
    t0_ns: int
    t1_ns: int
    attrs: Dict[str, Any]


class _Open:
    """A begun span: what ``end`` needs to record it."""

    __slots__ = ("name", "span_id", "parent_id", "request_id", "t0", "attrs")

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 request_id: Optional[int], t0: int, attrs: Dict[str, Any]):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.request_id = request_id
        self.t0 = t0
        self.attrs = attrs


class _Flow:
    """The phases of one piece of work.  Between phases either ``current``
    is open, or the last member was a synchronous span that ended at
    ``ended``, where the next phase begins."""

    __slots__ = ("request_id", "parent_id", "epoch", "current", "ended",
                 "aliases", "live")

    def __init__(self, request_id: Optional[int], parent_id: Optional[int],
                 epoch: int):
        self.request_id = request_id
        self.parent_id = parent_id
        self.epoch = epoch            # flows outlive no ``disable``
        self.current: Optional[_Open] = None
        self.ended: Optional[int] = None
        self.aliases: List[Hashable] = []
        self.live = True


class _Noop:
    """What ``span`` returns while the tracer is off."""

    __slots__ = ()

    def __enter__(self) -> "_Noop":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None

    def set(self, **attrs: Any) -> None:
        return None


NOOP = _Noop()


class _Span:
    """A synchronous span, mirrored into the profiler's trace."""

    __slots__ = ("tracer", "name", "flow", "attrs", "open", "annotation")

    def __init__(self, tracer: "Tracer", name: str, flow: Any,
                 attrs: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.flow = flow
        self.attrs = attrs

    def __enter__(self) -> "_Span":
        tr = self.tracer
        self.annotation = tr._annotate(self.name)
        self.annotation.__enter__()
        t0 = time.perf_counter_ns()
        flow = tr._resolve(self.flow)
        if flow is not None:
            tr._cut(flow, t0)
        outer = tr._stack[-1] if tr._stack else None
        if flow is not None:
            rid = flow.request_id
            parent = outer.span_id if outer is not None else flow.parent_id
        elif outer is not None:
            rid, parent = outer.request_id, outer.span_id
        else:
            rid = parent = None
        self.flow = flow
        self.open = _Open(self.name, next(tr._ids), parent, rid, t0,
                          self.attrs)
        tr._stack.append(self.open)
        return self

    def set(self, **attrs: Any) -> None:
        self.open.attrs.update(attrs)

    def __exit__(self, *exc: Any) -> None:
        tr = self.tracer
        t1 = time.perf_counter_ns()
        tr._stack.pop()
        tr._emit(self.open, t1)
        if tr._resolve(self.flow) is not None:
            self.flow.ended = t1
        self.annotation.__exit__(*exc)
        return None


class Tracer:
    """Records spans while ``on``; see the module docstring."""

    def __init__(self, cap: int = 1 << 18):
        self.on = False
        self.cap = cap
        self.records: List[Record] = []
        self.dropped = 0
        self._ids = itertools.count(1)
        self._stack: List[_Open] = []
        self._aliases: Dict[Hashable, _Flow] = {}
        self._epoch = 0
        self._annotate: Any = None

    # -- switching ------------------------------------------------------------
    def enable(self) -> None:
        if self._annotate is None:
            from jax.profiler import TraceAnnotation
            self._annotate = TraceAnnotation
        self.on = True

    def disable(self) -> None:
        """Stop recording.  Phases still open are forgotten; what was
        recorded stays until ``drain``."""
        self.on = False
        self._epoch += 1
        self._aliases.clear()

    def drain(self) -> List[Record]:
        """The records so far, oldest first; the tracer keeps none."""
        out, self.records = self.records, []
        return out

    # -- spans ----------------------------------------------------------------
    def span(self, name: str, flow: Any = None, **attrs: Any) -> Any:
        """Context manager around synchronous host work; with ``flow`` (a
        flow or a name linked to one) it is also that flow's next phase."""
        if not self.on:
            return NOOP
        return _Span(self, name, flow, attrs)

    def begin(self, name: str, request: Optional[int] = None,
              parent: Optional[_Open] = None, **attrs: Any) -> Optional[_Open]:
        """Open a span that ``end`` closes, across simulator yields."""
        if not self.on:
            return None
        return _Open(name, next(self._ids),
                     None if parent is None else parent.span_id, request,
                     time.perf_counter_ns(), attrs)

    def end(self, token: Optional[_Open], **attrs: Any) -> None:
        if token is None or not self.on:
            return
        token.attrs.update(attrs)
        self._emit(token, time.perf_counter_ns())

    # -- flows ----------------------------------------------------------------
    def start_flow(self, request: Optional[int] = None,
                   parent: Optional[_Open] = None) -> Optional[_Flow]:
        """A new flow whose phases carry ``request`` and are children of
        ``parent`` (a ``begin`` token)."""
        if not self.on:
            return None
        return _Flow(request, None if parent is None else parent.span_id,
                     self._epoch)

    def link(self, flow: Optional[_Flow], *aliases: Hashable) -> None:
        """Let code that knows only ``aliases`` (session ids) name
        ``flow``, until it closes."""
        if not self.on or self._resolve(flow) is None:
            return
        for a in aliases:
            self._aliases[a] = flow
            flow.aliases.append(a)

    def phase(self, flow: Any, name: str, **attrs: Any) -> None:
        """End ``flow``'s current phase and begin ``name`` at one instant
        (where the last member was a finished synchronous span, ``name``
        begins where it ended)."""
        if not self.on:
            return
        f = self._resolve(flow)
        if f is None:
            return
        t0 = self._cut(f, time.perf_counter_ns())
        f.current = _Open(name, next(self._ids), f.parent_id, f.request_id,
                          t0, attrs)

    def close_flow(self, flow: Any) -> None:
        """End ``flow``'s current phase now and forget its aliases."""
        if not self.on:
            return
        f = self._resolve(flow)
        if f is None:
            return
        self._cut(f, time.perf_counter_ns())
        f.live = False
        for a in f.aliases:
            if self._aliases.get(a) is f:
                del self._aliases[a]
        f.aliases.clear()

    # -- internals ------------------------------------------------------------
    def _resolve(self, key: Any) -> Optional[_Flow]:
        if key is None:
            return None
        f = key if isinstance(key, _Flow) else self._aliases.get(key)
        if f is None or not f.live or f.epoch != self._epoch:
            return None
        return f

    def _cut(self, flow: _Flow, t: int) -> int:
        """Close the flow's open phase at ``t``; returns where the next
        phase begins."""
        if flow.current is not None:
            self._emit(flow.current, t)
            flow.current = None
            return t
        if flow.ended is not None:
            t, flow.ended = flow.ended, None
        return t

    def _emit(self, o: _Open, t1: int) -> None:
        if len(self.records) >= self.cap:
            self.dropped += 1
            return
        self.records.append(Record(o.name, o.span_id, o.parent_id,
                                   o.request_id, o.t0, t1, o.attrs))


#: the process's tracer, which the serving plane records into
TRACER = Tracer()

enable = TRACER.enable
disable = TRACER.disable
drain = TRACER.drain
span = TRACER.span
begin = TRACER.begin
end = TRACER.end
start_flow = TRACER.start_flow
link = TRACER.link
phase = TRACER.phase
close_flow = TRACER.close_flow
