"""What one run leaves for the metric readers, and the arithmetic they
share.  Every time here is the host's ``time.perf_counter`` in seconds."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class Request:
    """One request as the closed-loop client saw it."""

    prompt: np.ndarray               # (S,) int32
    n_tokens: int
    submitted: float
    stamps: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)
    finished: bool = False
    failed: bool = False

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


@dataclasses.dataclass
class Run:
    """A run's record: the cell, the family module that counts its work,
    the window, the requests, and (traced runs only) the wrapped calls, the
    reduced trace and the program tracer's records."""

    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int
    peaks: Dict[str, Any]
    window: Tuple[float, float]
    setup_s: float
    requests: List[Request]
    calls: Dict[str, list] = dataclasses.field(default_factory=dict)
    trace: Optional[Any] = None
    spans: List[Any] = dataclasses.field(default_factory=list)
    family: Optional[Any] = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t <= self.window[1]


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolation percentile (numpy's default); None when empty."""
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) else None


def tokens_in_window(run: Run) -> int:
    return sum(1 for r in run.requests for t in r.stamps if run.in_window(t))


def token_gaps(run: Run) -> List[float]:
    """Every gap between consecutive tokens of a request whose later token
    was stamped in the window: stalls for other sessions' prefills
    included."""
    return [b - a for r in run.requests
            for a, b in zip(r.stamps, r.stamps[1:]) if run.in_window(b)]


def first_token_waits(run: Run) -> List[float]:
    """Submit to first token, for every request submitted in the window."""
    return [r.stamps[0] - r.submitted for r in run.requests
            if run.in_window(r.submitted) and r.stamps]


def wall_inside(calls: Sequence[Any], window: Tuple[float, float]) -> float:
    """Seconds of ``window`` covered by the calls (which never overlap:
    the host runs one at a time)."""
    lo, hi = window
    return sum(max(0.0, min(c.end, hi) - max(c.start, lo)) for c in calls)


def calls_in_window(calls: Sequence[Any], window: Tuple[float, float]) -> list:
    return [c for c in calls if window[0] <= c.start <= window[1]]
