"""End-to-end metrics, from the client's records of one window.

Each takes the window (its start, its end and every request the client
sent in it, fulfilled) and returns the value in the unit
``BENCHMARK.json`` gives.  ``setup_s`` is taken by the entry point.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List

import numpy as np

from .client import Sent


@dataclasses.dataclass
class Window:
    t_start: float
    t_end: float
    sent: List[Sent]

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start


def fit_s(w: Window) -> float:
    """All the time from the window's start to the last fulfilment, over
    the requests fulfilled (the last may finish after the window's end:
    no request is cut)."""
    last = max(s.t_done for s in w.sent)
    return (last - w.t_start) / len(w.sent)


def p99_ms(w: Window) -> float:
    """99th percentile of due-to-fulfilled latency over every request due
    in the window."""
    return float(np.percentile([s.latency_s for s in w.sent], 99)) * 1e3


def served_rps(w: Window) -> float:
    """Requests fulfilled inside the window, over the window's length."""
    return sum(1 for s in w.sent if s.t_done <= w.t_end) / w.seconds


METRICS: Dict[str, Callable[[Window], float]] = {
    "fit_s": fit_s,
    "p99_ms": p99_ms,
    "served_rps": served_rps,
}
