"""Reduce a ``jax.profiler`` trace to the benchmark's device numbers.

Read with ``jax.profiler.ProfileData`` (nothing beyond JAX):

* device planes  ``/device:TPU:<n>``, one per chip; on each, the ``XLA
  Ops`` line holds every operation the chip ran and the ``XLA Modules``
  line every executable launch.  Only the planes of the cell's own chips
  count: a host may hold more chips than the cell uses.
* host spans     the benchmark's own ``TraceAnnotation`` spans
  (``bench.*``) on the host plane, on the same clock.
* the window     the ``bench.window`` span: what was traced on purpose.

busy    union of the operation intervals of one chip inside the window,
        averaged over the chips; idle share = 1 - busy / window.
modules device seconds per executable name, averaged over the chips.
ops     device seconds per operation name, averaged over the chips.
gaps    the longest idle stretches of the first chip inside the window,
        each labelled with the innermost ``bench.*`` span the host was in
        at the gap's middle ("client" when in none).
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclasses.dataclass
class TraceSummary:
    chips: int
    window_s: float
    busy_s: float                       # per chip, averaged
    modules: Dict[str, float]           # name -> device seconds per chip
    ops: Dict[str, float]               # name -> device seconds per chip
    gaps: List[Tuple[str, float]]       # (host span, seconds), longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Disjoint sorted union of intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def idle_gaps(busy: Sequence[Interval], lo: float, hi: float
              ) -> List[Interval]:
    """The stretches of [lo, hi] that ``busy`` (disjoint, sorted) leaves."""
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label_at(t: float, spans: Sequence[Tuple[str, float, float]]) -> str:
    """Innermost (shortest) span covering ``t``."""
    best, width = "client", float("inf")
    for name, s, e in spans:
        if s <= t <= e and e - s < width:
            best, width = name, e - s
    return best


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def xplane_path(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def summarize(path: str, device_ids: Sequence[int],
              n_gaps: int = 10) -> Optional[TraceSummary]:
    """The window's numbers on the chips ``device_ids``, read from the
    trace file at ``path``."""
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, device_ids,
                         n_gaps)


def reduce_planes(planes, device_ids: Sequence[int],
                  n_gaps: int = 10) -> Optional[TraceSummary]:
    """The window's device numbers over the planes of the chips
    ``device_ids``, or None when the trace holds none of them or no
    window span."""
    wanted = {int(i) for i in device_ids}
    spans: List[Tuple[str, float, float]] = []
    devices = []
    for plane in planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX) and name[len(DEVICE_PREFIX):
                                                    ].isdigit():
            dev = int(name[len(DEVICE_PREFIX):])
            if dev in wanted:
                lines = {line.name: _events(line) for line in plane.lines}
                devices.append((dev, lines))
        elif name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in _events(line)
                             if ev[0].startswith(SPAN_PREFIX))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not devices or not windows:
        return None
    lo, hi = windows[0]
    devices.sort()
    busy_total, modules, ops = 0.0, {}, {}
    gaps: List[Tuple[str, float]] = []
    for i, (_, lines) in enumerate(devices):
        op_ev = lines.get(OPS_LINE, [])
        busy = union(clip([(s, e) for _, s, e in op_ev], lo, hi))
        busy_total += sum(e - s for s, e in busy)
        for table, evs in ((ops, op_ev), (modules,
                                          lines.get(MODULES_LINE, []))):
            for n, s, e in evs:
                for cs, ce in clip([(s, e)], lo, hi):
                    table[n] = table.get(n, 0.0) + (ce - cs)
        if i == 0:
            inner = [sp for sp in spans if sp[0] != WINDOW]
            gaps = sorted(((label_at((s + e) / 2, inner), (e - s) * 1e-9)
                           for s, e in idle_gaps(busy, lo, hi)),
                          key=lambda g: -g[1])[:n_gaps]
    n = len(devices)
    return TraceSummary(
        chips=n, window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
        modules={k: v * 1e-9 / n for k, v in modules.items()},
        ops={k: v * 1e-9 / n for k, v in ops.items()},
        gaps=gaps)
