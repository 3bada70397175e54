"""Trace reduction: interval arithmetic, and the reduction of a slice of
a trace recorded on a TPU v5e."""
import pathlib
from types import SimpleNamespace

import pytest

from harness import trace

DATA = pathlib.Path(__file__).parent / "data"
SLICE = DATA / "tenants-slice.xplane.pb"


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_clip_to_window():
    assert trace.clip([(0, 4), (6, 12), (20, 30)], 2, 10) == [(2, 4),
                                                             (6, 10)]


def test_idle_gaps():
    assert trace.idle_gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6),
                                                         (8, 10)]
    assert trace.idle_gaps([], 0, 3) == [(0, 3)]


def test_label_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.poll", 10, 50),
             ("bench.wait", 20, 30)]
    assert trace.label_at(25, spans) == "bench.wait"
    assert trace.label_at(40, spans) == "bench.poll"
    assert trace.label_at(200, spans) == "client"


def test_recorded_serving_slice():
    """18.8 ms cut from a trace of the small-matrix burst served on a v5e
    (three flushes of small buckets and the host's
    ``bench.*`` spans around them), with a ``bench.window`` span over the
    cut: the device is busy 6.27 ms, inside the three solver modules, and
    idle while the host is in ``bench.poll`` (stacking, launching,
    retiring)."""
    s = trace.summarize(str(SLICE), [0])
    assert s is not None and s.chips == 1
    assert s.window_s == pytest.approx(0.018768, rel=1e-3)
    assert s.busy_s == pytest.approx(0.006268, rel=1e-3)
    assert len(s.modules) == 3
    assert all(name.startswith("jit__lambda") for name in s.modules)
    assert sum(s.modules.values()) == pytest.approx(s.busy_s, rel=1e-3)
    assert s.gaps[0] == ("bench.poll", pytest.approx(0.004425, rel=1e-3))
    assert sum(g for _, g in s.gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-3)


def idle_plane(n):
    """A chip's plane on which nothing ran, as a host with more chips
    than the cell uses records it."""
    ops = SimpleNamespace(name=trace.OPS_LINE, events=[])
    return SimpleNamespace(name=f"{trace.DEVICE_PREFIX}{n}", lines=[ops])


def test_only_the_cells_chips_count():
    """An extra idle chip in the trace changes nothing for a cell on chip
    0; counted as the cell's own, it would halve the busy time."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(str(SLICE)).planes)
    alone = trace.reduce_planes(planes, [0])
    s = trace.reduce_planes(planes + [idle_plane(1)], [0])
    assert s.chips == 1
    assert s.busy_s == pytest.approx(alone.busy_s)
    assert s.modules == pytest.approx(alone.modules)
    both = trace.reduce_planes(planes + [idle_plane(1)], [0, 1])
    assert both.chips == 2
    assert both.busy_s == pytest.approx(alone.busy_s / 2)


def test_no_plane_of_the_cells_chips_reads_nothing():
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(str(SLICE)).planes)
    assert trace.reduce_planes(planes, [3]) is None
