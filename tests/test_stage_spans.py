"""Stage timing inside the serving engine: every flush's ``FlushRecord``
carries ordered stamps whose stages add up to the dispatch and retire
times, the ``Tracer`` rebuilds its stage children from those stamps and
nothing else, the uninstrumented path records the stamps and no spans,
and under a ``jax.profiler`` session every ``serve.*`` stage lands on the
host plane of the profile."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.core import PCAConfig
from repro.obs import Observability, Tracer, validate_trace
from repro.obs.tracing import STAGE_PREFIX, stage
from repro.serving import BucketPolicy, PCAServer

STAGES = ("stack", "lookup", "put", "launch", "wait", "fetch", "unpack")


class TickingClock:
    """Injected clock that moves 1 ms forward on every read, so each stage
    has a distinct, exactly representable start and end."""

    def __init__(self):
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        return self.reads * 1e-3


def _burst(n=6, seed=0):
    rng = np.random.default_rng(seed)
    mats = []
    for i in range(n):
        k = (6, 8)[i % 2]
        a = rng.standard_normal((k, k)).astype(np.float32)
        mats.append((a + a.T) / 2)
    return mats


def _server(clock, obs=None, max_inflight=1):
    return PCAServer(PCAConfig(T=8, S=4, sweeps=4), policy=BucketPolicy(T=8),
                     max_delay_s=10.0, max_inflight=max_inflight, obs=obs,
                     clock=clock)


def test_stage_reuses_a_given_start_and_reads_its_end():
    clock = TickingClock()
    with stage("stack", clock, start=0.5) as st:
        pass
    assert (st.start, st.end, clock.reads) == (0.5, 1e-3, 1)
    with stage("put", clock) as st:
        pass
    assert (st.start, st.end, clock.reads) == (2e-3, 3e-3, 3)
    assert st.seconds == pytest.approx(1e-3)


@pytest.mark.parametrize("max_inflight", [1, 2])
def test_flush_stamps_are_ordered_and_stages_add_up(max_inflight):
    clock = TickingClock()
    srv = _server(clock, max_inflight=max_inflight)
    srv.solve_many(_burst(), op="eigh")
    flushes = list(srv.stats.flush_records)
    assert len(flushes) == srv.stats.flushes >= 2
    for f in flushes:
        t_stacked = f.t_dispatch + f.stack_s
        t_looked = t_stacked + f.lookup_s
        stamps = [f.t_dispatch, t_stacked, t_looked, f.t_put, f.t_launched,
                  f.t_wait, f.t_ready, f.t_retire, f.t_done]
        assert stamps == sorted(stamps)
        # every stage read the ticking clock: none of them is empty
        for d in (f.stack_s, f.lookup_s, f.put_s, f.launch_s, f.wait_s,
                  f.fetch_s, f.unpack_s):
            assert d > 0
        assert (f.stack_s + f.lookup_s + f.put_s + f.launch_s
                == pytest.approx(f.dispatch_s, abs=1e-12))
        assert f.inflight_s == pytest.approx(f.overlap_s + f.wait_s
                                             + f.fetch_s, abs=1e-12)
    for r in srv.stats.records:      # requests keep "results on host"
        assert any(r.t_done == f.t_retire for f in flushes)


def test_tracer_stage_children_nest_inside_their_parents():
    clock = TickingClock()
    obs = Observability.enabled(clock=clock)
    srv = _server(clock, obs=obs)
    srv.solve_many(_burst(), op="eigh")
    doc = obs.trace_doc()
    assert validate_trace(doc) == []
    spans = {s.id: s for s in obs.tracer.spans}
    parent_of = {"stack": "dispatch", "lookup": "dispatch",
                 "put": "dispatch", "launch": "dispatch",
                 "dispatch": "flush:eigh", "inflight": "flush:eigh",
                 "wait": "flush:eigh", "fetch": "flush:eigh",
                 "retire": "flush:eigh", "unpack": "retire"}
    for name, parent in parent_of.items():
        found = [s for s in spans.values() if s.name == name]
        assert len(found) == srv.stats.flushes, name
        for s in found:
            p = spans[s.parent]
            assert p.name == parent
            assert p.ts <= s.ts and s.end <= p.end + 1e-12
    # the spans are the flush records' stamps, read once by the engine
    by_flush = {}
    for s in spans.values():
        if s.name.startswith("flush:"):
            by_flush[s.ts] = s
    for f in srv.stats.flush_records:
        flush = by_flush[f.t_dispatch]
        kids = {s.name: s for s in spans.values() if s.parent == flush.id}
        assert kids["wait"].dur == pytest.approx(f.wait_s)
        assert kids["fetch"].dur == pytest.approx(f.fetch_s)
        assert kids["fetch"].ts == f.t_ready


def test_uninstrumented_path_records_stamps_and_no_spans(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a span was recorded with obs off")
    for name in ("complete", "new_id", "begin"):
        monkeypatch.setattr(Tracer, name, refuse)
    clock = TickingClock()
    srv = _server(clock)
    out = srv.solve_many(_burst(), op="eigh")
    assert len(out) == 6 and srv.obs is None
    for f in srv.stats.flush_records:
        assert f.t_dispatch < f.t_put < f.t_launched < f.t_ready < f.t_done
        assert f.stack_s > 0 and f.lookup_s > 0


def _host_event_names(log_dir):
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, f"no profile written under {log_dir}"
    names = set()
    for plane in ProfileData.from_file(sorted(paths)[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    return names


def test_profiler_session_records_every_serve_stage_on_the_host_plane(
        tmp_path):
    srv = _server(TickingClock())
    srv.solve_many(_burst(4), op="eigh")        # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        srv.solve_many(_burst(4, seed=1), op="eigh")
    finally:
        jax.profiler.stop_trace()
    names = _host_event_names(tmp_path)
    assert {STAGE_PREFIX + s for s in STAGES} <= names
