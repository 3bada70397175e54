"""Host staging slabs (``batching.StagingPool``): every staged slab, and
the true sizes beside it, equals ``stack_requests`` plus the zero filler
byte for byte, whatever the slot held before; a slab is never handed to a
second flush while the first is in flight; free slabs stay within
``max_inflight`` over all buckets together; and the flush records say
whether the slab was kept or allocated."""
import numpy as np
import pytest

from repro.core import PCAConfig
from repro.obs import Observability
from repro.serving import BucketPolicy, PCAServer, stack_requests
from repro.serving.batching import StagingPool
from repro.serving.sharded import LocalExecutor


def reference(mats, bucket, bp):
    """Today's slab: stacked, padded, and zero filler up to ``bp``."""
    batch, n_active = stack_requests(mats, bucket)
    b = len(mats)
    batch = np.concatenate([batch, np.zeros((bp - b, *bucket), batch.dtype)])
    n_active = np.concatenate(
        [n_active, np.zeros((n_active.shape[0], bp - b), np.int32)], axis=1)
    return batch, n_active


def _mats(rng, shapes):
    return [rng.standard_normal(s).astype(np.float32) for s in shapes]


# flush sequences of one (1808, 64) bucket at bp=4: rows that shrink and
# grow, a full flush then a partial one (slots that held data become
# filler), and an exact-fit single request
SEQUENCES = {
    "shrink_grow": [[(1808, 64)], [(1797, 64)], [(1800, 64)],
                    [(1797, 50), (1808, 64)], [(1800, 60), (1797, 64)]],
    "full_then_partial": [[(1808, 64), (1797, 64), (1800, 50), (1799, 63)],
                          [(1797, 64), (1800, 64)], [(1808, 64)],
                          [(1800, 64)] * 4, [(1797, 64)]],
    "exact_fit_single": [[(1808, 64)], [(1808, 64)], [(1797, 33)],
                         [(1808, 64)]],
}


@pytest.mark.parametrize("name", sorted(SEQUENCES))
def test_pool_slab_equals_stacked_and_padded(name):
    rng = np.random.default_rng(0)
    pool, bucket, bp = StagingPool(), (1808, 64), 4
    for i, shapes in enumerate(SEQUENCES[name]):
        mats = _mats(rng, shapes)
        slab, reused = pool.take(mats, bucket, bp)
        assert reused == (i > 0)
        want = reference(mats, bucket, bp)[0]
        assert slab.array.dtype == want.dtype
        assert np.array_equal(slab.array, want)
        pool.release(slab, max_free=1)


def test_pool_seeded_sequence_over_two_slabs():
    """A seeded run of flushes of random sizes and shapes in one bucket,
    two slabs taken in turn (as two flushes in flight would)."""
    rng = np.random.default_rng(1234)
    pool, bucket, bp = StagingPool(), (48, 16), 4
    held = []
    for _ in range(60):
        b = int(rng.integers(1, bp + 1))
        shapes = [(int(rng.integers(33, 49)), int(rng.integers(1, 17)))
                  for _ in range(b)]
        mats = _mats(rng, shapes)
        slab, _ = pool.take(mats, bucket, bp)
        assert np.array_equal(slab.array, reference(mats, bucket, bp)[0])
        held.append(slab)
        if len(held) == 2:
            pool.release(held.pop(0), max_free=2)
    assert [s.key for s in pool._free] == [
        (bucket, bp, np.dtype(np.float32))]


def test_pool_refuses_a_matrix_outside_its_bucket():
    pool = StagingPool()
    with pytest.raises(ValueError, match="does not fit bucket"):
        pool.take([np.ones((9, 2), np.float32)], (8, 8), 2)
    with pytest.raises(ValueError, match="slots"):
        pool.take([np.ones((2, 2), np.float32)] * 3, (8, 8), 2)


class RecordingExecutor(LocalExecutor):
    """Copies each flush's slab and true sizes as the engine hands them
    over, and checks the slab is no in-flight flush's."""

    def __init__(self):
        self.server = None
        self.seen = []

    def submit(self, fn, batch, n_active, **kw):
        busy = [f.slab.array for f in self.server._inflight]
        assert not any(batch is a for a in busy)
        self.seen.append((batch.copy(), n_active.copy()))
        return super().submit(fn, batch, n_active, **kw)


def _server(max_inflight=1, obs=None):
    ex = RecordingExecutor()
    srv = PCAServer(PCAConfig(T=8, S=4, sweeps=3), policy=BucketPolicy(T=8),
                    max_delay_s=10.0, max_inflight=max_inflight,
                    executor=ex, obs=obs)
    ex.server = srv
    return srv


def _traffic(seed=7, n=34):
    """Same-bucket (24, 8) pca requests of shrinking and growing shapes."""
    rng = np.random.default_rng(seed)
    shapes = [(int(rng.integers(17, 25)), int(rng.integers(3, 9)))
              for _ in range(n)]
    return _mats(rng, shapes)


def _serve(srv, mats):
    """Bursts of 4, 4, 4, 2, 4, 1, ...: full flushes, three in a row, and
    partial ones forced by ``drain``, so filler slots follow live ones."""
    tickets, i = [], 0
    for b in (4, 4, 4, 2, 4, 1, 4, 4, 4, 3):
        tickets += [srv.submit(m, op="pca") for m in mats[i:i + b]]
        i += b
        if b < 4:
            srv.drain()
    return [t.result() for t in tickets]


def test_engine_hands_the_executor_the_stacked_slab():
    srv = _server()
    mats = _traffic()
    _serve(srv, mats)
    sizes = [f.batch_size for f in srv.stats.flush_records]
    at = np.cumsum([0] + sizes)
    assert len(srv.executor.seen) == len(sizes) > 4
    for (batch, n_active), lo, hi in zip(srv.executor.seen, at, at[1:]):
        want_b, want_n = reference(mats[lo:hi], (24, 8), 4)
        assert np.array_equal(batch, want_b)
        assert np.array_equal(n_active, want_n)
        assert n_active.dtype == np.int32


def _answers(results):
    return [np.concatenate([np.ravel(getattr(r, f)) for f in
                            ("components", "eigenvalues", "mean", "scale")])
            for r in results]


def _watch_free(srv):
    """Record how many slabs the pool keeps free after every release."""
    frees = []
    orig = srv._staging.release

    def release(slab, max_free):
        orig(slab, max_free)
        frees.append(len(srv._staging._free))
    srv._staging.release = release
    return frees


@pytest.mark.parametrize("depth", [2, 3])
def test_inflight_flushes_keep_their_own_slabs(depth):
    mats = _traffic()
    sync = _answers(_serve(_server(), mats))
    srv = _server(max_inflight=depth)
    frees = _watch_free(srv)
    piped = _answers(_serve(srv, mats))
    assert all(np.array_equal(a, b) for a, b in zip(piped, sync))
    assert max(f.inflight_depth for f in srv.stats.flush_records) == depth
    assert frees and max(frees) <= depth
    # one slab per flush that can be in flight at once, then reuse
    allocated = [f for f in srv.stats.flush_records if not f.slab_reused]
    assert len(allocated) == depth
    assert srv.stats.flush_records[0].slab_reused is False


def test_slab_reused_after_a_keys_first_flush():
    obs = Observability.enabled()
    srv = _server(obs=obs)
    _serve(srv, _traffic())
    flags = [f.slab_reused for f in srv.stats.flush_records]
    assert flags[0] is False and all(flags[1:])
    fam = obs.metrics.counter("serve_stage_slabs_total", labels=("event",))
    assert fam.labels("allocated").total == 1
    assert fam.labels("reused").total == len(flags) - 1


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_free_slabs_capped_across_buckets(depth):
    """Large buckets served in turn, more of them than ``max_inflight``:
    the pool never keeps more than ``max_inflight`` free slabs, dropping
    the least recently released, and a bucket that comes back within the
    cap reuses its slab."""
    srv = _server(max_inflight=depth)
    frees = _watch_free(srv)
    rng = np.random.default_rng(3)
    rows = (64, 128, 192, 256, 320)
    for r in rows + rows:
        srv.solve_many(_mats(rng, [(r, 8)] * 4), op="pca")
    assert len(frees) == 2 * len(rows)
    assert max(frees) == depth
    buckets = [s.key[0] for s in srv._staging._free]
    assert buckets == [(r, 8) for r in rows[len(rows) - depth:]]
    # five buckets in turn outrun any cap below five: nothing is kept long
    # enough to come back to
    assert not any(f.slab_reused for f in srv.stats.flush_records)
    # a bucket released within the last ``depth`` is taken again
    srv.solve_many(_mats(rng, [(rows[-1], 8)]), op="pca")
    assert srv.stats.flush_records[-1].slab_reused is True
