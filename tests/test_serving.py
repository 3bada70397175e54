"""Serving subsystem: bucket policies, batched-solver equivalence with the
per-matrix path, exactness of bucket padding, PCAServer microbatching
(flush-on-full / flush-on-timeout / executable-cache reuse), and the
dispatch / in-flight / retire pipeline (sync-vs-async parity, back-pressure,
out-of-order retirement, synchronous degradation at max_inflight=1)."""
import dataclasses

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import PCAConfig, fit, jacobi_eigh, jacobi_svd
from repro.serving import (BucketPolicy, PCAServer, jacobi_eigh_batched,
                           jacobi_svd_batched, pad_to_bucket, padding_waste,
                           pca_fit_batched, stack_requests)


def _sym(n, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    return (a + a.T) / 2


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

def test_bucket_policy_tile():
    pol = BucketPolicy(T=16, mode="tile")
    assert pol.bucket_dim(1) == 16
    assert pol.bucket_dim(16) == 16
    assert pol.bucket_dim(17) == 32
    assert pol.bucket_shape((10, 50)) == (16, 64)


def test_bucket_policy_pow2():
    pol = BucketPolicy(T=16, mode="pow2")
    # tile counts round to powers of two: 1, 2, 4, 8 tiles
    assert pol.bucket_dim(16) == 16
    assert pol.bucket_dim(33) == 64
    assert pol.bucket_dim(70) == 128


def test_pad_and_stack():
    mats = [np.ones((3, 5), np.float32), np.ones((4, 2), np.float32)]
    batch, n_active = stack_requests(mats, (8, 8))
    assert batch.shape == (2, 8, 8)
    np.testing.assert_array_equal(n_active, [[3, 4], [5, 2]])
    assert batch[0, 3:, :].sum() == 0 and batch[0, :, 5:].sum() == 0
    with pytest.raises(ValueError):
        pad_to_bucket(np.ones((9, 2)), (8, 8))
    assert padding_waste((4, 4), (8, 8)) == pytest.approx(0.75)


def test_pad_to_bucket_error_paths_and_exact_fit():
    a = np.ones((4, 6), np.float32)
    # rank mismatch names both ranks
    with pytest.raises(ValueError, match="bucket rank 3 != matrix rank 2"):
        pad_to_bucket(a, (8, 8, 8))
    with pytest.raises(ValueError, match="rank 1"):
        pad_to_bucket(a, (8,))
    # per-dim overflow: either axis exceeding its bucket edge raises
    with pytest.raises(ValueError, match="dim 6 exceeds bucket dim 4"):
        pad_to_bucket(a, (8, 4))
    with pytest.raises(ValueError, match="exceeds"):
        pad_to_bucket(a, (3, 8))
    # exact fit is a no-op passthrough (no copy)
    assert pad_to_bucket(a, (4, 6)) is a
    padded = pad_to_bucket(a, (4, 8))
    assert padded.shape == (4, 8) and padded[:, 6:].sum() == 0


def test_bucket_dim_validation():
    with pytest.raises(ValueError, match="unknown bucket mode"):
        BucketPolicy(T=16, mode="fib")
    with pytest.raises(ValueError, match=">= 1"):
        BucketPolicy(T=0)
    with pytest.raises(ValueError, match=">= 1"):
        BucketPolicy(T=16).bucket_dim(0)


@pytest.mark.parametrize("T", [1, 3, 16])
def test_pow2_bucket_dim_properties(T):
    """Geometric bucketing invariants: every bucket edge covers its input,
    is idempotent (a bucket is its own bucket), is monotone in the input,
    and holds a power-of-two number of T-tiles."""
    pol = BucketPolicy(T=T, mode="pow2")
    dims = [pol.bucket_dim(n) for n in range(1, 6 * T + 2)]
    for n, d in zip(range(1, 6 * T + 2), dims):
        assert d >= n                           # covers
        assert d % T == 0                       # tile-aligned
        tiles = d // T
        assert tiles & (tiles - 1) == 0         # power-of-two tile count
        assert pol.bucket_dim(d) == d           # idempotent
    assert dims == sorted(dims)                 # monotone
    # pow2 coarsens tile counts, never refines them
    tile = BucketPolicy(T=T, mode="tile")
    assert all(pol.bucket_dim(n) >= tile.bucket_dim(n)
               for n in range(1, 6 * T + 2))


# ---------------------------------------------------------------------------
# batched solvers vs the per-matrix path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pivot", [
    "parallel", "cyclic",
    pytest.param("paper", marks=pytest.mark.slow)])  # 30-sweep DLE solve
def test_eigh_batched_matches_loop(pivot):
    mats = [_sym(12, seed=i) for i in range(4)]
    sweeps = 30 if pivot == "paper" else 12
    res = jacobi_eigh_batched(jnp.asarray(np.stack(mats)), sweeps=sweeps,
                              pivot=pivot)
    for i, m in enumerate(mats):
        ref = jacobi_eigh(jnp.asarray(m), sweeps=sweeps, pivot=pivot)
        np.testing.assert_allclose(np.asarray(res.eigenvalues[i]),
                                   np.asarray(ref.eigenvalues),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.abs(np.asarray(res.eigenvectors[i])),
                                   np.abs(np.asarray(ref.eigenvectors)),
                                   atol=1e-4)


@pytest.mark.parametrize("angle", ["rutishauser", "atan2", "cordic"])
def test_bucket_padding_is_exact(angle):
    """A problem embedded in a zero-padded bucket: padded coordinates stay
    *exactly* unmixed (null-pivot guard), so padded eigenvalues are exact
    zeros, the padded block of V is exactly basis vectors, and the live
    eigenpairs match the un-padded solve."""
    n, nb = 11, 24
    a = _sym(n, seed=3)
    padded = np.zeros((1, nb, nb), np.float32)
    padded[0, :n, :n] = a
    res = jacobi_eigh_batched(jnp.asarray(padded), n_active=np.array([n]),
                              sweeps=14, angle=angle)
    w = np.asarray(res.eigenvalues[0])
    v = np.asarray(res.eigenvectors[0])
    assert np.all(w[n:] == 0.0)
    assert np.all(v[n:, :n] == 0.0)        # live eigenvectors: no padded mass
    assert np.all(v[:n, n:] == 0.0)        # padded eigenvectors: no live mass
    ref = np.linalg.eigh(a)[0][::-1]
    np.testing.assert_allclose(w[:n], ref, rtol=1e-4, atol=1e-4)


def test_bucket_padding_matches_native_solve():
    n, nb = 13, 32
    a = _sym(n, seed=7)
    padded = np.zeros((1, nb, nb), np.float32)
    padded[0, :n, :n] = a
    res = jacobi_eigh_batched(jnp.asarray(padded), n_active=np.array([n]),
                              sweeps=14)
    native = jacobi_eigh(jnp.asarray(a), sweeps=14)
    np.testing.assert_allclose(np.asarray(res.eigenvalues[0, :n]),
                               np.asarray(native.eigenvalues),
                               rtol=1e-4, atol=1e-4)


def test_svd_batched_mixed_shapes():
    rng = np.random.default_rng(5)
    shapes = [(20, 6), (17, 9), (24, 4)]
    bucket = (24, 16)
    mats = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    batch, (nr, nc) = stack_requests(mats, bucket)
    res = jacobi_svd_batched(jnp.asarray(batch), n_rows=nr, n_cols=nc,
                             sweeps=14)
    for i, (a, (m, d)) in enumerate(zip(mats, shapes)):
        ref = np.linalg.svd(a, compute_uv=False)
        np.testing.assert_allclose(np.asarray(res.S[i, :d]), ref,
                                   rtol=1e-4, atol=1e-4)
        u = np.asarray(res.U[i, :m, :d])
        s = np.asarray(res.S[i, :d])
        vt = np.asarray(res.Vt[i, :d, :d])
        np.testing.assert_allclose(u * s[None, :] @ vt, a, atol=2e-3)


def test_pca_fit_batched_matches_unbatched():
    rng = np.random.default_rng(6)
    cfg = PCAConfig(T=8, sweeps=15)
    shapes = [(40, 6), (50, 11)]
    bucket = (56, 16)
    mats = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    batch, (nr, nc) = stack_requests(mats, bucket)
    res = pca_fit_batched(jnp.asarray(batch), n_rows=nr, n_cols=nc,
                          config=cfg)
    for i, (x, (m, d)) in enumerate(zip(mats, shapes)):
        ref = fit(x, cfg)
        np.testing.assert_allclose(np.asarray(res.eigenvalues[i, :d]),
                                   np.asarray(ref.eigenvalues),
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(np.asarray(res.mean[i, :d]),
                                   np.asarray(ref.mean), atol=1e-5)
        np.testing.assert_allclose(np.asarray(res.cvcr[i, :d]),
                                   np.asarray(ref.cvcr), atol=1e-4)


def test_svd_matmul_fn_is_used_everywhere():
    """core satellite: the Gram product and U back-projection must route
    through the injected matmul."""
    calls = []

    def counting_mm(a, b):
        calls.append((a.shape, b.shape))
        return jnp.matmul(a, b)

    a = jnp.asarray(np.random.default_rng(0).standard_normal((10, 4)),
                    jnp.float32)
    jacobi_svd(a, matmul_fn=counting_mm, sweeps=4, rotation="rowcol")
    shapes = set(calls)
    assert ((4, 10), (10, 4)) in shapes     # Gram A^T A
    assert ((10, 4), (4, 4)) in shapes      # U = A V


# ---------------------------------------------------------------------------
# engine behavior
# ---------------------------------------------------------------------------

def _server(clock=None, **kw):
    kw.setdefault("config", PCAConfig(T=8, S=4, sweeps=12))
    kw.setdefault("policy", BucketPolicy(T=8))
    if clock is not None:
        kw["clock"] = clock
    return PCAServer(**kw)


def test_engine_flush_on_full():
    srv = _server(max_delay_s=1e9)   # deadline can never fire
    tickets = [srv.submit(_sym(6, seed=i)) for i in range(4)]
    assert all(t.done for t in tickets)          # S-full flush, no poll needed
    assert srv.pending() == 0
    for i, t in enumerate(tickets):
        ref = np.linalg.eigh(_sym(6, seed=i))[0][::-1]
        np.testing.assert_allclose(t.result().eigenvalues, ref,
                                   rtol=1e-3, atol=1e-3)


def test_engine_flush_on_timeout():
    t = [0.0]
    srv = _server(clock=lambda: t[0], max_delay_s=0.5)
    ticket = srv.submit(_sym(6))
    assert not ticket.done
    assert srv.poll() == 0                       # deadline not reached
    t[0] = 0.49
    assert srv.poll() == 0
    t[0] = 0.51
    assert srv.poll() == 1 and ticket.done       # deadline flush
    rec = ticket.record
    assert rec.batch_size == 1 and rec.queue_s == pytest.approx(0.51)


def test_engine_executable_cache_hits_on_repeated_shapes():
    srv = _server(max_delay_s=1e9)
    [srv.submit(_sym(6, seed=i)) for i in range(4)]
    assert srv.stats.cache_misses == 1 and srv.stats.cache_hits == 0
    [srv.submit(_sym(6, seed=10 + i)) for i in range(4)]
    assert srv.stats.cache_hits == 1             # same (op, bucket, batch)
    # a timeout-style partial flush runs at rung 1, whose executable the
    # bucket's first flush built beside rungs 2 and 4 -> still a hit
    srv.submit(_sym(7, seed=20))
    srv.drain()
    assert srv.stats.cache_hits == 2
    assert srv.stats.flush_records[-1].padded_batch == 1
    assert sorted(k[2] for k in srv._cache) == [1, 2, 4]


def test_engine_mixed_buckets_separate_queues():
    srv = _server(max_delay_s=1e9)
    small = srv.submit(_sym(6))                  # bucket (8, 8)
    big = srv.submit(_sym(12))                   # bucket (16, 16)
    assert not small.done and not big.done and srv.pending() == 2
    srv.drain()
    assert small.done and big.done
    assert small.record.bucket == (8, 8) and big.record.bucket == (16, 16)


def test_engine_stats_summary():
    srv = _server(max_delay_s=1e9)
    srv.solve_many([_sym(6, seed=i) for i in range(8)])
    s = srv.stats.summary()
    assert s["requests"] == 8 and s["flushes"] == 2
    assert s["mean_batch"] == 4.0
    assert 0.0 <= s["mean_padding_waste"] < 1.0
    assert s["latency_p99_ms"] >= s["latency_p50_ms"] > 0.0
    pvm = srv.stats.predicted_vs_measured()
    assert len(pvm) == 8 and all(r["predicted_s"] > 0 for r in pvm)


# ---------------------------------------------------------------------------
# dispatch / in-flight / retire pipeline
# ---------------------------------------------------------------------------

def _assert_served_equal(got, want, op):
    fields = [f.name for f in dataclasses.fields(got)]
    assert fields, op
    for field in fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)), np.asarray(getattr(want, field)),
            err_msg=f"{op}.{field}")


@pytest.mark.parametrize("op", ["eigh", "svd", "pca"])
def test_async_matches_sync_per_op(op):
    """Result parity: the pipeline runs the identical cached executable on
    identical slabs, so a deep pipeline must match the synchronous engine
    bit-for-bit on every served field."""
    rng = np.random.default_rng(11)
    if op == "eigh":
        mats = [_sym(n, seed=n) for n in (5, 7, 6, 8, 4, 6, 7, 5)]
    else:
        mats = [rng.standard_normal((24, d)).astype(np.float32)
                for d in (5, 7, 6, 4, 5, 7, 6, 4)]
    got = _server(max_delay_s=1e9, max_inflight=3).solve_many(mats, op=op)
    want = _server(max_delay_s=1e9).solve_many(mats, op=op)
    for g, w in zip(got, want):
        _assert_served_equal(g, w, op)


def test_async_inflight_cap_backpressures_dispatch():
    """Dispatching past max_inflight must retire the oldest flush first:
    older microbatches complete without any poll/drain, and the pipeline
    depth never exceeds the cap."""
    srv = _server(max_delay_s=1e9, max_inflight=2,
                  config=PCAConfig(T=8, S=2, sweeps=12), max_batch=2)
    t1 = [srv.submit(_sym(6, seed=i)) for i in range(2)]      # flush 1
    assert srv.inflight() == 1 and srv.pending() == 0
    assert not any(t.done for t in t1)
    t2 = [srv.submit(_sym(6, seed=10 + i)) for i in range(2)]  # flush 2
    # cap 2: dispatching flush 2 forced flush 1 home (no poll/drain called)
    assert all(t.done for t in t1)
    t3 = [srv.submit(_sym(6, seed=20 + i)) for i in range(2)]  # flush 3
    assert all(t.done for t in t2)
    assert srv.inflight() == 1
    assert [d for _, d in srv.stats.inflight_depths] == [1, 2, 2]
    srv.drain()
    assert all(t.done for t in t1 + t2 + t3) and srv.inflight() == 0
    for i, t in enumerate(t1):
        ref = np.linalg.eigh(_sym(6, seed=i))[0][::-1]
        np.testing.assert_allclose(t.result().eigenvalues, ref,
                                   rtol=1e-3, atol=1e-3)


def test_async_out_of_order_retirement():
    """A younger flush may retire before an older one: each flush fulfils
    only its own tickets, so completion order never corrupts results."""
    srv = _server(max_delay_s=1e9, max_inflight=4)
    small = [srv.submit(_sym(6, seed=i)) for i in range(4)]    # flush 1
    big = [srv.submit(_sym(12, seed=i)) for i in range(4)]     # flush 2
    assert srv.inflight() == 2
    big[0].result()                  # retire flush 2 while flush 1 flies
    assert all(t.done for t in big)
    assert not any(t.done for t in small) and srv.inflight() == 1
    assert srv.drain() == 4          # retires exactly flush 1
    assert all(t.done for t in small)
    for i, t in enumerate(small):
        ref = np.linalg.eigh(_sym(6, seed=i))[0][::-1]
        np.testing.assert_allclose(t.result().eigenvalues, ref,
                                   rtol=1e-3, atol=1e-3)
    for i, t in enumerate(big):
        ref = np.linalg.eigh(_sym(12, seed=i))[0][::-1]
        np.testing.assert_allclose(t.result().eigenvalues, ref,
                                   rtol=1e-3, atol=1e-3)


def test_max_inflight_one_is_synchronous_under_injected_clock():
    """The pipeline at depth 1 degrades exactly to the old synchronous
    flush: full batches retire inside submit, deadline flushes retire
    inside poll, and nothing is ever left in flight."""
    t = [0.0]
    srv = _server(clock=lambda: t[0], max_delay_s=0.5)
    assert srv.max_inflight == 1
    tickets = [srv.submit(_sym(6, seed=i)) for i in range(4)]
    assert all(tk.done for tk in tickets)        # S-full flush, synchronous
    assert srv.inflight() == 0
    late = srv.submit(_sym(6, seed=9))
    assert not late.done
    t[0] = 0.51
    assert srv.poll() == 1 and late.done and srv.inflight() == 0
    # under the frozen injected clock the pipeline accounting is exact:
    # dispatch == launch == wait == retire, so overlap is identically zero
    assert all(f.overlap_s == 0.0 and f.wait_s == 0.0
               for f in srv.stats.flush_records)
    assert srv.stats.summary()["max_inflight_depth"] == 1


def test_poll_dispatches_expired_queues_in_sorted_order():
    """Retirement order under poll is reproducible: expired queues are
    visited in sorted (op, bucket) order regardless of submission order."""
    t = [0.0]
    srv = _server(clock=lambda: t[0], max_delay_s=0.5)
    srv.submit(_sym(12))                         # ("eigh", (16, 16)) first
    srv.submit(_sym(6))                          # ("eigh", (8, 8)) second
    srv.submit(np.random.default_rng(0).standard_normal((8, 6))
               .astype(np.float32), op="svd")    # ("svd", (8, 8)) third
    t[0] = 1.0
    assert srv.poll() == 3
    flushed = [(r.op, r.bucket) for r in srv.stats.records]
    assert flushed == [("eigh", (8, 8)), ("eigh", (16, 16)),
                       ("svd", (8, 8))]


def test_ticket_result_error_names_op_bucket_and_depth():
    srv = _server(max_delay_s=1e9)
    srv.submit(_sym(6, seed=0))
    ticket = srv.submit(_sym(6, seed=1))
    with pytest.raises(RuntimeError, match=r"op='eigh'.*\(8, 8\).*2 "
                                           r"request\(s\)"):
        ticket.result()
    assert not ticket.done and srv.pending() == 2


def test_ticket_wait_flushes_its_own_queue():
    """wait() on a still-queued ticket dispatches its bucket's partial
    batch (like a deadline expiry) and blocks through retirement -- other
    buckets stay queued."""
    srv = _server(max_delay_s=1e9)
    other = srv.submit(_sym(12, seed=0))         # different bucket
    ticket = srv.submit(_sym(6, seed=3))
    res = ticket.wait()
    assert ticket.done and ticket.record.batch_size == 1
    assert not other.done and srv.pending() == 1
    ref = np.linalg.eigh(_sym(6, seed=3))[0][::-1]
    np.testing.assert_allclose(res.eigenvalues, ref, rtol=1e-3, atol=1e-3)
    assert ticket.wait() is res                  # idempotent once done
    assert ticket.wait(timeout=0.0) is res


def test_ticket_wait_timeout_leaves_flush_in_flight():
    srv = _server(max_delay_s=1e9, max_inflight=2,
                  config=PCAConfig(T=8, S=1, sweeps=80), max_batch=1)
    ticket = srv.submit(_sym(24, seed=0))        # slow enough to catch flying
    assert not ticket.done and srv.inflight() == 1
    try:
        ticket.wait(timeout=0.0)
        assert ticket.done                       # device won the race: fine
    except TimeoutError:
        assert not ticket.done and srv.inflight() == 1
    res = ticket.wait()                          # no timeout: blocks home
    assert ticket.done and srv.inflight() == 0
    assert res.eigenvalues.shape == (24,)
