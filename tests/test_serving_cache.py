"""Two-tier executable cache: SolverKey de-fragmentation, the bounded
in-memory LRU, the persistent AOT tier's failure modes (corruption,
environment drift, concurrent warmers), warmup, and the rank-deficiency
fix in the SVD back-projection that rides along."""
import dataclasses
import os
import pathlib
import pickle
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import PCAConfig
from repro.serving import (BucketPolicy, LRUCache, PCAServer, ServingPlan,
                           SolverKey, TrafficProfile, jacobi_svd_batched)
import repro.serving.cache as cache_mod
import repro.serving.sharded as sharded_mod
from repro.obs import Observability

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _sym(n, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    return (a + a.T) / 2


def _server(tmpdir=None, sweeps=4, **kw):
    kw.setdefault("policy", BucketPolicy(T=8))
    kw.setdefault("max_delay_s", 10.0)
    return PCAServer(PCAConfig(T=8, S=2, sweeps=sweeps),
                     cache_dir=(str(tmpdir) if tmpdir is not None else None),
                     **kw)


def _assert_results_equal(a, b):
    for ra, rb in zip(a, b):
        for field in ra.__dataclass_fields__:
            np.testing.assert_array_equal(getattr(ra, field),
                                          getattr(rb, field))


# ---------------------------------------------------------------------------
# keying + memory tier
# ---------------------------------------------------------------------------

def test_lru_cache_evicts_coldest_first():
    evicted = []
    lru = LRUCache(max_entries=2, on_evict=lambda k, v: evicted.append(k))
    lru["a"], lru["b"] = 1, 2
    assert lru["a"] == 1            # refresh "a": "b" is now coldest
    lru["c"] = 3
    assert set(lru) == {"a", "c"}
    assert lru.evictions == 1 and evicted == ["b"]
    assert lru.get("b") is None
    # unbounded mode never evicts
    unbounded = LRUCache(max_entries=None)
    for i in range(600):
        unbounded[i] = i
    assert len(unbounded) == 600 and unbounded.evictions == 0


def test_solver_key_ignores_scheduling_facts():
    """The fragmentation bug: T/S are scheduling facts, not numerics --
    configs differing only there must share one executable key."""
    a = SolverKey.from_config(PCAConfig(T=8, S=2))
    b = SolverKey.from_config(PCAConfig(T=32, S=64))
    assert a == b and hash(a) == hash(b)
    assert a != SolverKey.from_config(PCAConfig(T=8, S=2, sweeps=3))
    # ...except the matmul block size once a kernel backend consumes it
    ka = SolverKey.from_config(PCAConfig(T=8, backend="interpret"))
    kb = SolverKey.from_config(PCAConfig(T=16, backend="interpret"))
    assert ka != kb
    assert ka.backend == "interpret"      # engine tests key on k[3].backend


def test_solver_key_carries_precision_and_fused():
    """The mixed-precision policy changes the compiled executable, so it
    must fragment the key -- unlike T/S.  The fused kernel path is gone:
    neither the key nor the config carries a ``fused`` field."""
    base = SolverKey.from_config(PCAConfig(T=8, S=2))
    assert base.precision == "fp32"
    assert base != SolverKey.from_config(
        PCAConfig(T=8, S=2, precision="bf16_fp32acc"))
    assert "fused" not in {f.name for f in dataclasses.fields(SolverKey)}
    with pytest.raises(TypeError):
        PCAConfig(fused=True)
    # content hash fragments with them too (the disk-tier file name)
    h = lambda k: cache_mod.content_hash("pca", (8, 8), 2, k, None)
    assert h(base) != h(SolverKey.from_config(
        PCAConfig(T=8, S=2, precision="bf16_fp32acc")))


def test_cache_format_bump_invalidates_disk_entries(monkeypatch):
    """CACHE_FORMAT is key material: entries hashed under format N are
    never looked up by a format N+1 server (clean miss, no load error)."""
    key = SolverKey.from_config(PCAConfig(T=8, S=2))
    new = cache_mod.content_hash("eigh", (8, 8), 2, key, None)
    monkeypatch.setattr(cache_mod, "CACHE_FORMAT",
                        cache_mod.CACHE_FORMAT - 1)
    old = cache_mod.content_hash("eigh", (8, 8), 2, key, None)
    assert new != old


def test_local_executor_builds_each_solver_once(monkeypatch):
    """Regression for the rebuild-per-key bug: two batch sizes of one
    bucket used to re-build and re-trace an identical solver closure."""
    builds = []
    real = sharded_mod.build_solver_fn

    def counting(op, config):
        builds.append((op, SolverKey.from_config(config)))
        return real(op, config)

    monkeypatch.setattr(sharded_mod, "build_solver_fn", counting)
    srv = _server(pad_batches=False, sweeps=3)
    mats = [_sym(6, seed=i) for i in range(4)]
    srv.submit(mats[0]).wait()            # flush of batch 1
    for m in mats[1:]:                    # flush of batch 2 + batch 1
        srv.submit(m)
    srv.drain()
    assert {k[2] for k in srv._cache} >= {1, 2}   # distinct engine keys...
    fns = {id(srv._cache[k]) for k in srv._cache}
    assert len(fns) == 1                  # ...but one shared jit wrapper
    assert len(builds) == 1, builds       # built (and traced) exactly once


def test_engine_cache_bounded_with_gauge():
    obs = Observability.enabled()
    srv = _server(sweeps=2, obs=obs, clock=obs.clock,
                  max_cached_executables=2)
    for n in (5, 9, 17):                  # three buckets, one executable each
        srv.solve_many([_sym(n)], op="eigh")
    assert len(srv._cache) == 2
    assert srv._cache.evictions >= 1
    assert srv.cache_summary()["entries"] == 2
    text = obs.prometheus_text()
    assert "serve_executables_cached 2" in text
    # the evicted (coldest) bucket recompiles on return; the hot one hits
    srv.solve_many([_sym(17)], op="eigh")
    assert len(srv._cache) == 2


# ---------------------------------------------------------------------------
# warmup
# ---------------------------------------------------------------------------

def test_warmup_prebuilds_profile_executables():
    obs = Observability.enabled()
    srv = _server(sweeps=2, obs=obs, clock=obs.clock)
    profile = TrafficProfile.from_shapes(
        [("eigh", (6, 6), 3), ("eigh", (5, 5), 1), ("svd", (12, 6), 2)])
    # (6,6) and (5,5) share the (8,8) bucket -> two buckets, each with
    # an executable per rung of max_batch 2 (batches 1 and 2)
    assert len(srv.warmup_keys(profile)) == 4
    doc = srv.warmup(profile)
    assert doc["executables"] == 4 and doc["compile"] == 4
    again = srv.warmup(profile)
    assert again["memory"] == 4 and again["compile"] == 0
    # warm traffic is all cache hits from the first flush
    srv.solve_many([_sym(6), _sym(5)], op="eigh")
    assert srv.stats.summary()["cache_hit_rate"] == 1.0
    names = {e.get("name") for e in obs.trace_doc()["traceEvents"]}
    assert "warmup" in names
    assert "serve_warmup_executables_total" in obs.prometheus_text()


def test_warmup_keys_ordered_by_descending_traffic_weight():
    """SLO-aware warmup: the executables the profile says will be hit
    most compile first, so an interrupted warmup has already armed the
    highest-traffic paths.  Order is pinned: weight desc, then first
    appearance."""
    srv = _server(sweeps=2)
    profile = TrafficProfile.from_shapes([
        ("eigh", (6, 6), 2),       # bucket (8,8): 2 + 5 = 7 total
        ("svd", (12, 6), 1),       # lone low-traffic shape
        ("eigh", (5, 5), 5),       # folds onto the (8,8) eigh bucket
        ("pca", (12, 6), 4),
    ])
    keys = srv.warmup_keys(profile)
    # each bucket's rungs (batches 1 and 2) come together, ascending
    assert [(k[0], k[1], k[2]) for k in keys] == [
        ("eigh", (8, 8), 1), ("eigh", (8, 8), 2),    # weight 7
        ("pca", (16, 8), 1), ("pca", (16, 8), 2),    # weight 4
        ("svd", (16, 8), 1), ("svd", (16, 8), 2),    # weight 1
    ]
    # bare (op, shape) rows (no counts) keep working: weight 1 each,
    # insertion order preserved
    bare = srv.warmup_keys([("svd", (12, 6)), ("eigh", (6, 6))])
    assert [k[0] for k in bare] == ["svd", "svd", "eigh", "eigh"]


def test_apply_plan_prewarms_incoming_executables():
    srv = _server(sweeps=2, max_batch=2)
    srv.submit(_sym(6))                   # queued, below max_batch: no flush
    plan = ServingPlan(mode="tile", T=16, max_batch=2, max_inflight=1,
                       mesh="none")
    switch = srv.apply_plan(plan)
    assert switch["prewarmed"]["compile"] >= 1
    srv.drain()
    assert srv.stats.flush_records        # the queued request was served...
    assert all(f.cache_hit for f in srv.stats.flush_records)  # ...warm


# ---------------------------------------------------------------------------
# persistent tier
# ---------------------------------------------------------------------------

def test_disk_tier_round_trip_is_bitwise_identical(tmp_path):
    mats = [_sym(6), _sym(7, seed=1)]
    seeder = _server(tmp_path)
    expect = seeder.solve_many(mats, op="eigh")
    assert seeder.cache_summary()["disk"]["stores"] >= 1
    assert list(tmp_path.glob("*.jexec"))

    fresh = _server(tmp_path)
    got = fresh.solve_many(mats, op="eigh")
    disk = fresh.cache_summary()["disk"]
    assert disk["hits"] >= 1 and disk["errors"] == 0
    _assert_results_equal(expect, got)
    # and identical to a plain-JIT replica: the serialize round trip and
    # the AOT path must never touch the math
    _assert_results_equal(expect, _server().solve_many(mats, op="eigh"))


def test_corrupt_cache_entry_falls_back_and_repairs(tmp_path):
    mats = [_sym(6)]
    expect = _server(tmp_path).solve_many(mats, op="eigh")
    files = list(tmp_path.glob("*.jexec"))
    assert files
    for f in files:
        f.write_bytes(b"not a pickled executable")

    srv = _server(tmp_path)
    got = srv.solve_many(mats, op="eigh")
    _assert_results_equal(expect, got)
    disk = srv.cache_summary()["disk"]
    assert disk["errors"] >= 1            # quarantined the torn entry...
    assert disk["stores"] >= 1            # ...and repaired it in place

    repaired = _server(tmp_path)
    _assert_results_equal(expect, repaired.solve_many(mats, op="eigh"))
    disk = repaired.cache_summary()["disk"]
    assert disk["hits"] >= 1 and disk["errors"] == 0


def test_environment_drift_invalidates_cleanly(tmp_path, monkeypatch):
    """A different (jax version, device backend, device kind) fingerprint
    hashes to a different file name: the stale entry is simply never
    looked up."""
    mats = [_sym(6)]
    _server(tmp_path).solve_many(mats, op="eigh")
    before = set(tmp_path.glob("*.jexec"))

    monkeypatch.setattr(cache_mod, "environment_fingerprint",
                        lambda: ("jax-9.9.9", "quantum", "QPU v1"))
    srv = _server(tmp_path)
    srv.solve_many(mats, op="eigh")
    disk = srv.cache_summary()["disk"]
    assert disk["hits"] == 0 and disk["misses"] >= 1
    assert disk["errors"] == 0            # clean miss, not a load failure
    assert set(tmp_path.glob("*.jexec")) > before   # stored under new hash


def test_header_version_mismatch_is_quarantined(tmp_path):
    """Defense in depth: even if the hash collided across environments,
    the in-file header is checked and a drifted entry is rejected."""
    mats = [_sym(6)]
    expect = _server(tmp_path).solve_many(mats, op="eigh")
    path = next(iter(tmp_path.glob("*.jexec")))
    record = pickle.loads(path.read_bytes())
    record["env"] = ("0.0.1",) + tuple(record["env"][1:])
    path.write_bytes(pickle.dumps(record))

    srv = _server(tmp_path)
    got = srv.solve_many(mats, op="eigh")
    _assert_results_equal(expect, got)
    disk = srv.cache_summary()["disk"]
    assert disk["errors"] >= 1 and disk["stores"] >= 1


def test_fingerprint_keys_device_kind(tmp_path):
    """An executable serialized for one chip generation is never loaded on
    another: the device kind is in both the content hash and the header,
    and an entry whose header names another kind is quarantined."""
    import jax
    env = cache_mod.environment_fingerprint()
    assert env == (jax.__version__, jax.default_backend(),
                   jax.devices()[0].device_kind)
    mats = [_sym(6)]
    expect = _server(tmp_path).solve_many(mats, op="eigh")
    paths = sorted(tmp_path.glob("*.jexec"))
    assert len(paths) == 2             # the bucket's rungs, batches 1 and 2
    for path in paths:
        record = pickle.loads(path.read_bytes())
        assert tuple(record["env"]) == env
        record["env"] = env[:2] + ("TPU v4",)
        path.write_bytes(pickle.dumps(record))

    srv = _server(tmp_path)
    _assert_results_equal(expect, srv.solve_many(mats, op="eigh"))
    disk = srv.cache_summary()["disk"]
    assert disk["hits"] == 0 and disk["errors"] == 2


_WARMER = """\
import sys
from repro.core import PCAConfig
from repro.serving import BucketPolicy, PCAServer, TrafficProfile
srv = PCAServer(PCAConfig(T=8, S=2, sweeps=2), policy=BucketPolicy(T=8),
                max_delay_s=10.0, cache_dir=sys.argv[1])
doc = srv.warmup(TrafficProfile.from_shapes(
    [("eigh", (6, 6), 1), ("svd", (12, 6), 1)]))
assert doc["executables"] == 4, doc
print("warmed")
"""


def test_concurrent_warmers_share_one_cache_dir(tmp_path):
    """Two replicas warming the same --cache-dir concurrently must not
    torch each other's entries (atomic write-then-rename)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO_ROOT / "src")
    procs = [subprocess.Popen([sys.executable, "-c", _WARMER,
                               str(tmp_path)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True, env=env)
             for _ in range(2)]
    outs = [p.communicate(timeout=600) for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    assert all("warmed" in out for out, _ in outs)
    # every surviving entry is loadable: a third replica warms with zero
    # compiles and zero quarantines
    srv = PCAServer(PCAConfig(T=8, S=2, sweeps=2), policy=BucketPolicy(T=8),
                    max_delay_s=10.0, cache_dir=str(tmp_path))
    doc = srv.warmup(TrafficProfile.from_shapes(
        [("eigh", (6, 6), 1), ("svd", (12, 6), 1)]))
    assert doc["compile"] == 0 and doc["disk"] == doc["executables"] == 4
    assert srv.cache_summary()["disk"]["errors"] == 0


def test_disk_cache_size_cap_evicts_down_to_cap(tmp_path):
    fn = jax.jit(lambda x: x + 1.0).lower(
        jax.ShapeDtypeStruct((4,), jnp.float32)).compile()
    probe = cache_mod.DiskCache(tmp_path / "probe")
    assert probe.put("a" * 64, fn)
    entry_bytes = probe.total_bytes()

    disk = cache_mod.DiskCache(tmp_path / "capped",
                               max_bytes=int(entry_bytes * 1.5))
    assert disk.put("a" * 64, fn)
    assert disk.put("b" * 64, fn)         # over cap: one entry evicted
    assert len(disk.entries()) == 1
    assert disk.total_bytes() <= disk.max_bytes


# ---------------------------------------------------------------------------
# rank-deficiency fix in the SVD back-projection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", [None, "interpret"])
def test_rank_deficient_svd_zeroes_dead_columns(backend):
    """U = A V / s used to amplify Gram-path rounding noise into garbage
    columns wherever s ~ 0; those columns must now be exactly zero while
    the live ones still reconstruct A."""
    rng = np.random.default_rng(3)
    n, rank = 16, 2
    A = (rng.standard_normal((n, rank))
         @ rng.standard_normal((rank, n))).astype(np.float32)
    mm = PCAConfig(T=16, backend=backend).matmul_fn()
    res = jacobi_svd_batched(A[None], matmul_fn=mm, sweeps=14)
    U, S, Vt = (np.asarray(res.U[0]), np.asarray(res.S[0]),
                np.asarray(res.Vt[0]))
    ref = np.linalg.svd(A, compute_uv=False)
    np.testing.assert_allclose(S[:rank], ref[:rank], rtol=1e-3)
    assert np.all(U[:, rank:] == 0.0)     # dead columns: exactly zero
    assert np.all(np.isfinite(U))
    scale = float(ref[0])
    np.testing.assert_allclose(U @ np.diag(S) @ Vt, A,
                               atol=2e-3 * scale)
    # live columns are orthonormal (the noise never leaked into them)
    np.testing.assert_allclose(U[:, :rank].T @ U[:, :rank], np.eye(rank),
                               atol=1e-3)


def test_zero_matrix_svd_is_all_zero():
    res = jacobi_svd_batched(np.zeros((1, 8, 8), np.float32), sweeps=4)
    assert np.all(np.asarray(res.U) == 0.0)
    assert np.all(np.asarray(res.S) == 0.0)


def test_full_rank_svd_unchanged_by_rcond_mask():
    """The mask only ever turns noise into zeros: a well-conditioned
    input's factors are bit-identical with the mask disabled."""
    rng = np.random.default_rng(11)
    u, _, vt = np.linalg.svd(rng.standard_normal((8, 8)))
    A = (u @ np.diag(np.linspace(2.0, 1.0, 8)) @ vt).astype(
        np.float32)[None]                 # condition number 2: all live
    masked = jacobi_svd_batched(A, sweeps=10)
    unmasked = jacobi_svd_batched(A, sweeps=10, rcond=0.0)
    np.testing.assert_array_equal(np.asarray(masked.U),
                                  np.asarray(unmasked.U))
    np.testing.assert_array_equal(np.asarray(masked.S),
                                  np.asarray(unmasked.S))
