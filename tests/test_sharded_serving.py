"""Sharded serving: MeshExecutor parity with the single-device flush path,
executor-qualified cache keys, partial-flush padding to the data-axis
multiple, and refusal when fewer devices are visible than requested.

Multi-device cases follow the ``test_distributed.py`` recipe -- a
subprocess forcing ``--xla_force_host_platform_device_count=8`` -- so they
exercise a real 8-way mesh no matter how the main pytest process was
launched.  In-process cases that genuinely need >= 2 devices carry a
``skipif`` guard and only light up under the mesh-8 CI matrix job (or any
launch with multiple visible devices); everything else runs anywhere,
down to a single device.
"""
import dataclasses

import numpy as np
import pytest
import jax

from _mesh import run_in_mesh_subprocess as _run
from repro.core import PCAConfig
from repro.serving import (BucketPolicy, InFlightFlush, LocalExecutor,
                           MeshExecutor, PCAServer, host_mesh, mesh_executor)


def _sym(n, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    return (a + a.T) / 2


# ---------------------------------------------------------------------------
# executor seam (single-device safe)
# ---------------------------------------------------------------------------

def test_default_executor_is_local():
    srv = PCAServer()
    assert isinstance(srv.executor, LocalExecutor)
    assert not isinstance(srv.executor, MeshExecutor)
    assert srv.executor.n_shards == 1
    assert srv.executor.round_batch(3) == 3
    assert srv.executor.cache_token() is None


def test_mesh_executor_single_device_parity_all_ops():
    """A 1-device mesh is the degenerate shard: results must equal the
    LocalExecutor path for all three ops (placement-invariance base case)."""
    rng = np.random.default_rng(2)
    cfg = PCAConfig(T=8, S=4, sweeps=14)
    mesh_srv = PCAServer(cfg, policy=BucketPolicy(T=8), max_delay_s=1e9,
                         executor=MeshExecutor(mesh=host_mesh(1)))
    local_srv = PCAServer(cfg, policy=BucketPolicy(T=8), max_delay_s=1e9)
    eigh_in = [_sym(n, seed=n) for n in (5, 7, 6, 8)]
    rect_in = [rng.standard_normal((24, d)).astype(np.float32)
               for d in (5, 7, 6, 4)]
    for op, mats in (("eigh", eigh_in), ("svd", rect_in), ("pca", rect_in)):
        got = mesh_srv.solve_many(mats, op=op)
        want = local_srv.solve_many(mats, op=op)
        for g, w in zip(got, want):
            fields = [f.name for f in dataclasses.fields(g)]
            assert fields, op
            for field in fields:
                np.testing.assert_allclose(
                    np.asarray(getattr(g, field)),
                    np.asarray(getattr(w, field)), rtol=1e-5, atol=1e-6,
                    err_msg=f"{op}.{field}")
    assert {r.n_shards for r in mesh_srv.stats.records} == {1}


@pytest.mark.parametrize("make_executor", [
    LocalExecutor, lambda: MeshExecutor(mesh=host_mesh(1))])
def test_executor_submit_is_nonblocking_run_is_submit_result(make_executor):
    """The dispatch-stage seam: ``submit`` hands back an InFlightFlush whose
    ``ready``/``block_until_ready``/``result`` drive the pipeline, and
    ``run`` is exactly the blocking composition of the two."""
    ex = make_executor()
    cfg = PCAConfig(T=8, S=2, sweeps=14)
    fn = ex.compile("eigh", cfg, (8, 8), 2)
    rng = np.random.default_rng(0)
    a = rng.standard_normal((2, 8, 8)).astype(np.float32)
    batch = (a + np.swapaxes(a, 1, 2)) / 2
    n_active = np.full((2, 2), 8, np.int32)
    flush = ex.submit(fn, batch, n_active)
    assert isinstance(flush, InFlightFlush)
    assert flush.n_shards == ex.n_shards
    assert flush.block_until_ready() is flush and flush.ready()
    out = flush.result()
    assert isinstance(out.eigenvalues, np.ndarray)       # host, not device
    assert out.eigenvalues.shape == (2, 8)
    want = ex.run(fn, batch, n_active)
    np.testing.assert_array_equal(out.eigenvalues, want.eigenvalues)
    np.testing.assert_array_equal(out.eigenvectors, want.eigenvectors)
    # an executor-level flush has no engine attached: retire() must refuse
    with pytest.raises(RuntimeError, match="not attached"):
        ex.submit(fn, batch, n_active).retire()


def test_mesh_executor_rejects_foreign_axis():
    with pytest.raises(ValueError, match="data_axis"):
        MeshExecutor(mesh=host_mesh(1), data_axis="model")


def test_mesh_executor_rounds_and_validates_batch():
    ex = MeshExecutor(mesh=host_mesh(1))
    assert ex.round_batch(0) == 1 and ex.round_batch(3) == 3
    n = jax.device_count()
    ex_all = mesh_executor("auto")
    for b in range(1, 2 * max(n, 1) + 1):
        assert ex_all.round_batch(b) % ex_all.n_shards == 0
        assert ex_all.round_batch(b) >= b
    if ex_all.n_shards > 1:
        with pytest.raises(ValueError, match="multiple"):
            ex_all.compile("eigh", PCAConfig(T=8, S=4), (8, 8),
                           ex_all.n_shards + 1)


def test_mesh_executor_spec_degrades_to_visible_devices():
    """Asking for more devices than are visible no longer degrades: it
    raises, naming both counts, instead of quietly running on fewer.
    "auto" is the spelling that takes whatever is visible."""
    n = jax.device_count()
    with pytest.raises(ValueError, match=f"{n * 4}-device mesh.*only {n}"):
        mesh_executor(str(n * 4))
    with pytest.raises(ValueError, match="requested"):
        host_mesh(n + 1)
    assert mesh_executor("auto").n_shards == n
    assert mesh_executor("none").n_shards == 1
    assert mesh_executor("1").n_shards == 1
    assert not isinstance(mesh_executor("1"), MeshExecutor)


def test_executor_cache_token_distinguishes_mesh_shapes():
    tokens = {LocalExecutor().cache_token(),
              MeshExecutor(mesh=host_mesh(1)).cache_token()}
    assert len(tokens) == 2
    if jax.device_count() >= 2:
        tokens.add(MeshExecutor(mesh=host_mesh(2)).cache_token())
        assert len(tokens) == 3


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >= 2 visible devices (mesh-8 CI job runs "
                           "this in-process; single-device hosts rely on "
                           "the subprocess parity tests)")
def test_multi_device_flush_in_process():
    """Under a multi-device launch (e.g. the mesh-8 matrix job) the main
    process itself can shard a flush; records must carry the shard count."""
    ex = mesh_executor("auto")
    assert ex.n_shards == jax.device_count() > 1
    srv = PCAServer(PCAConfig(T=8, S=4, sweeps=14), policy=BucketPolicy(T=8),
                    max_batch=2 * ex.n_shards, max_delay_s=1e9, executor=ex)
    mats = [_sym(6, seed=i) for i in range(2 * ex.n_shards)]
    for m, r in zip(mats, srv.solve_many(mats)):
        ref = np.linalg.eigh(m)[0][::-1]
        np.testing.assert_allclose(r.eigenvalues, ref, rtol=1e-3, atol=1e-3)
    assert {r.n_shards for r in srv.stats.records} == {ex.n_shards}
    assert srv.stats.summary()["max_shards"] == ex.n_shards


# ---------------------------------------------------------------------------
# real 8-way mesh (subprocess, forced host devices)
# ---------------------------------------------------------------------------

def test_sharded_flush_matches_single_device_all_ops():
    """Sharded parity -- and, since the sharded server runs a deep
    pipeline (max_inflight=3), async-over-mesh parity: in-flight sharded
    flushes must retire to the synchronous local results.  The sharded
    and single-device executables are separate compiles, so they agree to
    rounding, not bit for bit; eigenvector signs are canonical, so no
    column may come back flipped."""
    out = _run("""
        from repro.core import PCAConfig
        from repro.serving import (BucketPolicy, MeshExecutor, PCAServer,
                                   host_mesh)
        rng = np.random.default_rng(0)
        cfg = PCAConfig(T=8, S=8, sweeps=14)
        sharded = PCAServer(cfg, policy=BucketPolicy(T=8), max_batch=8,
                            max_delay_s=1e9, max_inflight=3,
                            executor=MeshExecutor(mesh=host_mesh(8)))
        local = PCAServer(cfg, policy=BucketPolicy(T=8), max_batch=8,
                          max_delay_s=1e9)
        sym = [0.5 * (a + a.T) for a in
               [rng.standard_normal((6, 6)).astype(np.float32)
                for _ in range(8)]]
        rect = [rng.standard_normal((16, d)).astype(np.float32)
                for d in (5, 7, 6, 4, 5, 7, 6, 4)]
        import dataclasses
        errs = {}
        for op, mats in (("eigh", sym), ("svd", rect), ("pca", rect)):
            got = sharded.solve_many(mats, op=op)
            want = local.solve_many(mats, op=op)
            err = 0.0
            for g, w in zip(got, want):
                fields = [f.name for f in dataclasses.fields(g)]
                assert fields, op
                for f in fields:
                    a = np.asarray(getattr(g, f), np.float64)
                    b = np.asarray(getattr(w, f), np.float64)
                    # relative to the field's scale (unit floor): the two
                    # compiles round differently, so eigenvalues near 35
                    # differ in their last bits
                    scale = max(1.0, float(np.max(np.abs(b))))
                    err = max(err, float(np.max(np.abs(a - b))) / scale)
            errs[op] = err
        errs["n_shards"] = sorted({r.n_shards
                                   for r in sharded.stats.records})
        errs["inflight_left"] = sharded.inflight()
        print(json.dumps(errs))
    """)
    assert out["n_shards"] == [8]
    assert out["inflight_left"] == 0
    for op in ("eigh", "svd", "pca"):
        assert out[op] < 1e-5, (op, out)


def test_cache_isolation_across_mesh_shapes_and_partial_flush():
    out = _run("""
        from repro.core import PCAConfig
        from repro.serving import (BucketPolicy, MeshExecutor, PCAServer,
                                   host_mesh)
        rng = np.random.default_rng(1)
        sym = [0.5 * (a + a.T) for a in
               [rng.standard_normal((6, 6)).astype(np.float32)
                for _ in range(8)]]
        ref = [np.linalg.eigh(m)[0][::-1] for m in sym]
        srv = PCAServer(PCAConfig(T=8, S=8, sweeps=14),
                        policy=BucketPolicy(T=8), max_batch=8,
                        max_delay_s=1e9)
        ok = []
        # same server, three executors: local, 2-wide, 4-wide.  Each mesh
        # shape must compile its own executable (no placement reuse) and
        # still produce the right answers.
        for ex in (None, MeshExecutor(mesh=host_mesh(2)),
                   MeshExecutor(mesh=host_mesh(4))):
            if ex is not None:
                srv.executor = ex
            res = srv.solve_many(sym)
            ok.append(all(
                np.allclose(r.eigenvalues, e, rtol=1e-3, atol=1e-3)
                for r, e in zip(res, ref)))
        n_execs = len(srv._cache)

        # partial flush on an 8-wide mesh with pad_batches=False: 3 live
        # requests must pad up to the data-axis multiple (8), not crash
        # with a ragged shard
        srv8 = PCAServer(PCAConfig(T=8, S=8, sweeps=14),
                         policy=BucketPolicy(T=8), pad_batches=False,
                         max_delay_s=1e9,
                         executor=MeshExecutor(mesh=host_mesh(8)))
        tickets = [srv8.submit(m) for m in sym[:3]]
        srv8.drain()
        ok_partial = all(
            np.allclose(t.result().eigenvalues, e, rtol=1e-3, atol=1e-3)
            for t, e in zip(tickets, ref))
        compiled_batches = sorted(k[2] for k in srv8._cache)
        batch_sizes = sorted({r.batch_size
                              for r in srv8.stats.records})
        print(json.dumps({
            "ok": ok, "n_execs": n_execs, "ok_partial": ok_partial,
            "compiled_batches": compiled_batches,
            "batch_sizes": batch_sizes}))
    """)
    assert out["ok"] == [True, True, True]
    # one executable per rung per mesh shape: max_batch 8's rungs are
    # 1, 2, 4, 8 locally, 2, 4, 8 on the 2-wide mesh, 4, 8 on the 4-wide
    assert out["n_execs"] == 4 + 3 + 2
    assert out["ok_partial"]
    assert out["compiled_batches"] == [8]   # 3 requests padded up to 8
    assert out["batch_sizes"] == [3]        # telemetry reports live batch


def test_mesh_rungs_round_to_the_data_axis_and_serve_without_compiling():
    """On a 4-wide data axis every rung rounds up to a multiple of 4 and
    none repeats: max_batch 8 has rungs 4 and 8.  A bucket's first flush
    builds both, and flushes of 1 to 8 live requests then compile nothing
    (the slab goes straight onto the data axis, so the ahead-of-time
    executables never reshard) and answer correctly."""
    out = _run("""
        import jax.monitoring as mon
        from repro.core import PCAConfig
        from repro.serving import (BucketPolicy, MeshExecutor, PCAServer,
                                   host_mesh)
        from repro.serving.batching import batch_rungs
        ex = MeshExecutor(mesh=host_mesh(4))
        rungs = {mb: list(batch_rungs(mb, ex.round_batch))
                 for mb in (1, 2, 4, 6, 8)}
        compiles = []
        mon.register_event_duration_secs_listener(
            lambda e, d, **kw: compiles.append(e)
            if e == "/jax/core/compile/backend_compile_duration" else None)
        srv = PCAServer(PCAConfig(T=8, S=8, sweeps=14),
                        policy=BucketPolicy(T=8), max_batch=8,
                        max_delay_s=1e9, executor=ex)
        rng = np.random.default_rng(3)
        def sym():
            a = rng.standard_normal((6, 6)).astype(np.float32)
            return (a + a.T) / 2
        srv.submit(sym()).wait()
        cached = sorted(k[2] for k in srv._cache)
        srv.stats.reset()
        n_warm = len(compiles)
        ok = True
        for b in (1, 3, 5, 8, 2):
            mats = [sym() for _ in range(b)]
            tickets = [srv.submit(m) for m in mats]
            srv.drain()
            ok &= all(np.allclose(t.result().eigenvalues,
                                  np.linalg.eigh(m)[0][::-1],
                                  rtol=1e-3, atol=1e-3)
                      for t, m in zip(tickets, mats))
        print(json.dumps({
            "rungs": rungs, "cached": cached, "ok": bool(ok),
            "padded": [f.padded_batch for f in srv.stats.flush_records],
            "misses": srv.stats.cache_misses,
            "compiles": len(compiles) - n_warm}))
    """, device_count=4)
    assert out["rungs"] == {"1": [4], "2": [4], "4": [4], "6": [4, 8],
                            "8": [4, 8]}
    assert out["cached"] == [4, 8]
    assert out["padded"] == [4, 4, 8, 8, 4]
    assert out["ok"]
    assert out["misses"] == 0 and out["compiles"] == 0
