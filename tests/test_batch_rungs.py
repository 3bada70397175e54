"""A flush runs at its rung: the smallest of the powers of two below
``max_batch``, and ``max_batch`` itself, that holds its live requests
(``batching.batch_rungs``).  A bucket's first flush builds the executable
of every rung, so no later flush of it compiles; a request answers the
same at any rung; the autotuner's cost model charges a partial flush at
its rung; with obs attached ``serve_flush_rungs_total`` counts them."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import PCAConfig
from repro.obs import Observability
from repro.parallel.sharding import pad_to_multiple
from repro.serving import BucketPolicy, PCAServer, TrafficProfile
from repro.serving.autotune import CostModel, ServingPlan, solve_work
from repro.serving.batching import batch_rungs

COMPILE = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def compiles():
    """XLA compiles while the test runs, counted by a ``jax.monitoring``
    listener as the benchmark harness counts them."""
    seen = []

    def listen(event, duration, **kw):
        if event == COMPILE:
            seen.append(duration)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _server(**kw):
    return PCAServer(PCAConfig(T=8, S=4, sweeps=4), policy=BucketPolicy(T=8),
                     max_delay_s=10.0, **kw)


def _mats(rng, op, shapes):
    mats = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    if op == "eigh":
        mats = [(m + m.T) / 2 for m in mats]
    return mats


@pytest.mark.parametrize("max_batch, shards, rungs", [
    (1, 1, (1,)), (2, 1, (1, 2)), (3, 1, (1, 2, 3)), (4, 1, (1, 2, 4)),
    (5, 1, (1, 2, 4, 5)), (8, 1, (1, 2, 4, 8)),
    (4, 4, (4,)), (6, 4, (4, 8)), (8, 4, (4, 8)), (4, 8, (8,)),
])
def test_batch_rungs(max_batch, shards, rungs):
    """Powers of two below max_batch plus max_batch, each rounded up to
    the data-axis multiple, ascending and without repeats."""
    assert batch_rungs(max_batch,
                       lambda b: pad_to_multiple(b, shards)) == rungs


@pytest.mark.parametrize("op", ["eigh", "svd", "pca"])
def test_warmed_bucket_serves_every_flush_size_without_compiling(
        op, compiles):
    """One request warms the bucket: its flush (rung 1) builds rungs 2 and
    4 too, so flushes of 1 to 4 live requests then run with zero server
    cache misses and zero XLA compiles."""
    rng = np.random.default_rng(5)
    srv = _server()
    shape = (6, 6) if op == "eigh" else (20, 6)
    srv.submit(_mats(rng, op, [shape])[0], op=op).wait()
    assert sorted(k[2] for k in srv._cache) == [1, 2, 4]
    srv.stats.reset()
    del compiles[:]
    for b in (1, 2, 3, 4, 3, 2, 1):
        tickets = [srv.submit(m, op=op) for m in _mats(rng, op, [shape] * b)]
        srv.drain()
        assert all(t.done for t in tickets)
    assert len(compiles) == 0
    assert srv.stats.cache_misses == 0
    assert [f.padded_batch for f in srv.stats.flush_records] == [
        1, 2, 4, 4, 4, 2, 1]


def test_pad_batches_off_runs_each_flush_at_its_own_size():
    srv = _server(pad_batches=False)
    rng = np.random.default_rng(2)
    for b in (3, 1):
        for m in _mats(rng, "pca", [(20, 6)] * b):
            srv.submit(m, op="pca")
        srv.drain()
    assert [f.padded_batch for f in srv.stats.flush_records] == [3, 1]


@pytest.mark.parametrize("op", ["pca", "svd"])
def test_request_alone_at_rung_1_answers_as_in_a_full_flush(op):
    """The batch axis is vmapped and the svd refinement stops per slot, so
    a request's answer does not depend on its rung or its neighbours.
    Not bit for bit: the CPU compiles each batch size on its own and may
    order a sum differently (eigenvalues 2 ulp apart here), so each field
    agrees within 1e-6 of its scale (unit floor), as two compiles do in
    the sharded-parity tests."""
    rng = np.random.default_rng(9)
    mats = _mats(rng, op, [(20, 6), (23, 8), (17, 5), (24, 7)])
    alone_srv, full_srv = _server(), _server()
    alone = alone_srv.solve_many(mats[:1], op=op)[0]
    full = full_srv.solve_many(mats, op=op)[0]
    assert [f.padded_batch for f in alone_srv.stats.flush_records] == [1]
    assert [f.padded_batch for f in full_srv.stats.flush_records] == [4]
    for f in dataclasses.fields(alone):
        a = np.asarray(getattr(alone, f.name), np.float64)
        b = np.asarray(getattr(full, f.name), np.float64)
        scale = max(1.0, float(np.max(np.abs(b))))
        assert float(np.max(np.abs(a - b))) / scale < 1e-6, f.name


def test_warmup_and_apply_plan_build_every_rung():
    profile = TrafficProfile.from_shapes([("pca", (20, 6), 3)])
    srv = _server()
    doc = srv.warmup(profile)
    assert doc["compile"] == doc["executables"] == 3
    assert [k[2] for k in srv.warmup_keys(profile)] == [1, 2, 4]
    plan = ServingPlan(mode="tile", T=8, max_batch=8, max_inflight=1,
                       mesh="none")
    switch = srv.apply_plan(plan, warm_profile=profile)
    # rungs 1, 2 and 4 of the bucket are already built; rung 8 is new
    assert switch["prewarmed"] == {"memory": 3, "disk": 0, "compile": 1}


def test_flush_rung_counter():
    obs = Observability.enabled()
    srv = _server(obs=obs, clock=obs.clock)
    rng = np.random.default_rng(4)
    for b in (1, 3, 4, 2, 1):
        for m in _mats(rng, "pca", [(20, 6)] * b):
            srv.submit(m, op="pca")
        srv.drain()
    fam = obs.metrics.counter("serve_flush_rungs_total", labels=("rung",))
    assert [fam.labels(r).total for r in (1, 2, 4)] == [2, 1, 2]
    assert "serve_flush_rungs_total" in obs.prometheus_text()


def test_cost_model_charges_a_partial_flush_at_its_rung():
    """Nine requests at max_batch 4: two full flushes at rung 4 and one
    of a single request at rung 1, not at 4."""
    model = CostModel(host_s_per_flush=0.0, host_s_per_request=0.0)
    bucket = (16, 16)
    one = solve_work("eigh", bucket) / model.device_work_per_s
    cost = model.plan_cost(ServingPlan(T=16, max_batch=4),
                           TrafficProfile.from_shapes(
                               [("eigh", bucket, 9)]))
    assert cost["device_s"] == pytest.approx((2 * 4 + 1) * one)
    # on a data axis of s shards (s = 2 where two devices are visible)
    # the lone request pads to the rung of s slots, one per shard
    plan = ServingPlan(T=16, max_batch=4, mesh="2")
    s = plan.n_shards()
    sharded = model.plan_cost(plan, TrafficProfile.from_shapes(
        [("eigh", bucket, 9)]))
    assert sharded["device_s"] == pytest.approx(
        (2 * pad_to_multiple(4, s) + pad_to_multiple(1, s)) / s * one)
