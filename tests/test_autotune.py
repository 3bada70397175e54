"""Serving-plan autotuner: deterministic traffic generators, traffic-profile
JSON round trip, cost-model behavior (padding-waste monotonicity, batching
and pipelining preferences, overlap-calibrated occupancy), the ``pow2_cap``
bucket-policy extension, ``apply_plan`` mid-stream hot-swap under the
injected clock, and analytic-vs-measured top-1 agreement on a simple
trace."""
import dataclasses
import json

import numpy as np
import pytest

from repro.core import PCAConfig
from repro.serving import (BucketPolicy, CostModel, PCAServer, ServingPlan,
                           ServingStats, TrafficProfile, TRACE_KINDS,
                           autotune, plan_grid, server_for_plan,
                           synthetic_trace, trace_dims)
from repro.serving.autotune import request_sequence, solve_work


def _sym(n, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    return (a + a.T) / 2


CFG = PCAConfig(T=8, S=4, sweeps=10)


# ---------------------------------------------------------------------------
# synthetic traffic generators
# ---------------------------------------------------------------------------

def test_trace_dims_deterministic_and_bounded():
    for kind in TRACE_KINDS:
        a = trace_dims(kind, 64, lo=6, hi=48, seed=3)
        b = trace_dims(kind, 64, lo=6, hi=48, seed=3)
        assert a == b, kind                       # same seed, same stream
        assert all(6 <= d <= 48 for d in a), kind
    assert trace_dims("uniform", 64, seed=3) != trace_dims("uniform", 64,
                                                           seed=4)
    with pytest.raises(ValueError, match="unknown trace kind"):
        trace_dims("spiky", 8)


def test_trace_kinds_have_distinct_shapes():
    uniform = trace_dims("uniform", 256, lo=6, hi=48, seed=0)
    bimodal = trace_dims("bimodal", 256, lo=6, hi=48, seed=0)
    heavy = trace_dims("heavy", 256, lo=6, hi=48, seed=0)
    # bimodal: two modes at the ends, a hole in the middle
    mid = [d for d in bimodal if 18 <= d <= 36]
    assert len(mid) < len(bimodal) * 0.2
    assert any(d <= 12 for d in bimodal) and any(d >= 40 for d in bimodal)
    # heavy: mass near lo with a long tail
    assert float(np.median(heavy)) <= 12
    assert max(heavy) >= 30
    # uniform: spread across the whole range
    assert float(np.std(uniform)) > float(np.std(heavy))


def test_synthetic_trace_matrices():
    eigh = synthetic_trace("uniform", 8, op="eigh", lo=6, hi=12, seed=0)
    assert all(m.shape[0] == m.shape[1] for m in eigh)
    assert all(np.allclose(m, m.T) for m in eigh)
    svd = synthetic_trace("uniform", 8, op="svd", lo=6, hi=12, seed=0)
    assert all(m.shape[0] == 4 * m.shape[1] for m in svd)
    again = synthetic_trace("uniform", 8, op="eigh", lo=6, hi=12, seed=0)
    for a, b in zip(eigh, again):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# pow2_cap bucket policy
# ---------------------------------------------------------------------------

def test_bucket_policy_pow2_cap():
    pol = BucketPolicy(T=16, mode="pow2", pow2_cap=64)
    assert pol.bucket_dim(16) == 16
    assert pol.bucket_dim(33) == 64            # geometric below the cap
    assert pol.bucket_dim(65) == 80            # linear beyond it (5 tiles)
    assert pol.bucket_dim(70) == 80
    # capped growth is still monotone across the crossover
    dims = [pol.bucket_dim(n) for n in range(1, 200)]
    assert dims == sorted(dims)
    assert all(d >= n for n, d in enumerate(dims, start=1))


def test_bucket_policy_pow2_cap_validation():
    with pytest.raises(ValueError, match="only applies to the pow2"):
        BucketPolicy(T=16, mode="tile", pow2_cap=64)
    with pytest.raises(ValueError, match="multiple of T"):
        BucketPolicy(T=16, mode="pow2", pow2_cap=40)
    with pytest.raises(ValueError, match="multiple of T"):
        BucketPolicy(T=16, mode="pow2", pow2_cap=8)


def test_plan_grid_skips_invalid_caps():
    grid = plan_grid(modes=("tile", "pow2"), tiles=(8, 16),
                     pow2_caps=(None, 32, 40), batches=(4,),
                     inflights=(1,))
    assert all(p.pow2_cap is None for p in grid if p.mode == "tile")
    caps16 = {p.pow2_cap for p in grid if p.mode == "pow2" and p.T == 16}
    assert caps16 == {None, 32}                # 40 % 16 != 0 -> skipped
    caps8 = {p.pow2_cap for p in grid if p.mode == "pow2" and p.T == 8}
    assert caps8 == {None, 32, 40}
    for p in grid:
        p.policy()                             # every grid point is valid


# ---------------------------------------------------------------------------
# profile capture + JSON round trip
# ---------------------------------------------------------------------------

def test_profile_round_trip_through_json(tmp_path):
    srv = PCAServer(CFG, policy=BucketPolicy(T=8), max_delay_s=10.0)
    mats = [_sym(n, seed=n) for n in (5, 9, 12, 7)]
    srv.solve_many(mats)
    srv.solve_many(mats)                       # second pass: cache hits
    profile = TrafficProfile.from_stats(srv.stats,
                                        captured=srv.describe_plan())
    assert profile.requests == 8
    assert profile.flushes >= 2
    assert profile.work_dispatched > 0         # flush op/bucket enrichment
    assert profile.mean_dispatch_miss_s > profile.mean_dispatch_hit_s > 0
    assert profile.captured_plan["T"] == 8
    assert TrafficProfile.from_json(profile.to_json()) == profile
    path = tmp_path / "profile.json"
    profile.save(path)
    assert TrafficProfile.load(path) == profile
    json.loads(profile.to_json())              # valid JSON, not just repr


def test_profile_of_idle_server_is_well_defined():
    stats = ServingStats()
    profile = TrafficProfile.from_stats(stats)
    assert profile.requests == 0 and profile.shape_counts == ()
    assert profile.arrival_rate == 0.0 and profile.overlap_frac == 0.0
    assert TrafficProfile.from_json(profile.to_json()) == profile
    # and the underlying summary is explicit zeros, never NaN
    summary = stats.summary()
    for key, val in summary.items():
        assert np.isfinite(val), (key, val)
    assert summary["latency_p50_ms"] == 0.0
    assert summary["latency_p99_ms"] == 0.0
    assert summary["queue_p50_ms"] == 0.0
    assert summary["requests_per_s"] == 0.0


def test_request_sequence_is_deterministic_shuffle():
    profile = TrafficProfile.from_shapes(
        [("eigh", (8, 8), 3), ("svd", (16, 4), 2)])
    seq = request_sequence(profile, seed=1)
    assert seq == request_sequence(profile, seed=1)
    assert len(seq) == 5
    assert sorted(seq) == [("eigh", (8, 8))] * 3 + [("svd", (16, 4))] * 2


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_cost_model_padding_waste_monotone():
    """More padding waste under the same plan -> strictly worse score."""
    plan = ServingPlan(mode="tile", T=16, max_batch=4)
    snug = TrafficProfile.from_shapes([("eigh", (16, 16), 32)])
    wasteful = TrafficProfile.from_shapes([("eigh", (17, 17), 32)])
    model = CostModel()
    c_snug = model.plan_cost(plan, snug)
    c_waste = model.plan_cost(plan, wasteful)
    assert c_waste["est_padding_waste"] > c_snug["est_padding_waste"]
    assert c_waste["total_s"] > c_snug["total_s"]


def test_cost_model_prefers_batching_on_homogeneous_traffic():
    profile = TrafficProfile.from_shapes([("eigh", (16, 16), 64)])
    model = CostModel()
    one = model.plan_cost(ServingPlan(T=16, max_batch=1), profile)
    eight = model.plan_cost(ServingPlan(T=16, max_batch=8), profile)
    assert eight["total_s"] < one["total_s"]


def test_cost_model_credits_pipelining():
    profile = TrafficProfile.from_shapes([("eigh", (16, 16), 64)])
    model = CostModel()
    sync = model.plan_cost(ServingPlan(T=16, max_batch=4,
                                       max_inflight=1), profile)
    deep = model.plan_cost(ServingPlan(T=16, max_batch=4,
                                       max_inflight=4), profile)
    assert sync["hidden_s"] == 0.0
    assert deep["hidden_s"] > 0.0
    assert deep["total_s"] < sync["total_s"]


def test_cost_model_occupancy_calibrates_from_measured_overlap():
    """A profile captured under a pipelined plan that only reached half its
    theoretical overlap scales the candidate's occupancy down too."""
    ideal = TrafficProfile.from_shapes(
        [("eigh", (16, 16), 16)],
        captured={"max_inflight": 4}, overlap_frac=0.75)
    poor = dataclasses.replace(ideal, overlap_frac=0.375)
    model = CostModel()
    plan = ServingPlan(T=16, max_batch=4, max_inflight=4)
    assert model.occupancy(plan, ideal) == pytest.approx(0.75)
    assert model.occupancy(plan, poor) == pytest.approx(0.375)
    assert model.occupancy(ServingPlan(T=16, max_inflight=1), ideal) == 0.0


def test_cost_model_charges_bucket_fragmentation():
    """A tiny tile shatters heterogeneous traffic into many executables;
    the compile term must bite."""
    shapes = [("eigh", (d, d), 4) for d in (6, 14, 22, 30, 38, 46)]
    profile = TrafficProfile.from_shapes(shapes)
    model = CostModel()
    fine = model.plan_cost(ServingPlan(mode="tile", T=8, max_batch=4),
                           profile)
    coarse = model.plan_cost(ServingPlan(mode="pow2", T=16, max_batch=4),
                             profile)
    assert fine["n_buckets"] > coarse["n_buckets"]
    assert fine["compile_s"] > coarse["compile_s"]


def test_cost_model_calibrates_from_profile():
    srv = PCAServer(CFG, policy=BucketPolicy(T=8), max_delay_s=10.0)
    mats = [_sym(9, seed=i) for i in range(8)]
    srv.solve_many(mats)
    srv.solve_many(mats)
    profile = TrafficProfile.from_stats(srv.stats,
                                        captured=srv.describe_plan())
    model = CostModel.calibrated(profile)
    default = CostModel()
    # compile cost comes from the measured hit/miss dispatch split
    assert model.compile_s_per_executable != pytest.approx(
        default.compile_s_per_executable)
    assert model.device_work_per_s == pytest.approx(
        profile.work_dispatched / profile.device_s)


def test_solve_work_scales():
    assert solve_work("eigh", (32, 32)) == 32.0 ** 3
    assert solve_work("svd", (64, 16)) == 64 * 16 ** 2 + 16 ** 3
    assert solve_work("pca", (64, 16)) > solve_work("eigh", (16, 16))


# ---------------------------------------------------------------------------
# apply_plan hot-swap
# ---------------------------------------------------------------------------

def test_apply_plan_midstream_preserves_inflight_and_queued_tickets():
    """The swap drains in-flight flushes, re-buckets queued tickets in
    place, and dispatches any queue the new (smaller) batch cap considers
    full -- all under the injected clock, so every step is deterministic."""
    t = [0.0]
    srv = PCAServer(CFG, policy=BucketPolicy(T=8), max_delay_s=0.5,
                    clock=lambda: t[0], max_inflight=2, max_batch=4)
    flying = [srv.submit(_sym(6, seed=i)) for i in range(4)]  # full flush
    assert all(tk.inflight and not tk.done for tk in flying)
    queued = [srv.submit(_sym(11, seed=10 + i)) for i in range(2)]
    assert all(tk.bucket == (16, 16) for tk in queued)
    switch = srv.apply_plan(ServingPlan(mode="pow2", T=4, pow2_cap=16,
                                        max_batch=2, max_inflight=1))
    # in-flight work retired first: those tickets are done, under the old
    # plan's buckets
    assert all(tk.done for tk in flying)
    # queued tickets were re-bucketed in place (pow2 T=4: 11 -> 16) and the
    # new max_batch=2 made their queue full, so they dispatched at once
    assert switch["requeued"] == 2
    assert all(tk.done and tk.bucket == (16, 16) for tk in queued)
    assert srv.pending() == 0 and srv.inflight() == 0
    for i, tk in enumerate(flying):
        ref = np.linalg.eigh(_sym(6, seed=i))[0][::-1]
        np.testing.assert_allclose(tk.result().eigenvalues, ref,
                                   rtol=1e-3, atol=1e-3)
    for i, tk in enumerate(queued):
        ref = np.linalg.eigh(_sym(11, seed=10 + i))[0][::-1]
        np.testing.assert_allclose(tk.result().eigenvalues, ref,
                                   rtol=1e-3, atol=1e-3)
    # the switch is on the record: old plan, new plan, requeue count
    assert len(srv.stats.plan_switches) == 1
    rec = srv.stats.plan_switches[0]
    assert rec["from"]["T"] == 8 and rec["to"]["T"] == 4
    assert rec["to"]["max_batch"] == 2 and rec["requeued"] == 2
    assert srv.stats.summary()["plan_switches"] == 1


def test_apply_plan_requeue_keeps_deadlines_and_submit_order():
    t = [0.0]
    srv = PCAServer(CFG, policy=BucketPolicy(T=8), max_delay_s=0.5,
                    clock=lambda: t[0], max_batch=8)
    early = srv.submit(_sym(6, seed=0))
    t[0] = 0.2
    late = srv.submit(_sym(12, seed=1))
    srv.apply_plan(ServingPlan(mode="tile", T=16, max_batch=8,
                               max_inflight=1))
    # both requests now share one (16, 16) bucket queue, oldest first
    assert early.bucket == late.bucket == (16, 16)
    assert srv.pending() == 2
    t[0] = 0.45
    assert srv.poll() == 0                     # original deadlines survive
    t[0] = 0.51                                # early's deadline (0.5) fires
    assert srv.poll() == 2                     # one flush retires both
    assert early.done and late.done
    assert early.record.batch_size == 2


def test_apply_plan_validates_plan():
    srv = PCAServer(CFG, policy=BucketPolicy(T=8))
    with pytest.raises(ValueError, match="max_inflight"):
        srv.apply_plan(ServingPlan(max_inflight=0))
    with pytest.raises(ValueError, match="max_batch"):
        srv.apply_plan(ServingPlan(max_batch=0))


def test_apply_plan_failure_leaves_server_and_tickets_intact():
    """A plan that fails to materialize (bad pow2_cap, bogus mesh spec)
    must raise *before* the server mutates: queued tickets stay queued and
    the old plan stays in force."""
    srv = PCAServer(CFG, policy=BucketPolicy(T=8), max_delay_s=10.0,
                    max_batch=4)
    ticket = srv.submit(_sym(6))
    before = srv.describe_plan()
    with pytest.raises(ValueError, match="multiple of T"):
        srv.apply_plan(ServingPlan(mode="pow2", T=16, pow2_cap=40))
    with pytest.raises(ValueError):
        srv.apply_plan(ServingPlan(mesh="bogus"))
    assert srv.describe_plan() == before
    assert srv.pending() == 1 and not ticket.done
    srv.drain()
    assert ticket.done                     # the ticket was never orphaned
    ref = np.linalg.eigh(_sym(6))[0][::-1]
    np.testing.assert_allclose(ticket.result().eigenvalues, ref,
                               rtol=1e-3, atol=1e-3)


def test_apply_plan_realigns_config_with_cold_server():
    """A hot-swapped server must compile the executables a cold server
    built from the same plan would -- including the matmul block size
    (config.T) when the config routes through a kernel backend -- so
    hot-vs-cold results stay bit-identical even off the default datapath."""
    cfg = PCAConfig(T=16, S=4, sweeps=8, backend="ref", rotation="matmul")
    mats = [_sym(n, seed=n) for n in (5, 9, 12, 7)]
    plan = ServingPlan(mode="tile", T=8, max_batch=2, max_inflight=1)
    cold = server_for_plan(plan, cfg)
    hot = PCAServer(cfg, policy=BucketPolicy(T=16), max_delay_s=10.0)
    hot.submit(mats[0])                        # queued across the swap
    hot.apply_plan(plan)
    assert hot.config.T == 8 and hot.config.S == 2
    # serve the re-queued request alone, so both servers flush the same
    # groups at the same rungs below
    hot.drain()
    for g, w in zip(cold.solve_many(mats), hot.solve_many(mats)):
        for f in dataclasses.fields(g):
            np.testing.assert_array_equal(np.asarray(getattr(g, f.name)),
                                          np.asarray(getattr(w, f.name)))


def test_apply_plan_same_buckets_reuse_executables():
    srv = PCAServer(CFG, policy=BucketPolicy(T=8), max_delay_s=10.0)
    srv.solve_many([_sym(7, seed=i) for i in range(4)])
    misses = srv.stats.cache_misses
    # same bucketing, different pipeline depth: the (op, bucket, batch)
    # executable survives the swap
    srv.apply_plan(ServingPlan(mode="tile", T=8, max_batch=4,
                               max_inflight=2))
    srv.solve_many([_sym(7, seed=10 + i) for i in range(4)])
    assert srv.stats.cache_misses == misses


# ---------------------------------------------------------------------------
# the search driver
# ---------------------------------------------------------------------------

def test_autotune_analytic_and_measured_agree_on_simple_trace():
    """Batching a homogeneous burst beats serve-one-at-a-time both in the
    model and on the hardware: the analytic top-1 and the measured top-1
    must be the same plan."""
    mats = [_sym(9, seed=i) for i in range(16)]
    srv = PCAServer(CFG, policy=BucketPolicy(T=8), max_delay_s=10.0)
    for _ in range(2):
        srv.solve_many(mats)
    profile = TrafficProfile.from_stats(srv.stats,
                                        captured=srv.describe_plan())
    grid = [ServingPlan(mode="tile", T=8, max_batch=1),
            ServingPlan(mode="tile", T=8, max_batch=8)]
    analytic = autotune(profile, grid=grid, config=CFG)
    assert analytic.mode == "analytic"
    assert analytic.best.max_batch == 8
    measured = autotune(profile, grid=grid, config=CFG, measure_top_k=2,
                        passes=2)
    assert measured.mode == "measured"
    assert len(measured.measured) == 2
    assert measured.best == analytic.best
    json.dumps(measured.to_json())             # result is report-ready


def test_server_for_plan_matches_plan():
    plan = ServingPlan(mode="pow2", T=8, pow2_cap=32, max_batch=2,
                       max_inflight=3)
    srv = server_for_plan(plan, CFG)
    described = srv.describe_plan()
    assert described["mode"] == "pow2" and described["T"] == 8
    assert described["pow2_cap"] == 32
    assert described["max_batch"] == 2 and described["max_inflight"] == 3
    assert srv.config.sweeps == CFG.sweeps     # config carries over
