"""Serving throughput: batched multi-tenant engine vs one-at-a-time baseline.

Sweeps (T, S, bucket policy) over a fixed mixed-shape eigh request stream and
reports requests/s plus p50/p99 service latency.  The S=1 row is the
serve-one-at-a-time baseline (every request its own dispatch); batched rows
must clear >2x its requests/s to demonstrate the S-array axis paying off in
software.  Also emits ``BENCH_serve_throughput.json`` for the perf
trajectory.

The sharded sweep axis (``sharded_rows``) holds the flush size fixed and
sweeps the device-mesh size: one large bucket, ``MeshExecutor`` over
1/2/4/8 host devices.  It always runs in a subprocess that forces
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
``tests/test_distributed.py`` recipe), so the per-device-count rows mean
the same thing on a laptop, in either CI matrix job, or next to a real
accelerator -- the comparison ``scripts/check_bench.py`` gates on never
mixes device-visibility regimes.

The sync-vs-async sweep axis (``async_rows``) sweeps the pipeline depth
(``max_inflight`` 1/2/4) over the same large bucket in *latency mode*:
single-request flushes (``max_batch=1``), every request its own dispatch.
That is the regime where a synchronous engine loses the most to
host/device serialization -- the flush rate is highest, so the host stage
(stack / launch / gather / unpack / telemetry, plus the next request's
submission) is a measurable fraction of each flush -- and therefore the
regime that isolates what the dispatch/in-flight/retire pipeline buys: at
``max_inflight>1`` the host batches request k+1 while the device solves
request k.  A deliberately light sweep count keeps the device stage from
drowning the host stage (all rows, sync and async, share the identical
solver, so the comparison is pure pipeline).  Rows are regime-pinned like
the sharded ones: a subprocess forces a single host device, and the three
servers' timing passes are interleaved so a slow host phase cannot land on
one pipeline depth systematically.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

from repro.core import PCAConfig
from repro.launch.serve_pca import mixed_traffic
from repro.serving import (BucketPolicy, LocalExecutor, MeshExecutor,
                           PCAServer, host_mesh, threshold_router)

from .common import REPO_ROOT, emit, emit_json, refuse_on_tpu

MIXED_DIMS = (10, 14, 18, 24, 29, 31, 37, 46)

# sharded sweep: one large bucket (dim 46 -> 48 under T=16), fixed flush
# size, device count as the only axis
SHARDED_DIM = 46
SHARDED_FLUSH = 64
SHARDED_DEVICE_COUNTS = (1, 2, 4, 8)

# sync-vs-async sweep: the same large bucket in latency mode
# (single-request flushes), pipeline depth as the only axis
ASYNC_DIM = 46
ASYNC_FLUSH = 1
ASYNC_SWEEPS = 2
ASYNC_REQUESTS = 48
ASYNC_INFLIGHT = (1, 2, 4)


def _measure(mats, T: int, S: int, mode: str, sweeps: int = 10,
             backend_router=None, executor=None, max_batch=None,
             max_inflight: int = 1, reps: int = 3):
    srv = PCAServer(PCAConfig(T=T, S=S, sweeps=sweeps),
                    policy=BucketPolicy(T=T, mode=mode), max_delay_s=10.0,
                    backend_router=backend_router, executor=executor,
                    max_batch=max_batch, max_inflight=max_inflight)
    srv.solve_many(mats)            # warmup: compile every bucket executable
    # best-of-reps: scheduler noise only ever slows a pass down, and the
    # check_bench regression gate needs run-to-run stability
    wall = float("inf")
    for _ in range(reps):
        srv.stats.reset()
        t0 = time.perf_counter()
        srv.solve_many(mats)
        wall = min(wall, time.perf_counter() - t0)
    s = srv.stats.summary()
    return {
        "T": T, "S": S, "policy": mode,
        "wall_s": wall,
        "requests_per_s": len(mats) / wall,
        "us_per_request": wall / len(mats) * 1e6,
        "latency_p50_ms": s["latency_p50_ms"],
        "latency_p99_ms": s["latency_p99_ms"],
        "mean_padding_waste": s["mean_padding_waste"],
        "mean_batch": s["mean_batch"],
        "cache_hit_rate": s["cache_hit_rate"],
    }


def sharded_sweep() -> list:
    """Per-device-count rows for one large bucket at a fixed flush size.

    Must run under ``--xla_force_host_platform_device_count=8`` (or with 8
    real devices); device counts beyond what is visible are dropped.  The
    n_devices=1 row is the single-device ``LocalExecutor`` flush of the
    same ``SHARDED_FLUSH``-request batch, so each row answers "what did
    sharding this exact flush across n devices buy?".
    """
    import jax

    mats = mixed_traffic(SHARDED_FLUSH, "eigh", (SHARDED_DIM,))
    rows = []
    base_rps = None
    for n_dev in SHARDED_DEVICE_COUNTS:
        if n_dev > jax.device_count():
            break
        ex = (MeshExecutor(mesh=host_mesh(n_dev)) if n_dev > 1
              else LocalExecutor())
        row = _measure(mats, T=16, S=SHARDED_FLUSH, mode="tile",
                       executor=ex, max_batch=SHARDED_FLUSH)
        row["n_devices"] = n_dev
        row["flush_batch"] = SHARDED_FLUSH
        if n_dev == 1:
            base_rps = row["requests_per_s"]
        row["speedup_vs_1dev"] = (row["requests_per_s"] / base_rps
                                  if base_rps else float("nan"))
        rows.append(row)
    return rows


def _sweep_subprocess(fn_name: str, xla_flags: str) -> list:
    """Run a sweep function in a child pinned to one XLA regime.

    XLA fixes the device count at backend init, so an already-started
    process cannot change its device visibility; the subprocess both makes
    a sweep runnable from anywhere (either CI matrix job, a laptop, next
    to an accelerator) and pins its rows to one regime so
    ``scripts/check_bench.py`` never compares across regimes.
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = xla_flags
    env["PYTHONPATH"] = (str(REPO_ROOT / "src") + os.pathsep
                         + str(REPO_ROOT))
    prog = (f"import json; from benchmarks.serve_throughput import "
            f"{fn_name}; print(json.dumps({fn_name}()))")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=1200, cwd=REPO_ROOT)
    if r.returncode != 0:
        raise RuntimeError(f"{fn_name} subprocess failed:\n"
                           f"{r.stderr[-4000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def sharded_sweep_subprocess() -> list:
    return _sweep_subprocess("sharded_sweep",
                             "--xla_force_host_platform_device_count=8")


def async_sweep() -> list:
    """Pipeline-depth rows for the large bucket in latency mode.

    One server per ``max_inflight`` depth, identical solver and traffic;
    the only difference is whether the engine blocks on every flush
    (depth 1, the synchronous baseline) or keeps flushes in flight while
    it batches the next request.  Timing passes are *interleaved* across
    the servers -- a noisy-neighbour phase hits every depth equally
    instead of skewing one row -- and each row keeps its best pass (the
    same best-of-reps policy as ``_measure``).
    """
    import jax

    mats = mixed_traffic(ASYNC_REQUESTS, "eigh", (ASYNC_DIM,))
    servers = {
        depth: PCAServer(
            PCAConfig(T=16, S=ASYNC_FLUSH, sweeps=ASYNC_SWEEPS),
            policy=BucketPolicy(T=16, mode="tile"), max_delay_s=10.0,
            max_batch=ASYNC_FLUSH, max_inflight=depth)
        for depth in ASYNC_INFLIGHT
    }
    for srv in servers.values():
        srv.solve_many(mats)        # warmup: compile the bucket executable
    best = {depth: (float("inf"), None) for depth in ASYNC_INFLIGHT}
    for _ in range(8):
        for depth, srv in servers.items():
            srv.stats.reset()
            t0 = time.perf_counter()
            srv.solve_many(mats)
            wall = time.perf_counter() - t0
            if wall < best[depth][0]:
                best[depth] = (wall, srv.stats.summary())
    rows = []
    base_rps = None
    for depth in ASYNC_INFLIGHT:
        wall, s = best[depth]
        row = {
            "T": 16, "S": ASYNC_FLUSH, "policy": "tile", "op": "eigh",
            "sweeps": ASYNC_SWEEPS, "inflight": depth,
            "device_count": jax.device_count(),
            "wall_s": wall,
            "requests_per_s": len(mats) / wall,
            "us_per_request": wall / len(mats) * 1e6,
            "latency_p50_ms": s["latency_p50_ms"],
            "latency_p99_ms": s["latency_p99_ms"],
            "overlap_frac": s["overlap_frac"],
            "mean_inflight_depth": s["mean_inflight_depth"],
        }
        if depth == 1:
            base_rps = row["requests_per_s"]
        row["speedup_vs_sync"] = (row["requests_per_s"] / base_rps
                                  if base_rps else float("nan"))
        rows.append(row)
    return rows


def async_sweep_subprocess() -> list:
    return _sweep_subprocess("async_sweep",
                             "--xla_force_host_platform_device_count=1")


def run(fast: bool = True) -> None:
    import jax

    refuse_on_tpu("benchmarks.serve_throughput")
    n_req = 32 if fast else 128
    mats = mixed_traffic(n_req, "eigh", MIXED_DIMS)
    grid = [(16, 1, "tile"),            # serve-one-at-a-time baseline
            (16, 4, "tile"), (16, 8, "tile"),
            (16, 4, "pow2"), (16, 8, "pow2")]
    if not fast:
        grid += [(32, 4, "tile"), (32, 8, "tile"), (32, 8, "pow2")]

    rows = []
    baseline_rps = None
    for T, S, mode in grid:
        row = _measure(mats, T, S, mode)
        # part of the row's *identity* for scripts/check_bench.py: grid
        # timings measured under different device splits (the mesh-8 CI
        # job carves the CPU into 8 host devices) are not comparable, so
        # rows only match within one device-visibility regime.  The
        # sharded rows pin their regime by construction (subprocess with
        # forced host-device count).
        row["device_count"] = jax.device_count()
        if S == 1:
            baseline_rps = row["requests_per_s"]
        row["speedup_vs_serial"] = (row["requests_per_s"] / baseline_rps
                                    if baseline_rps else float("nan"))
        rows.append(row)
        emit(f"serve_T{T}_S{S}_{mode}", f"{row['us_per_request']:.1f}",
             f"rps={row['requests_per_s']:.1f}"
             f";p50_ms={row['latency_p50_ms']:.2f}"
             f";p99_ms={row['latency_p99_ms']:.2f}"
             f";waste={row['mean_padding_waste']:.3f}"
             f";speedup={row['speedup_vs_serial']:.2f}")

    best = max(r["speedup_vs_serial"] for r in rows if r["S"] >= 4)
    emit("serve_best_batched_speedup", f"{best:.2f}",
         "acceptance: >2x vs serve-one-at-a-time")

    sharded_rows = sharded_sweep_subprocess()
    for row in sharded_rows:
        emit(f"serve_sharded_{row['n_devices']}dev",
             f"{row['us_per_request']:.1f}",
             f"rps={row['requests_per_s']:.1f}"
             f";speedup_vs_1dev={row['speedup_vs_1dev']:.2f}")
    sharded_best = (max(r["speedup_vs_1dev"] for r in sharded_rows)
                    if sharded_rows else float("nan"))
    emit("serve_sharded_best_speedup", f"{sharded_best:.2f}",
         "acceptance: >=2x at 8 host devices vs 1 (large bucket)")

    async_rows = async_sweep_subprocess()
    for row in async_rows:
        emit(f"serve_async_inflight{row['inflight']}",
             f"{row['us_per_request']:.1f}",
             f"rps={row['requests_per_s']:.1f}"
             f";speedup_vs_sync={row['speedup_vs_sync']:.2f}"
             f";overlap={row['overlap_frac']:.2f}")
    async_best = (max(r["speedup_vs_sync"] for r in async_rows)
                  if async_rows else float("nan"))
    emit("serve_async_best_speedup", f"{async_best:.2f}",
         "acceptance: >=1.3x for max_inflight>1 vs 1 (large bucket)")

    emit_json("serve_throughput", {
        "n_requests": n_req,
        "mixed_dims": list(MIXED_DIMS),
        "baseline_requests_per_s": baseline_rps,
        "best_batched_speedup": best,
        "rows": rows,
        "sharded_dim": SHARDED_DIM,
        "sharded_flush": SHARDED_FLUSH,
        "sharded_best_speedup": sharded_best,
        "sharded_rows": sharded_rows,
        "async_dim": ASYNC_DIM,
        "async_flush": ASYNC_FLUSH,
        "async_sweeps": ASYNC_SWEEPS,
        "async_requests": ASYNC_REQUESTS,
        "async_best_speedup": async_best,
        "async_rows": async_rows,
    })


def selftest() -> int:
    """CI smoke: one backend-sweep point -- a routed server splits traffic
    across two kernel backends in one run; results are verified against
    numpy and both backends must actually be exercised."""
    import json

    import numpy as np

    mats = mixed_traffic(8, "eigh", (6, 20))
    srv = PCAServer(PCAConfig(T=8, S=4, sweeps=14),
                    policy=BucketPolicy(T=8), max_delay_s=10.0,
                    backend_router=threshold_router(16, large="interpret",
                                                    small=None))
    # warmup pass doubles as the correctness check (compiles both buckets)
    for m, r in zip(mats, srv.solve_many(mats)):
        ref = np.linalg.eigh(m)[0][::-1]
        np.testing.assert_allclose(r.eigenvalues, ref, rtol=1e-3, atol=1e-3)
    routed = sorted({(r.bucket, str(r.backend))
                     for r in srv.stats.records})
    assert len({b for _, b in routed}) == 2, routed
    srv.stats.reset()
    t0 = time.perf_counter()
    srv.solve_many(mats)
    wall = time.perf_counter() - t0
    s = srv.stats.summary()
    assert s["cache_hit_rate"] == 1.0, s   # steady state: no recompiles
    print("serve_throughput selftest ok:", json.dumps({
        "routed_buckets": [f"{bkt}->{be}" for bkt, be in routed],
        "requests_per_s": round(len(mats) / wall, 1),
        "cache_hit_rate": s["cache_hit_rate"],
    }))
    return 0


if __name__ == "__main__":
    import argparse
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--selftest", action="store_true",
                    help="one backend-sweep smoke point and exit")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    if args.selftest:
        sys.exit(selftest())
    print("name,us_per_call,derived")
    run(fast=not args.full)
