"""Cold start: time-to-first-response for a fresh serving replica.

MANOJAVAM's fabric answers from cycle one because it is pre-built; a JIT
replica spends its first seconds inside XLA instead -- exactly when it was
spawned because traffic already exceeds capacity.  This benchmark measures
what the persistent executable cache (``serving.cache``) and
``PCAServer.warmup`` buy, as the latency of the *first* request a fresh
replica serves:

  cold       no cache dir: the first flush pays the full JIT compile.
  warm_disk  ``cache_dir`` points at a directory a previous replica
             seeded: the first flush deserializes the AOT executable
             (zero XLA work) instead of compiling.
  warmup     ``cache_dir`` warm *and* ``warmup(profile)`` runs before any
             request is accepted (the real deployment shape: warm before
             joining the load balancer): the first flush is a memory hit.

Every mode runs in a **fresh subprocess** -- a replica's cold start cannot
be measured in a process whose jit caches are already warm -- against the
byte-identical burst, and every row carries a sha256 over its results so
the parent can assert the three paths are *bit-for-bit* identical (the
serialize/deserialize round trip must never touch the math).

Emits ``BENCH_cold_start.json``; ``scripts/check_bench.py`` gates the warm
rows' ``ttfr_ms`` against the cold row's (a warm replica that still pays
compile-scale first-request latency is a cache regression).
"""
from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time

from .common import REPO_ROOT, emit, emit_json, refuse_on_tpu

T = 16
BATCH = 4
SWEEPS = 10
DIM = 14            # one eigh bucket (16, 16) under T -- one executable
REQUESTS = 8
MODES = ("cold", "warm_disk", "warmup")


def _burst(n: int = REQUESTS):
    import numpy as np
    rng = np.random.default_rng(7)
    mats = []
    for _ in range(n):
        a = rng.standard_normal((DIM, DIM)).astype(np.float32)
        mats.append((a + a.T) / 2)
    return mats


def write_profile(path: str) -> None:
    from repro.serving import TrafficProfile
    TrafficProfile.from_shapes(
        [("eigh", (DIM, DIM), REQUESTS)]).save(path)


def replica_row(mode: str, cache_dir: str, profile_path: str) -> dict:
    """One fresh replica's first-request story (run in a fresh process)."""
    import numpy as np
    from repro.core import PCAConfig
    from repro.serving import BucketPolicy, PCAServer, TrafficProfile

    srv = PCAServer(PCAConfig(T=T, S=BATCH, sweeps=SWEEPS),
                    policy=BucketPolicy(T=T), max_delay_s=10.0,
                    cache_dir=(cache_dir if mode != "cold" else None))
    warmup_s = 0.0
    warmed = 0
    if mode == "warmup":
        t0 = time.perf_counter()
        doc = srv.warmup(TrafficProfile.load(profile_path))
        warmup_s = time.perf_counter() - t0
        warmed = doc["executables"]
    mats = _burst()
    # TTFR: the first request's submit-to-result latency -- compile (cold),
    # AOT deserialize (warm_disk) or pure execution (warmup) included
    t0 = time.perf_counter()
    first = srv.submit(mats[0], op="eigh").wait()
    ttfr_s = time.perf_counter() - t0
    rest = srv.solve_many(mats[1:], op="eigh")
    digest = hashlib.sha256()
    for r in [first] + rest:
        digest.update(np.ascontiguousarray(r.eigenvalues).tobytes())
        digest.update(np.ascontiguousarray(r.eigenvectors).tobytes())
    summary = srv.cache_summary()
    disk = summary["disk"] or {}
    return {
        "mode": mode,
        "ttfr_ms": ttfr_s * 1e3,
        "warmup_s": warmup_s,
        "warmup_executables": warmed,
        "requests": len(mats),
        "disk_hits": int(disk.get("hits", 0)),
        "disk_stores": int(disk.get("stores", 0)),
        "burst_sha256": digest.hexdigest(),
    }


def _replica_subprocess(mode: str, cache_dir: str,
                        profile_path: str) -> dict:
    """Run one replica in a fresh process (fresh jit caches, fresh XLA)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(REPO_ROOT / "src") + os.pathsep
                         + str(REPO_ROOT))
    prog = ("import json, sys; "
            "from benchmarks.cold_start import replica_row; "
            "print(json.dumps(replica_row(*sys.argv[1:4])))")
    r = subprocess.run(
        [sys.executable, "-c", prog, mode, cache_dir, profile_path],
        capture_output=True, text=True, env=env, timeout=1200,
        cwd=REPO_ROOT)
    if r.returncode != 0:
        raise RuntimeError(f"cold_start replica ({mode}) failed:\n"
                           f"{r.stderr[-4000:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def sweep() -> list:
    """Seed a cache dir once, then measure every mode in a fresh process."""
    rows = []
    with tempfile.TemporaryDirectory() as tmp:
        profile_path = os.path.join(tmp, "profile.json")
        cache_dir = os.path.join(tmp, "cache")
        write_profile(profile_path)
        # seed: one throwaway replica compiles + serializes the executable
        # (its own timings are a cold start and are discarded)
        _replica_subprocess("warmup", cache_dir, profile_path)
        for mode in MODES:
            rows.append(_replica_subprocess(mode, cache_dir, profile_path))
    digests = {r["burst_sha256"] for r in rows}
    assert len(digests) == 1, f"cold/warm results diverged: {rows}"
    cold_ms = next(r["ttfr_ms"] for r in rows if r["mode"] == "cold")
    for r in rows:
        r["ttfr_reduction_vs_cold"] = (1.0 - r["ttfr_ms"] / cold_ms
                                       if cold_ms > 0 else 0.0)
    return rows


def run(fast: bool = True) -> None:
    del fast                        # 4 short subprocesses either way
    refuse_on_tpu("benchmarks.cold_start")
    rows = sweep()
    for row in rows:
        emit(f"cold_start_{row['mode']}", f"{row['ttfr_ms'] * 1e3:.1f}",
             f"ttfr_ms={row['ttfr_ms']:.1f}"
             f";reduction={row['ttfr_reduction_vs_cold']:.3f}"
             f";disk_hits={row['disk_hits']}")
    by_mode = {r["mode"]: r for r in rows}
    emit_json("cold_start", {
        "dim": DIM, "T": T, "batch": BATCH, "sweeps": SWEEPS,
        "requests": REQUESTS,
        "cold_ttfr_ms": by_mode["cold"]["ttfr_ms"],
        "warm_disk_ttfr_reduction":
            by_mode["warm_disk"]["ttfr_reduction_vs_cold"],
        "warmup_ttfr_reduction":
            by_mode["warmup"]["ttfr_reduction_vs_cold"],
        "bitwise_identical": True,  # sweep() asserts it
        "rows": rows,
    })


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
