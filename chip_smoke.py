"""Smoke run of the PCA serving path on a TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the multi-chip paths only

One chip: ``PCAServer`` is built from ``ServerSpec`` defaults, as
``repro.launch.serve_pca`` builds it, and serves

  * one ``pca`` request of mnist-28x28 at its full 70000x784 and one
    ``svd`` request of 20-newsgroups at 18846x1024 (seeded synthetic data
    of those shapes, ``benchmarks.common.synthetic_dataset``);
  * a burst of small eigh/svd/pca requests at dims 8-64, replayed to show
    the steady state compiles nothing;
  * the mnist request again on the kernel datapath (``backend="pallas"``);
  * the burst through a disk-warmed replica (the persistent executable
    tier);
  * ``serve_pca``'s own selftest.

Four chips: the small burst through ``MeshExecutor`` against
``LocalExecutor``, and ``fit_distributed`` of mnist-28x28 on a 4-device
data mesh against the single-chip ``fit``.

Every result is checked against a float64 NumPy reference computed in
this process, or against its single-device counterpart; any failed check
raises and the exit code is non-zero.  The script runs in one process and
starts none.  It refuses to run where JAX's default backend is not a TPU.
Times it prints are smoke timings of single requests (host wall clock,
transfers included), not benchmark figures.  The last line of standard
output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
FULL_SHAPES = {"mnist-28x28": "pca", "20-newsgroups": "svd"}
BURST_REQUESTS = 72


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def say(tag: str, **doc) -> None:
    print(f"{tag}: {json.dumps(doc, default=float)}", flush=True)


# -- float64 references ------------------------------------------------------

def standardized(X: np.ndarray) -> np.ndarray:
    """float64 twin of ``core.covariance.standardize`` (ddof 0)."""
    X = X.astype(np.float64)
    std = X.std(axis=0)
    return (X - X.mean(axis=0)) / np.where(std < 1e-8, 1.0, std)


def fp64_spectrum(G: np.ndarray):
    """Descending eigenpairs of a symmetric float64 matrix."""
    w, V = np.linalg.eigh(G)
    return w[::-1], V[:, ::-1]


def rel_err(got, ref) -> float:
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(np.asarray(got, np.float64) - ref)
                 / max(np.linalg.norm(ref), 1e-300))


def cvcr_k(w: np.ndarray, target: float = 0.95) -> int:
    cvcr = np.cumsum(np.maximum(w, 0.0)) / np.sum(np.maximum(w, 0.0))
    return int(min(np.sum(cvcr < target) + 1, len(w)))


def sin_theta(Q1: np.ndarray, Q2: np.ndarray) -> float:
    """Sine of the largest principal angle between span(Q1) and span(Q2)
    (orthonormal columns): ||(I - Q2 Q2^T) Q1||_2."""
    Q1 = np.asarray(Q1, np.float64)
    Q2 = np.asarray(Q2, np.float64)
    return float(np.linalg.norm(Q1 - Q2 @ (Q2.T @ Q1), 2))


def subspace_bound(w: np.ndarray, k: int, cov_budget: float) -> float:
    """Davis-Kahan: a symmetric perturbation E moves the top-k invariant
    subspace of G by sin(theta) <= ||E||_2 / (lambda_k - lambda_k+1).
    The fp32 covariance budget bounds ||E||_F <= budget * ||G||_F, and
    ||E||_2 <= ||E||_F.  A covariance formed at bf16 operand precision
    (relative error ~1e-3) overshoots this bound by two orders."""
    return cov_budget * float(np.linalg.norm(w)) / float(w[k - 1] - w[k])


def check_values(label: str, got, ref, budget: float) -> float:
    err = rel_err(got, ref)
    check(err <= budget, f"{label}: spectrum error {err} > {budget}")
    return err


def check_subspace(label: str, vecs, ref_w, ref_V, cov_budget: float
                   ) -> dict:
    """Top-k subspace (k at 95% CVCR) within the Davis-Kahan bound."""
    k = cvcr_k(ref_w)
    sin = sin_theta(np.asarray(vecs)[:, :k], ref_V[:, :k])
    bound = subspace_bound(ref_w, k, cov_budget)
    check(sin <= bound, f"{label}: top-{k} subspace sin {sin} > {bound}")
    return {"k_95cvcr": k, "sin_theta_max": sin, "sin_theta_bound": bound}


# -- one chip ----------------------------------------------------------------

def serve_twice(srv, op: str, X: np.ndarray):
    """Serve one request twice: the first call compiles, the second must
    be a cache hit with bitwise the same result."""
    t0 = time.perf_counter()
    first = srv.solve_many([X], op=op)[0]
    t1 = time.perf_counter()
    again = srv.solve_many([X], op=op)[0]
    t2 = time.perf_counter()
    rec = srv.stats.records[-1]
    check(rec.cache_hit, f"{op} {X.shape}: second request recompiled")
    check(same_bits(first, again), f"{op} {X.shape}: repeat differs")
    timing = {"bucket": list(rec.bucket),
              "padded_batch": srv.stats.flush_records[-1].padded_batch,
              "setup_s": (t1 - t0) - (t2 - t1), "solve_s": t2 - t1}
    return first, timing


def same_bits(a, b) -> bool:
    return all(np.array_equal(np.asarray(getattr(a, f.name)),
                              np.asarray(getattr(b, f.name)))
               for f in dataclasses.fields(a))


def pca_reference(X: np.ndarray):
    xs = standardized(X)
    return fp64_spectrum(xs.T @ xs)


def full_shape_phase(srv, data, refs, budgets) -> dict:
    """The paper's datasets at full shape, one request each."""
    fits = {}
    for name, op in FULL_SHAPES.items():
        X = data[name]
        res, timing = serve_twice(srv, op, X)
        ref_w, ref_V = refs[name]
        doc = {"op": op, "shape": list(X.shape)}
        if op == "pca":
            doc["spectrum_rel_err"] = check_values(
                name, res.eigenvalues, ref_w, budgets["eigh"])
            doc.update(check_subspace(name, res.components, ref_w, ref_V,
                                      budgets["covariance"]))
            doc["off_norm"] = res.off_norm
        else:
            doc["spectrum_rel_err"] = check_values(
                name, res.S, np.sqrt(np.maximum(ref_w, 0.0)), budgets["svd"])
            doc.update(check_subspace(name, np.asarray(res.Vt).T, ref_w,
                                      ref_V, budgets["covariance"]))
            doc["off_norm"] = "not served for svd"
        say(f"full-shape {name} (smoke timings)", **doc, **timing)
        fits[name] = res
    return fits


def make_burst(n: int, seed: int):
    from repro.serving.autotune import synthesize
    rng = np.random.default_rng(seed)
    burst = []
    for i in range(n):
        op = ("eigh", "svd", "pca")[i % 3]
        d = int(rng.integers(8, 65))
        burst.append((op, synthesize(op, (d, d) if op == "eigh"
                                     else (4 * d, d), rng)))
    return burst


def serve_burst(srv, burst):
    tickets = [srv.submit(m, op=op) for op, m in burst]
    srv.drain()
    return [t.result() for t in tickets]


def burst_phase(srv, burst, budgets):
    """Small mixed requests against NumPy, then a replay that must hit
    the executable cache on every flush."""
    t0 = time.perf_counter()
    results = serve_burst(srv, burst)
    t1 = time.perf_counter()
    worst = 0.0
    for i, ((op, m), r) in enumerate(zip(burst, results)):
        label = f"burst request {i} ({op} {m.shape})"
        if op == "eigh":
            ref = np.linalg.eigvalsh(m.astype(np.float64))[::-1]
            err = check_values(label, r.eigenvalues, ref, budgets["eigh"])
        elif op == "svd":
            ref = np.linalg.svd(m.astype(np.float64), compute_uv=False)
            err = check_values(label, r.S, ref, budgets["svd"])
        else:
            err = check_values(label, r.eigenvalues, pca_reference(m)[0],
                               budgets["eigh"])
        worst = max(worst, err)
    records = list(srv.stats.records)[-len(burst):]
    buckets = {(r.op, r.bucket) for r in records}
    srv.stats.reset()
    t2 = time.perf_counter()
    replay = serve_burst(srv, burst)
    t3 = time.perf_counter()
    hit_rate = srv.stats.summary()["cache_hit_rate"]
    check(hit_rate == 1.0, f"burst replay compiled: hit rate {hit_rate}")
    check(all(same_bits(a, b) for a, b in zip(results, replay)),
          "burst replay is not bitwise equal to the first pass")
    say("burst (smoke timings)", requests=len(burst), buckets=len(buckets),
        worst_spectrum_rel_err=worst, replay_cache_hit_rate=hit_rate,
        first_pass_s=t1 - t0, replay_s=t3 - t2)
    return results


def kernel_phase(X, ref, xla, budgets) -> None:
    """The mnist request on the Pallas datapath, against float64 and
    against the XLA path.  The MM-Engine needs 128-aligned blocks on the
    chip (the compiler refuses T=16), so this server tiles at 128."""
    from repro.backends import registry
    from repro.serving import (ExecutionSpec, PCAServer, SchedulingSpec,
                               ServerSpec)
    registry.reset_resolution_counts()
    srv = PCAServer.from_spec(ServerSpec(
        scheduling=SchedulingSpec(T=128),
        execution=ExecutionSpec(backend="pallas")))
    res, timing = serve_twice(srv, "pca", X)
    ref_w, ref_V = ref
    label = "pallas mnist-28x28"
    doc = {"spectrum_rel_err": check_values(label, res.eigenvalues, ref_w,
                                            budgets["eigh"])}
    doc.update(check_subspace(label, res.components, ref_w, ref_V,
                              budgets["covariance"]))
    k = doc["k_95cvcr"]
    doc["vs_xla_spectrum_rel_err"] = check_values(
        f"{label} vs XLA", res.eigenvalues, xla.eigenvalues, budgets["eigh"])
    doc["vs_xla_sin_theta_max"] = sin_theta(res.components[:, :k],
                                            xla.components[:, :k])
    check(doc["vs_xla_sin_theta_max"] <= doc["sin_theta_bound"],
          f"{label} vs XLA subspace {doc['vs_xla_sin_theta_max']}")
    rec = srv.stats.records[-1]
    hlo = srv.executor.aot_compile(
        "pca", srv.config, rec.bucket,
        srv.stats.flush_records[-1].padded_batch).as_text()
    resolved = registry.resolution_counts().get(
        ("mm_engine_matmul", "pallas"), 0)
    check("tpu_custom_call" in hlo, f"{label}: executable has no kernel")
    check(resolved > 0, f"{label}: mm_engine_matmul never resolved to pallas")
    say("kernel datapath mnist-28x28 (smoke timings)", **doc,
        **timing, off_norm=res.off_norm, tpu_custom_call=True,
        mm_engine_pallas_resolutions=resolved)


def disk_phase(spec, burst, jit_results) -> None:
    """One replica seeds a cache directory; a fresh one must load every
    executable from it, compile nothing, and match the JIT path bitwise."""
    from repro.serving import CacheSpec, PCAServer, TrafficProfile
    profile = TrafficProfile.from_shapes([(op, m.shape, 1)
                                          for op, m in burst])
    with tempfile.TemporaryDirectory() as cdir:
        dspec = dataclasses.replace(spec, cache=CacheSpec(cache_dir=cdir))
        seeded = PCAServer.from_spec(dspec).warmup(profile)
        fresh = PCAServer.from_spec(dspec)
        warmed = fresh.warmup(profile)
        disk = fresh.cache_summary()["disk"]
        check(seeded["compile"] == seeded["executables"] > 0,
              f"seeding replica did not compile: {seeded}")
        check(warmed["compile"] == 0
              and warmed["disk"] == warmed["executables"],
              f"fresh replica compiled: {warmed}")
        check(disk["errors"] == 0, f"disk entries quarantined: {disk}")
        served = serve_burst(fresh, burst)
        check(fresh.stats.summary()["cache_hit_rate"] == 1.0,
              "disk-warmed replica compiled while serving")
        check(all(same_bits(a, b) for a, b in zip(served, jit_results)),
              "disk-warmed results are not bitwise equal to the JIT path's")
    say("disk tier (smoke timings)", executables=warmed["executables"],
        disk_hits=warmed["disk"], compiles=warmed["compile"],
        quarantined=disk["errors"], seed_s=seeded["seconds"],
        warm_s=warmed["seconds"], bitwise_equal=True)


def one_chip() -> None:
    from benchmarks.common import DATASETS, synthetic_dataset
    from repro.core.precision import ERROR_BUDGETS
    from repro.launch.serve_pca import selftest
    from repro.serving import PCAServer, ServerSpec

    budgets = ERROR_BUDGETS["fp32"]
    spec = ServerSpec()
    srv = PCAServer.from_spec(spec)
    say("server", spec=json.loads(spec.to_json()), fp32_budgets=budgets)
    data = {name: synthetic_dataset(*DATASETS[name], seed=SEED)
            for name in FULL_SHAPES}
    refs = {"mnist-28x28": pca_reference(data["mnist-28x28"])}
    news = data["20-newsgroups"].astype(np.float64)
    refs["20-newsgroups"] = fp64_spectrum(news.T @ news)
    del news
    fits = full_shape_phase(srv, data, refs, budgets)
    burst = make_burst(BURST_REQUESTS, SEED)
    results = burst_phase(srv, burst, budgets)
    kernel_phase(data["mnist-28x28"], refs["mnist-28x28"],
                 fits["mnist-28x28"], budgets)
    disk_phase(spec, burst, results)
    check(selftest() == 0, "serve_pca selftest failed")


# -- four chips ------------------------------------------------------------

def align_signs(got, want, axis: int):
    """Flip ``got``'s vectors (columns for axis=0, rows for axis=1) onto
    the sign of ``want``'s; returns the flipped array and the signs."""
    dots = np.sum(np.asarray(got, np.float64) * want, axis=axis)
    signs = np.where(dots < 0, -1.0, 1.0)
    return (got * (signs[None, :] if axis == 0 else signs[:, None])), signs


def compare_up_to_sign(op: str, g, w) -> tuple:
    """(relative value error, max vector error) with vectors sign-aligned."""
    if op == "svd":
        vt, signs = align_signs(g.Vt, w.Vt, axis=1)
        u = np.asarray(g.U) * signs[None, :]
        vec = max(np.max(np.abs(vt - w.Vt)), np.max(np.abs(u - w.U)))
        return rel_err(g.S, w.S), float(vec)
    got = g.eigenvectors if op == "eigh" else g.components
    want = w.eigenvectors if op == "eigh" else w.components
    v, _ = align_signs(got, want, axis=0)
    return rel_err(g.eigenvalues, w.eigenvalues), float(
        np.max(np.abs(v - want)))


def four_chips() -> None:
    import functools

    import jax
    from benchmarks.common import DATASETS, synthetic_dataset
    from repro.core import PCAConfig, fit, fit_distributed
    from repro.core.precision import ERROR_BUDGETS
    from repro.parallel.sharding import make_mesh
    from repro.serving import ExecutionSpec, PCAServer, SchedulingSpec, \
        ServerSpec

    budgets = ERROR_BUDGETS["fp32"]
    # unit vectors from two placements: each within rounding of the truth;
    # the smallest eigengaps of the 8-64 wide random inputs amplify that
    # rounding to ~1e-4 in a vector entry
    vec_bound = 1e-3
    n = 4
    sched = SchedulingSpec(max_batch=8)
    mesh_srv = PCAServer.from_spec(ServerSpec(
        scheduling=sched, execution=ExecutionSpec(mesh=str(n))))
    local_srv = PCAServer.from_spec(ServerSpec(scheduling=sched))
    burst = make_burst(BURST_REQUESTS, SEED)
    t0 = time.perf_counter()
    got = serve_burst(mesh_srv, burst)
    t1 = time.perf_counter()
    want = serve_burst(local_srv, burst)
    worst_val = worst_vec = 0.0
    for i, ((op, _), g, w) in enumerate(zip(burst, got, want)):
        val, vec = compare_up_to_sign(op, g, w)
        budget = budgets["svd" if op == "svd" else "eigh"]
        check(val <= budget and vec <= vec_bound,
              f"mesh request {i} ({op}): values {val}, vectors {vec}")
        worst_val, worst_vec = max(worst_val, val), max(worst_vec, vec)
    shards = {r.n_shards for r in mesh_srv.stats.records}
    check(shards == {n}, f"mesh flushes used {shards} shards, not {n}")
    ex = mesh_srv.executor
    m = burst[0][1]
    bucket = mesh_srv.policy.bucket_shape(m.shape)
    fn = ex.compile("eigh", mesh_srv.config, bucket, 8)
    slab = np.zeros((8, *bucket), np.float32)
    out = fn(slab, np.zeros(8, np.int32), np.zeros(8, np.int32))
    placed = {len(leaf.sharding.device_set)
              for leaf in jax.tree.leaves(out)}
    check(placed == {n}, f"mesh outputs sit on {placed} devices, not {n}")
    say("mesh burst vs local (smoke timings)", executor=ex.describe(),
        requests=len(burst), worst_value_rel_err=worst_val,
        worst_vector_err=worst_vec, value_budget=budgets["eigh"],
        vector_bound=vec_bound, output_devices=n, mesh_pass_s=t1 - t0)

    name = "mnist-28x28"
    X = synthetic_dataset(*DATASETS[name], seed=SEED)
    cfg = PCAConfig(sweeps=12)
    mesh = make_mesh((n,), ("data",))
    dist = jax.jit(functools.partial(fit_distributed, mesh=mesh, config=cfg))
    single = jax.jit(functools.partial(fit, config=cfg))
    t0 = time.perf_counter()
    rd = jax.block_until_ready(dist(X))
    t1 = time.perf_counter()
    rs = jax.block_until_ready(single(jax.device_put(X, jax.devices()[0])))
    t2 = time.perf_counter()
    placed = len(rd.components.sharding.device_set)
    check(placed == n, f"fit_distributed result on {placed} devices")
    ref_w = pca_reference(X)[0]
    k = cvcr_k(ref_w)
    val = rel_err(rd.eigenvalues, rs.eigenvalues)
    sin = sin_theta(np.asarray(rd.components)[:, :k],
                    np.asarray(rs.components)[:, :k])
    bound = subspace_bound(ref_w, k, budgets["covariance"])
    check(val <= budgets["eigh"], f"fit_distributed spectrum {val}")
    check(sin <= bound, f"fit_distributed top-{k} subspace {sin} > {bound}")
    say(f"fit_distributed {name} vs one chip (smoke timings)",
        devices=n, spectrum_rel_err=val, budget=budgets["eigh"],
        k_95cvcr=k, sin_theta_max=sin, sin_theta_bound=bound,
        distributed_first_call_s=t1 - t0, single_first_call_s=t2 - t1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the one-chip serving path; 4: only the "
                         "multi-chip paths and their single-device "
                         "comparisons")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    kind = devices[0].device_kind
    print(f"jax {jax.__version__}, backend {jax.default_backend()}, "
          f"device {kind}, {len(devices)} device(s)", flush=True)
    if jax.default_backend() != "tpu":
        print("chip_smoke: no TPU; this script has no CPU path",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, {len(devices)} visible", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)()
    print(f"smoke wall time: {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
