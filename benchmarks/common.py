"""Shared benchmark utilities: timing, the paper's dataset suite
(Table IV), CSV emission, machine-readable JSON trajectory files."""
from __future__ import annotations

import datetime
import json
import pathlib
import subprocess
import time
from typing import Callable, Dict, Tuple

import numpy as np
import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent

# Paper Table IV: (records M, features N) per benchmark dataset.  No
# network access in this container, so measured runs use synthetic
# stand-ins with the same shapes (spectra controlled where it matters --
# fig8 uses structured covariances).
DATASETS: Dict[str, Tuple[int, int]] = {
    "mnist-8x8": (1797, 64),
    "mnist-28x28": (70000, 784),
    "cifar-10": (60000, 3072),
    "olivetti": (400, 4096),
    "breast-cancer": (45312, 7),
    "20-newsgroups": (18846, 1024),
}

# paper headline GPU comparison numbers (A6000; Sec. VII-B/C) for reference
PAPER_CLAIMS = {
    "cifar10_total_speedup_vs_a6000": 3.87,
    "svd_speedup_vs_a6000": 22.75,
    "cifar10_energy_reduction_vs_a6000": 42.14,
}


def synthetic_dataset(m: int, n: int, seed: int = 0,
                      spectrum: str = "decay") -> np.ndarray:
    rng = np.random.default_rng(seed)
    k = min(n, 32)
    if spectrum == "decay":
        base = rng.standard_normal((m, k)) * np.geomspace(1, 0.05, k)
        mix = rng.standard_normal((k, n)) / np.sqrt(k)
        x = base @ mix + 0.05 * rng.standard_normal((m, n))
    else:
        x = rng.standard_normal((m, n))
    return x.astype(np.float32)


def refuse_on_tpu(name: str) -> None:
    """Refuse a sweep that starts device-using child processes on a TPU.

    A chip belongs to one process at a time.  Once this process has
    touched JAX on a TPU it holds the chip, and a child that needs a
    device then fails or hangs.  The sweeps that call this are CPU-regime
    measurements (forced host-device counts, fresh-process replicas); they
    run under ``JAX_PLATFORMS=cpu`` and are not chip benchmarks.
    """
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{name} starts child processes that need a device, but this "
            "process already holds the TPU and a chip serves one process "
            "at a time; it is a CPU-regime sweep: run it with "
            "JAX_PLATFORMS=cpu")


def time_call(fn: Callable, *args, reps: int = 3, warmup: int = 1) -> float:
    """Median wall-time of a jitted call in microseconds."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e6)


def emit(name: str, us_per_call, derived=""):
    print(f"{name},{us_per_call},{derived}", flush=True)


def provenance() -> Dict:
    """Where/when/what produced a benchmark number: git SHA, timestamp,
    jax version, device backend and count.  Best-effort (a checkout-less
    run stamps ``git_sha: null``) -- the numbers must still emit."""
    try:
        r = subprocess.run(["git", "rev-parse", "HEAD"], cwd=REPO_ROOT,
                           capture_output=True, text=True, timeout=10)
        sha = r.stdout.strip() if r.returncode == 0 else None
    except OSError:
        sha = None
    return {
        "git_sha": sha,
        "emitted_at": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
    }


def emit_json(name: str, payload: Dict) -> pathlib.Path:
    """Write a machine-readable result file ``BENCH_<name>.json`` at the
    repo root so the perf trajectory accumulates across PRs.  ``payload``
    should be a dict of plain scalars/lists (rows keyed like the CSV).

    Every file carries a ``provenance`` block (git SHA, emission time, jax
    version, device fleet); ``scripts/check_bench.py`` ignores it when
    diffing rows, so provenance churn never reads as a perf change."""
    path = REPO_ROOT / f"BENCH_{name}.json"
    doc = {"benchmark": name, "timestamp_s": time.time(),
           "provenance": provenance(), **payload}
    path.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    return path
