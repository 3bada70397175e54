"""Mixed-precision policy for the PCA hot path.

MANOJAVAM runs fixed-point datapaths sized to the workload; the TPU analog
is reduced-precision *operand streaming* with guarded accumulation (the
standard throughput lever in the related FPGA-PCA literature -- Martel et
al.'s hyperspectral PCA, Burrello et al.'s embedded PCA).  Three policies:

  ``fp32``          fp32 operands, fp32 accumulation, HIGHEST-precision
                    matmuls.  The default.
  ``bf16_fp32acc``  bf16 operand streaming into fp32 accumulators for the
                    covariance/Gram products (half the HBM bytes on the
                    bandwidth-bound leg).  Jacobi rotations, angles and the
                    U = A V back-projection stay fp32: rotation numerics
                    are what convergence rests on, and they are
                    compute-light -- all the bandwidth is in the Gram pass.
  ``fp64``          the reference lane.  Requires an ``JAX_ENABLE_X64=1``
                    process; error budgets are measured against it via the
                    subprocess idiom (``run_fp64_oracle``), so the serving
                    process never has to flip the global x64 switch.

``ERROR_BUDGETS`` documents the relative-Frobenius-error ceiling of each
(policy, op) against the fp64 oracle.  Measured typical errors on the
benchmark suites sit 4-10x below these ceilings (bf16 covariance ~1e-3 to
4e-3; fp32 ~1e-7); ``tests/test_precision.py`` enforces them and
``benchmarks/fig8_frobenius.py`` reports the measured values per release.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from typing import Dict

import numpy as np
import jax
import jax.numpy as jnp

PRECISIONS = ("fp32", "bf16_fp32acc", "fp64")

# relative Frobenius error vs the fp64 oracle, per (precision, op).
# "covariance" is ||C - C64|| / ||C64||; "eigh" is the eigenvalue-vector
# error; "svd" the singular-value-vector error (eigenvectors/singular
# vectors are compared through the subspaces they span, not budgeted here).
ERROR_BUDGETS: Dict[str, Dict[str, float]] = {
    "fp32": {"covariance": 1e-5, "eigh": 1e-4, "svd": 1e-4},
    "bf16_fp32acc": {"covariance": 2e-2, "eigh": 2e-2, "svd": 2e-2},
    "fp64": {"covariance": 0.0, "eigh": 0.0, "svd": 0.0},
}


def validate(precision: str) -> str:
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; expected one of {PRECISIONS}")
    return precision


def operand_dtype(precision: str):
    """The dtype operands *stream* at (HBM-side) under a policy."""
    validate(precision)
    if precision == "bf16_fp32acc":
        return jnp.bfloat16
    if precision == "fp64":
        return jnp.float64
    return jnp.float32


def matmul_precision(precision: str):
    """The XLA dot precision of the policy's plain (non-kernel) matmuls.

    A TPU runs an fp32 dot at DEFAULT precision as one bf16 pass: on a
    v5e that put a 176x44 SVD's singular values 1.5e-4 from float64,
    over the fp32 budget.  ``fp32`` (and ``fp64``) therefore ask for
    HIGHEST; ``bf16_fp32acc`` keeps DEFAULT -- it is the reduced-precision
    lane.  On a CPU the setting changes nothing.
    """
    validate(precision)
    if precision == "bf16_fp32acc":
        return None
    return jax.lax.Precision.HIGHEST


def acc_dtype(precision: str):
    """The accumulator dtype -- never narrower than fp32."""
    validate(precision)
    return jnp.float64 if precision == "fp64" else jnp.float32


def supports_x64() -> bool:
    """Whether this process can hold a real float64 (x64 enabled)."""
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")     # probe, not a request: the
        return jnp.asarray(0.0, jnp.float64).dtype == jnp.float64  # truncation IS the answer


_ORACLE_SCRIPT = r"""
import json, sys
import numpy as np
import jax.numpy as jnp
from repro.core.covariance import covariance, standardize
from repro.core.jacobi import jacobi_eigh, jacobi_svd

inp, out = sys.argv[1], sys.argv[2]
data = np.load(inp)
X = jnp.asarray(data["X"], jnp.float64)
op = str(data["op"])
res = {"x64": bool(jnp.asarray(0.0, jnp.float64).dtype == jnp.float64)}
if op == "covariance":
    C = covariance(X)
    np.savez(out, C=np.asarray(C))
elif op == "eigh":
    C = covariance(X)
    r = jacobi_eigh(C, sweeps=int(data["sweeps"]))
    np.savez(out, eigenvalues=np.asarray(r.eigenvalues),
             eigenvectors=np.asarray(r.eigenvectors))
elif op == "svd":
    U, s, Vt = jacobi_svd(X, sweeps=int(data["sweeps"]))
    np.savez(out, U=np.asarray(U), S=np.asarray(s), Vt=np.asarray(Vt))
else:
    raise SystemExit(f"unknown op {op}")
print(json.dumps(res))
"""


def run_fp64_oracle(X: np.ndarray, op: str, sweeps: int = 50,
                    timeout: float = 600.0) -> Dict[str, np.ndarray]:
    """Compute the fp64 reference for ``op`` in a ``JAX_ENABLE_X64=1``
    subprocess (SNIPPETS snippet-1 idiom: the x64 switch is global and
    read at jax import, so the serving process cannot flip it for one
    call -- a child process can).

    Returns the result arrays as float64 numpy.  Raises on any subprocess
    failure: a missing oracle must fail the caller loudly, not silently
    compare against garbage.
    """
    if op not in ("covariance", "eigh", "svd"):
        raise ValueError(f"unknown oracle op {op!r}")
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ)
    env["JAX_ENABLE_X64"] = "1"
    # the oracle is a host computation: pinned to the CPU, it never
    # competes with the parent for an accelerator the parent holds
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = src_root + os.pathsep + env.get("PYTHONPATH", "")
    with tempfile.TemporaryDirectory() as td:
        inp = os.path.join(td, "in.npz")
        out = os.path.join(td, "out.npz")
        np.savez(inp, X=np.asarray(X, np.float64), op=op, sweeps=sweeps)
        proc = subprocess.run(
            [sys.executable, "-c", _ORACLE_SCRIPT, inp, out],
            env=env, capture_output=True, text=True, timeout=timeout)
        if proc.returncode != 0:
            raise RuntimeError(
                f"fp64 oracle subprocess failed:\n{proc.stderr[-2000:]}")
        header = json.loads(proc.stdout.strip().splitlines()[-1])
        if not header.get("x64"):
            raise RuntimeError("fp64 oracle subprocess did not get x64 "
                               "dtypes (JAX_ENABLE_X64 ignored?)")
        with np.load(out) as z:
            return {k: np.asarray(z[k]) for k in z.files}


def rel_frobenius(a: np.ndarray, b: np.ndarray) -> float:
    """||a - b||_F / ||b||_F (b is the reference)."""
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    denom = max(float(np.linalg.norm(b)), 1e-30)
    return float(np.linalg.norm(a - b)) / denom
