"""Paper Fig. 8 + Sec. VII-D: relative off-diagonal Frobenius norm vs sweep
count across data modalities -- the offline study that justifies the fixed
50-sweep schedule.  Validates the paper's claims: standard datasets hit the
numerical noise floor within 10-15 sweeps; ill-conditioned (clustered
eigenvalue) data needs more, motivating the 50-sweep factor of safety.

A precision axis rides along (ISSUE 9): the measured relative Frobenius
error of the fp32 and bf16-streamed eigenvalue spectra against the fp64
subprocess oracle, reported next to the documented ``ERROR_BUDGETS``
ceiling each must stay under."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.schedule import (convergence_curve, make_ill_conditioned,
                                 sweeps_to_tolerance)
from .common import emit, synthetic_dataset


def precision_axis(fast: bool = True):
    """Measured error vs the fp64 oracle per precision policy.

    One small dataset (the oracle pays a subprocess + x64 solve per op);
    the budgets are ceilings, the emitted numbers the measured truth."""
    from repro.core import precision as prec
    from repro.kernels import ref as kref
    from repro.core.jacobi import jacobi_eigh

    x = synthetic_dataset(512, 24, 9)
    sweeps = 15 if fast else 30
    oracle_c = prec.run_fp64_oracle(x, "covariance")
    oracle_e = prec.run_fp64_oracle(x, "eigh", sweeps=sweeps)
    for precision in ("fp32", "bf16_fp32acc"):
        # the bf16-operand, fp32-accumulate Gram
        C = kref.covariance_gram(
            jnp.asarray(x).astype(prec.operand_dtype(precision)),
            acc_dtype=prec.acc_dtype(precision))
        err_c = prec.rel_frobenius(np.asarray(C), oracle_c["C"])
        res = jacobi_eigh(np.asarray(C), sweeps=sweeps)
        err_e = prec.rel_frobenius(np.asarray(res.eigenvalues),
                                   oracle_e["eigenvalues"])
        emit(f"fig8/precision/{precision}", "",
             f"cov_err={err_c:.2e}"
             f";budget={prec.ERROR_BUDGETS[precision]['covariance']:.0e}"
             f";eigh_err={err_e:.2e}"
             f";eigh_budget={prec.ERROR_BUDGETS[precision]['eigh']:.0e}")


def run(fast: bool = True):
    suites = {
        # shape-matched stand-ins for the paper's modalities
        "mnist-like_1797x64": synthetic_dataset(1797, 64, 1),
        "faces-like_400x128": synthetic_dataset(400, 128, 2),
        "biomed-like_4000x7": synthetic_dataset(4000, 7, 3),
        "text-like_2000x96": synthetic_dataset(2000, 96, 4,
                                               spectrum="flat"),
        "ill-conditioned_512x64": make_ill_conditioned(512, 64,
                                                       cluster_gap=1e-5),
    }
    floors = []
    for name, x in suites.items():
        curve = convergence_curve(x, sweeps=25 if fast else 50)
        k6 = sweeps_to_tolerance(curve, 1e-6)
        floors.append((name, k6))
        emit(f"fig8/{name}", "",
             f"sweeps_to_1e-6={k6};final={curve[-1]:.2e}")
    standard = [k for n, k in floors if not n.startswith("ill")]
    emit("fig8/claim_10_to_15_sweeps", "",
         f"max_standard={max(standard)};within_15={max(standard) <= 15}")
    ill = [k for n, k in floors if n.startswith("ill")]
    emit("fig8/claim_50_sweep_safety_margin", "",
         f"ill_conditioned={ill[0]};margin_ok={ill[0] <= 50}")
    precision_axis(fast=fast)
