"""End-to-end PCA pipeline on the MANOJAVAM engine (paper Alg. 1).

standardize -> C = X^T X (block-streamed MM-Engine) -> Jacobi eigh
(DLE pivoting + CORDIC rotations, fixed sweep schedule) -> EVCR/CVCR top-k
selection -> projection O = X V_k (MM-Engine again).

``PCAConfig(T, S)`` mirrors the hardware's two tunable parameters: T is the
tile size (Pallas block edge / streaming block), S the parallelism index
(grid parallelism on-chip; data-axis shards across a mesh).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .covariance import blocked_covariance, covariance, distributed_covariance, standardize
from .jacobi import DEFAULT_SWEEPS, EighResult, jacobi_eigh
from .precision import matmul_precision
from .schedule import SweepSchedule


@dataclasses.dataclass(frozen=True)
class PCAConfig:
    T: int = 128                  # tile size (paper T; MXU-aligned default)
    S: int = 8                    # parallelism index (paper S)
    sweeps: int = DEFAULT_SWEEPS  # fixed deterministic schedule
    tol: Optional[float] = None   # software early-exit (None = hardware mode)
    pivot: str = "parallel"       # "paper" | "cyclic" | "parallel"
    rotation: str = "rowcol"      # "matmul" = unified MM-Engine datapath
    angle: str = "rutishauser"    # "cordic" = paper-faithful datapath
    standardize: bool = True
    # kernel backend for the matmul datapath: None = plain XLA jnp.matmul;
    # "pallas" / "interpret" / "ref" route every matmul through the
    # mm_engine op in the backend registry (repro.backends).  The old
    # boolean ``use_pallas=True`` is spelled ``backend="pallas"`` now.
    backend: Optional[str] = None
    # mixed-precision policy for the covariance/Gram leg ("fp32" |
    # "bf16_fp32acc" | "fp64"; see repro.core.precision).  Rotations,
    # angles and back-projections always stay fp32.
    precision: str = "fp32"
    # route the hot path through the fused one-launch kernels (covariance
    # + jacobi_sweep registry ops); bitwise-identical to the unfused path
    # at fp32
    fused: bool = False

    @property
    def use_pallas(self) -> bool:
        return self.backend == "pallas"

    def matmul_fn(self) -> Optional[Callable]:
        if self.backend is None:
            return None
        from repro.kernels import ops as kops
        backend = self.backend
        return lambda a, b: kops.mm_engine_matmul(a, b, block=self.T,
                                                  backend=backend)


PAPER_CONFIG_ARTIX7 = PCAConfig(T=4, S=8)
PAPER_CONFIG_VUS = PCAConfig(T=16, S=32)


class PCAResult(NamedTuple):
    components: jnp.ndarray    # (d, d) eigenvectors, columns, descending
    eigenvalues: jnp.ndarray   # (d,) descending
    mean: jnp.ndarray
    scale: jnp.ndarray
    evcr: jnp.ndarray          # explained variance contribution ratio (eq. 3)
    cvcr: jnp.ndarray          # cumulative variance contribution ratio (eq. 4)
    off_norm: jnp.ndarray      # final relative off-diagonal norm


def evcr_cvcr(eigenvalues):
    lam = jnp.maximum(eigenvalues, 0.0)
    total = jnp.maximum(jnp.sum(lam), 1e-30)
    evcr = lam / total
    cvcr = jnp.cumsum(evcr)
    return evcr, cvcr


def select_k(cvcr, variance_target: float = 0.95) -> jnp.ndarray:
    """Smallest k whose CVCR reaches the target (scree-plot companion)."""
    return jnp.minimum(jnp.sum(cvcr < variance_target) + 1, cvcr.shape[0])


def fit(X, config: PCAConfig = PCAConfig()) -> PCAResult:
    X = jnp.asarray(X)
    if config.standardize:
        Xs, mean, scale = standardize(X)
    else:
        Xs = X
        mean = jnp.zeros((X.shape[1],), X.dtype)
        scale = jnp.ones((X.shape[1],), X.dtype)
    mm = config.matmul_fn()
    C = blocked_covariance(Xs, block_m=config.T, matmul_fn=mm,
                           fused=config.fused, precision=config.precision,
                           backend=config.backend)
    res: EighResult = jacobi_eigh(
        C,
        sweeps=config.sweeps,
        tol=config.tol,
        pivot=config.pivot,
        rotation=config.rotation,
        angle=config.angle,
        matmul_fn=mm,
        fused=config.fused,
        fused_backend=config.backend,
    )
    evcr, cvcr = evcr_cvcr(res.eigenvalues)
    return PCAResult(res.eigenvectors, res.eigenvalues, mean, scale, evcr,
                     cvcr, res.off_norm)


def transform(X, result: PCAResult, k: int, config: PCAConfig = PCAConfig()):
    """Project onto the top-k subspace: O = X_std V_k (paper eq. 5)."""
    Xs = (jnp.asarray(X) - result.mean) / result.scale
    mm = config.matmul_fn() or functools.partial(
        jnp.matmul, precision=matmul_precision(config.precision))
    return mm(Xs, result.components[:, :k])


def fit_transform(X, k: int, config: PCAConfig = PCAConfig()):
    res = fit(X, config)
    return transform(X, res, k, config), res


def fit_distributed(X, mesh, config: PCAConfig = PCAConfig(),
                    data_axis: str = "data") -> PCAResult:
    """Data-parallel PCA: covariance block-streamed across the mesh
    (each shard = one 'row-block group' of the paper's schedule), Jacobi on
    the replicated d x d covariance."""
    X = jnp.asarray(X)
    if config.standardize:
        Xs, mean, scale = standardize(X)
    else:
        Xs, mean, scale = X, jnp.zeros((X.shape[1],)), jnp.ones((X.shape[1],))
    C = distributed_covariance(Xs, mesh, data_axis=data_axis,
                               block_m=config.T)
    res = jacobi_eigh(C, sweeps=config.sweeps, tol=config.tol,
                      pivot=config.pivot, rotation=config.rotation,
                      angle=config.angle)
    evcr, cvcr = evcr_cvcr(res.eigenvalues)
    return PCAResult(res.eigenvectors, res.eigenvalues, mean, scale, evcr,
                     cvcr, res.off_norm)
