"""Batched PCA/SVD solvers: the paper's S-array axis realized with vmap.

MANOJAVAM(T, S) instantiates S independent TxT systolic arrays; here the S
axis becomes a leading batch dimension over ``vmap``-ed Jacobi solves, so one
compiled executable retires S independent problems per dispatch.  All three
pivot strategies ("parallel" / "cyclic" / "paper") and both rotation modes
("rowcol" / "matmul") vmap cleanly: the sweep machinery is pure lax
control flow and the DLE argmax batches element-wise.

Backend dispatch: every matmul in these solvers flows through the injected
``matmul_fn`` (or the ``config.backend`` name on ``pca_fit_batched``), which
``PCAServer`` resolves per bucket via its ``backend_router`` -- so one server
can retire a large bucket on the Pallas MM-Engine while a small bucket stays
on plain XLA, each under its own backend-qualified cached executable.

Bucket-padding contract: inputs arrive zero-padded into a shared bucket
(``serving.batching``) with per-problem true sizes ``n_active``.  The
zero-pivot guard in ``core.jacobi`` makes every rotation that touches a
padded coordinate the *exact* identity, so the padded block of C stays
exactly zero and eigenvector columns of padded coordinates remain exact
basis vectors e_j at their original positions.  That invariant is what lets
``_masked_sort`` recover the embedded problem's descending eigenpairs with a
pure O(n log n) reorder -- no per-problem dynamic shapes anywhere.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.jacobi import DEFAULT_SWEEPS, jacobi_eigh
from repro.core.pca import PCAConfig, evcr_cvcr
from repro.core.precision import matmul_precision


class BatchedEighResult(NamedTuple):
    eigenvalues: jnp.ndarray   # (B, nb) descending per problem, padded tail 0
    eigenvectors: jnp.ndarray  # (B, nb, nb) columns pair with eigenvalues
    off_norm: jnp.ndarray      # (B,) final relative off-diagonal norms
    n_active: jnp.ndarray      # (B,) true problem sizes


class BatchedSVDResult(NamedTuple):
    U: jnp.ndarray             # (B, mb, nb)
    S: jnp.ndarray             # (B, nb) descending, padded tail 0
    Vt: jnp.ndarray            # (B, nb, nb)
    n_rows: jnp.ndarray        # (B,)
    n_cols: jnp.ndarray        # (B,)


class BatchedPCAResult(NamedTuple):
    components: jnp.ndarray    # (B, nb, nb) eigenvector columns, descending
    eigenvalues: jnp.ndarray   # (B, nb)
    mean: jnp.ndarray          # (B, nb)
    scale: jnp.ndarray         # (B, nb)
    evcr: jnp.ndarray          # (B, nb)
    cvcr: jnp.ndarray          # (B, nb)
    off_norm: jnp.ndarray      # (B,)
    n_rows: jnp.ndarray        # (B,)
    n_cols: jnp.ndarray        # (B,)


def _as_n_active(n_active, batch: int, full: int):
    if n_active is None:
        return jnp.full((batch,), full, jnp.int32)
    return jnp.asarray(n_active, jnp.int32)


def _masked_sort(w, V, n_active):
    """Descending sort of the *live* eigenpairs; padded pairs go last.

    Padded coordinates hold exact zero eigenvalues, which would interleave
    with a mixed-sign live spectrum under a plain sort.  Scoring padded
    slots at -inf pushes them behind every live eigenvalue, so slots
    [0, n_active) are exactly the embedded problem's descending eigenpairs.
    """
    nb = w.shape[-1]
    ids = jnp.arange(nb)
    live = ids < n_active
    score = jnp.where(live, w, -jnp.inf)
    order = jnp.argsort(-score)
    w = jnp.where(live, w[order], jnp.zeros_like(w))
    V = V[:, order]
    return w, V


def _canonical_sign(V):
    """Flip each eigenvector column so its largest-magnitude entry is
    positive.

    An eigenvector is defined up to sign, and which sign a Jacobi solve
    lands on depends on rounding: the same problem compiled for one device
    and for a sharded mesh can come back with opposite columns.  Fixing
    the sign makes served results placement-stable (equal up to rounding,
    not up to sign).  Padded columns are exact basis vectors e_j and keep
    their +1.
    """
    pivot = jnp.take_along_axis(
        V, jnp.argmax(jnp.abs(V), axis=0)[None, :], axis=0)[0]
    return V * jnp.where(pivot < 0, -1.0, 1.0).astype(V.dtype)[None, :]


def jacobi_eigh_batched(
    C,
    n_active=None,
    sweeps: int = DEFAULT_SWEEPS,
    pivot: str = "parallel",
    rotation: str = "rowcol",
    angle: str = "rutishauser",
    matmul_fn: Optional[Callable] = None,
    tol: Optional[float] = None,
    sort: bool = True,
    fused: bool = False,
    fused_backend: Optional[str] = None,
) -> BatchedEighResult:
    """Batched symmetric eigendecomposition over a shape bucket.

    Args:
      C: (B, nb, nb) zero-padded symmetric matrices sharing one bucket.
      n_active: (B,) true sizes (None = all full).  Rows/cols >= n_active[i]
        must be zero; they provably never mix (null-pivot guard).
      remaining args: as ``core.jacobi.jacobi_eigh`` (``fused`` vmaps the
        one-launch-per-round ``jacobi_sweep`` kernel across the batch).
    """
    C = jnp.asarray(C)
    if C.ndim != 3:
        raise ValueError(f"expected (B, n, n) batch, got shape {C.shape}")
    n_active = _as_n_active(n_active, C.shape[0], C.shape[-1])

    def solve(c):
        return jacobi_eigh(c, sweeps=sweeps, pivot=pivot, rotation=rotation,
                           angle=angle, matmul_fn=matmul_fn, tol=tol,
                           sort=False, fused=fused,
                           fused_backend=fused_backend)

    res = jax.vmap(solve)(C)
    w, V = res.eigenvalues, res.eigenvectors
    if sort:
        w, V = jax.vmap(_masked_sort)(w, V, n_active)
    V = jax.vmap(_canonical_sign)(V)
    return BatchedEighResult(w, V, res.off_norm, n_active)


def jacobi_svd_batched(
    A,
    n_rows=None,
    n_cols=None,
    matmul_fn: Optional[Callable] = None,
    rcond: Optional[float] = None,
    fused: bool = False,
    fused_backend: Optional[str] = None,
    precision: str = "fp32",
    **eigh_kwargs,
) -> BatchedSVDResult:
    """Batched thin SVD via the Gram-matrix path (paper PCA datapath).

    A: (B, mb, nb) zero-padded.  All three matmuls (Gram, rotations, the
    U = A V back-projection) share the injected ``matmul_fn`` datapath.

    Rank deficiency: the back-projection U = A V / s divides by singular
    values the Gram path cannot resolve below ~sqrt(eps) * s_max -- for a
    rank-deficient *live* input (s ~ 0 inside n_cols) that division
    amplifies rounding noise in A V into garbage U columns.  Columns whose
    singular value falls below ``rcond * s_max`` are therefore zeroed
    exactly (their live counterparts keep bit-identical values: the mask
    only ever turns noise into zeros).  ``rcond`` defaults to
    sqrt(nb * eps_f32), a few times the Gram path's own noise floor.
    """
    A = jnp.asarray(A)
    if A.ndim != 3:
        raise ValueError(f"expected (B, m, n) batch, got shape {A.shape}")
    B, mb, nb = A.shape
    n_rows = _as_n_active(n_rows, B, mb)
    n_cols = _as_n_active(n_cols, B, nb)
    mm = matmul_fn or functools.partial(
        jnp.matmul, precision=matmul_precision(precision))
    with jax.named_scope("covariance"):
        if fused:
            from repro.kernels import ops as kops
            gram = jax.vmap(lambda a: kops.covariance(
                a, precision=precision, backend=fused_backend))(A)
        else:
            gram = jax.vmap(lambda a: mm(a.T, a))(A)
    res = jacobi_eigh_batched(gram, n_active=n_cols, matmul_fn=matmul_fn,
                              fused=fused, fused_backend=fused_backend,
                              **eigh_kwargs)
    s = jnp.sqrt(jnp.maximum(res.eigenvalues, 0.0))
    safe = jnp.maximum(s, 1e-30)
    if rcond is None:
        rcond = float(np.sqrt(nb * np.finfo(np.float32).eps))
    # relative cutoff per problem; an all-zero problem (s_max == 0) has no
    # live column at all and U comes out exactly zero
    cutoff = rcond * jnp.max(s, axis=-1, keepdims=True)
    live = s > cutoff
    U = jnp.where(live[:, None, :],
                  jax.vmap(mm)(A, res.eigenvectors) / safe[:, None, :],
                  0.0)
    Vt = jnp.swapaxes(res.eigenvectors, -1, -2)
    return BatchedSVDResult(U, s, Vt, n_rows, n_cols)


def _masked_standardize(X, m, d, eps: float = 1e-8):
    """Per-feature zero-mean / unit-variance over the live (m, d) block.

    Padded rows must not bias the moments and padded entries must stay
    exactly zero afterwards (X - mean is nonzero on padded rows), so both
    masks are applied explicitly.  Matches ``core.covariance.standardize``
    (ddof=0) on an exact-fit matrix.
    """
    mb, db = X.shape
    rmask = (jnp.arange(mb) < m)[:, None].astype(X.dtype)
    cmask = (jnp.arange(db) < d).astype(X.dtype)
    cnt = jnp.maximum(m, 1).astype(X.dtype)
    mean = jnp.sum(X * rmask, axis=0) / cnt
    diff = (X - mean[None, :]) * rmask
    var = jnp.sum(diff * diff, axis=0) / cnt
    std = jnp.sqrt(var)
    std = jnp.where(std < eps, jnp.ones_like(std), std)
    return (diff / std[None, :]) * cmask[None, :], mean * cmask, std


def pca_fit_batched(
    X,
    n_rows=None,
    n_cols=None,
    config: PCAConfig = PCAConfig(),
) -> BatchedPCAResult:
    """Batched PCA fit (paper Alg. 1 across the S axis).

    X: (B, mb, db) zero-padded data matrices sharing one bucket; per-problem
    true shapes in (n_rows, n_cols).  EVCR/CVCR are computed over the live
    spectrum only (padded eigenvalues are exactly zero, so they contribute
    nothing to the totals).
    """
    X = jnp.asarray(X)
    if X.ndim != 3:
        raise ValueError(f"expected (B, m, d) batch, got shape {X.shape}")
    B, mb, db = X.shape
    n_rows = _as_n_active(n_rows, B, mb)
    n_cols = _as_n_active(n_cols, B, db)
    mm = config.matmul_fn() or functools.partial(
        jnp.matmul, precision=matmul_precision(config.precision))

    if config.standardize:
        Xs, mean, scale = jax.vmap(_masked_standardize)(X, n_rows, n_cols)
    else:
        Xs = X
        mean = jnp.zeros((B, db), X.dtype)
        scale = jnp.ones((B, db), X.dtype)
    with jax.named_scope("covariance"):
        if config.fused:
            from repro.kernels import ops as kops
            C = jax.vmap(lambda x: kops.covariance(
                x, precision=config.precision, backend=config.backend))(Xs)
        else:
            C = jax.vmap(lambda x: mm(x.T, x))(Xs)
    res = jacobi_eigh_batched(
        C, n_active=n_cols, sweeps=config.sweeps, pivot=config.pivot,
        rotation=config.rotation, angle=config.angle,
        matmul_fn=config.matmul_fn(), tol=config.tol,
        fused=config.fused, fused_backend=config.backend)
    evcr, cvcr = jax.vmap(evcr_cvcr)(res.eigenvalues)
    return BatchedPCAResult(res.eigenvectors, res.eigenvalues, mean, scale,
                            evcr, cvcr, res.off_norm, n_rows, n_cols)


def build_solver_fn(op: str, config: PCAConfig) -> Callable:
    """The un-jitted batched solver for one op under one config.

    Uniform signature ``(batch, n_rows, n_cols) -> result`` across all three
    ops (eigh ignores the redundant column counts: the two n_active axes of a
    square bucket coincide), so the serving executors can jit it with
    whatever device placement they own -- plain ``jax.jit`` on the default
    executor, batch-axis ``NamedSharding``s on the mesh executor.  The
    function is named ``<op>_solve``, so its executable reads
    ``jit_<op>_solve`` in a device trace.
    """
    kw = dict(sweeps=config.sweeps, pivot=config.pivot,
              rotation=config.rotation, angle=config.angle, tol=config.tol,
              matmul_fn=config.matmul_fn(),
              fused=config.fused, fused_backend=config.backend)
    def eigh_solve(C, nr, nc):
        return jacobi_eigh_batched(C, nr, **kw)

    def svd_solve(A, nr, nc):
        return jacobi_svd_batched(A, nr, nc, precision=config.precision,
                                  **kw)

    def pca_solve(X, nr, nc):
        return pca_fit_batched(X, nr, nc, config=config)

    solvers = {"eigh": eigh_solve, "svd": svd_solve, "pca": pca_solve}
    if op not in solvers:
        raise ValueError(f"unknown op {op!r}")
    return solvers[op]


def pca_transform_batched(X, result: BatchedPCAResult, k: int,
                          matmul_fn: Optional[Callable] = None):
    """Batched top-k projection O = X_std V_k (paper eq. 5)."""
    mm = matmul_fn or jnp.matmul
    X = jnp.asarray(X)
    scale = jnp.where(result.scale == 0.0, 1.0, result.scale)
    rmask = (jnp.arange(X.shape[1])[None, :]
             < result.n_rows[:, None]).astype(X.dtype)
    Xs = (X - result.mean[:, None, :]) / scale[:, None, :] * rmask[:, :, None]
    return jax.vmap(lambda x, v: mm(x, v[:, :k]))(Xs, result.components)
