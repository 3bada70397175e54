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
    n_dead: jnp.ndarray        # (B,) live columns zeroed by the cutoff


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
) -> BatchedEighResult:
    """Batched symmetric eigendecomposition over a shape bucket.

    Args:
      C: (B, nb, nb) zero-padded symmetric matrices sharing one bucket.
      n_active: (B,) true sizes (None = all full).  Rows/cols >= n_active[i]
        must be zero; they provably never mix (null-pivot guard).
      remaining args: as ``core.jacobi.jacobi_eigh``.
    """
    C = jnp.asarray(C)
    if C.ndim != 3:
        raise ValueError(f"expected (B, n, n) batch, got shape {C.shape}")
    n_active = _as_n_active(n_active, C.shape[0], C.shape[-1])

    def solve(c):
        return jacobi_eigh(c, sweeps=sweeps, pivot=pivot, rotation=rotation,
                           angle=angle, matmul_fn=matmul_fn, tol=tol,
                           sort=False)

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
    precision: str = "fp32",
    sweeps: int = DEFAULT_SWEEPS,
    **eigh_kwargs,
) -> BatchedSVDResult:
    """Batched thin SVD: the paper's Gram path, then one refinement pass.

    A: (B, mb, nb) zero-padded.  Every matmul (Gram, rotations,
    back-projection, refinement) shares the injected ``matmul_fn``
    datapath.

    Gram path: Jacobi on A^T A gives V.  Its eigenvalues carry an error of
    order eps * s_max^2, so sqrt of them resolves a singular value s only
    to eps * cond^2 relative, and the Gram's rounding leaves the small
    singular directions mixed.

    Refinement (one-sided, Demmel & Veselic 1992): B = A V, then sweeps
    of Jacobi on H = B^T B, each giving Q, V <- V Q, B <- B Q and H afresh
    from B; at the end s = the column norms of B and U = B / s, sorted.
    B's columns are nearly orthogonal, so H is scaled diagonally dominant
    and each of its entries is computed to rounding relative to
    sqrt(h_ii h_jj): Jacobi on it resolves small singular values to
    eps * cond relative, not eps * cond^2, and leaves U orthonormal.  How
    many sweeps that takes depends on how far the Gram path left the small
    directions mixed, which grows with cond^2: 3 at cond 147 (18846x1024),
    4 at cond 1000, 8 at cond 1e4 (512x256).  So the pass stops on what it
    measures: the scaled off-norm of H, max |h_ij| / sqrt(h_ii h_jj) over
    the columns above nb * eps_f32 * s_max, which is U's worst
    |U^T U - I|.  It sweeps while that reads above 2 * eps_f32, at most
    ``sweeps`` times: until U is orthonormal to rounding.  The stop sits
    in the gap between the last converging sweep and rounding, where the
    count of sweeps does not flip from input to input.  At 18846x1024 on
    a TPU v5e the reading before each sweep is 2.5e-4-7.6e-4,
    7.8e-5-3.4e-4, 1.3e-6-3.9e-5, then 3e-8-7e-8, rounding, in each of
    12 fits: three sweeps.  A stop at nb * eps_f32 = 1.2e-4 or at an eighth
    of it fell inside the spread of one sweep's reading, and the count
    flipped from fit to fit.  ``tol`` does not stop it: after a
    converged Gram path H is already diagonal relative to ||H||, so
    ``tol``'s norm-relative test would stop before the small directions
    are resolved.

    Dead columns: each column of B = A V is a length-nb dot product per
    entry, computed to an absolute error of about nb * eps * s_max; a
    column whose norm is below that carries no resolved direction, and
    dividing by it would serve rounding noise as a singular vector.  Such
    a column of U is zeroed exactly and its singular value served as 0
    (``n_dead`` counts them), so U S V^T is the truncated SVD at the
    resolved rank.  ``rcond`` defaults to nb * eps_f32: at 18846x1024 the
    cutoff is 1.2e-4 * s_max, 55x below the noise floor of the benchmark's
    data, while the old sqrt(nb * eps_f32) = 0.011 * s_max zeroed 992 of
    its 1024 columns.  Columns above the cutoff keep bit-identical values
    whatever ``rcond`` is: the mask only ever zeroes.
    """
    A = jnp.asarray(A)
    if A.ndim != 3:
        raise ValueError(f"expected (B, m, n) batch, got shape {A.shape}")
    B, mb, nb = A.shape
    n_rows = _as_n_active(n_rows, B, mb)
    n_cols = _as_n_active(n_cols, B, nb)
    mm = matmul_fn or functools.partial(
        jnp.matmul, precision=matmul_precision(precision))

    def gram(x):
        return jax.vmap(lambda a: mm(a.T, a))(x)

    eigh = functools.partial(jacobi_eigh_batched, n_active=n_cols,
                             matmul_fn=matmul_fn, **eigh_kwargs)
    with jax.named_scope("covariance"):
        G = gram(A)
    V = eigh(G, sweeps=sweeps).eigenvectors
    with jax.named_scope("back_projection"):
        Bv = jax.vmap(mm)(A, V)
    eps32 = float(np.finfo(np.float32).eps)
    eps_nb = nb * eps32
    not_diag = ~jnp.eye(nb, dtype=bool)

    def unresolved(H):
        """max |h_ij| / sqrt(h_ii h_jj) over the columns above the
        resolution cutoff, the worst of the batch."""
        d = jnp.sqrt(jnp.maximum(jnp.diagonal(H, axis1=-2, axis2=-1), 0.0))
        kept = d > eps_nb * jnp.max(d, axis=-1, keepdims=True)
        pairs = kept[:, :, None] & kept[:, None, :] & not_diag
        scaled = jnp.abs(H) / jnp.maximum(d[:, :, None] * d[:, None, :],
                                          1e-30)
        return jnp.max(jnp.where(pairs, scaled, 0.0))

    def refining(state):
        k, _, _, H = state
        return (k < sweeps) & (unresolved(H) > 2 * eps32)

    def refine_sweep(state):
        k, Bv, V, H = state
        Q = eigh(H, sweeps=1, tol=None, sort=False).eigenvectors
        Bv = jax.vmap(mm)(Bv, Q)
        return k + 1, Bv, jax.vmap(mm)(V, Q), gram(Bv)

    with jax.named_scope("svd_refine"):
        _, Bv, V, _ = jax.lax.while_loop(
            refining, refine_sweep, (jnp.int32(0), Bv, V, gram(Bv)))
        s = jnp.sqrt(jnp.sum(Bv * Bv, axis=1))
        # padded columns of B are exactly zero; -inf sends them last
        live = jnp.arange(nb)[None, :] < n_cols[:, None]
        order = jnp.argsort(-jnp.where(live, s, -jnp.inf), axis=-1)
        s = jnp.take_along_axis(s, order, axis=-1)
        V = jnp.take_along_axis(V, order[:, None, :], axis=-1)
        Bv = jnp.take_along_axis(Bv, order[:, None, :], axis=-1)
        if rcond is None:
            rcond = eps_nb
        # relative cutoff per problem; an all-zero problem (s_max == 0) has
        # no live column at all and U comes out exactly zero
        cutoff = rcond * s[:, :1]
        kept = s > cutoff
        n_dead = jnp.sum(live & ~kept, axis=-1).astype(jnp.int32)
        s = jnp.where(kept, s, 0.0)
        U = jnp.where(kept[:, None, :],
                      Bv / jnp.maximum(s, 1e-30)[:, None, :], 0.0)
        # placement-stable signs, as the Gram path's eigenvectors have
        sign = jnp.where(jnp.take_along_axis(
            V, jnp.argmax(jnp.abs(V), axis=1)[:, None, :], axis=1) < 0,
            -1.0, 1.0).astype(V.dtype)
        V = V * sign
        U = U * sign
    Vt = jnp.swapaxes(V, -1, -2)
    return BatchedSVDResult(U, s, Vt, n_rows, n_cols, n_dead)


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
        C = jax.vmap(lambda x: mm(x.T, x))(Xs)
    res = jacobi_eigh_batched(
        C, n_active=n_cols, sweeps=config.sweeps, pivot=config.pivot,
        rotation=config.rotation, angle=config.angle,
        matmul_fn=config.matmul_fn(), tol=config.tol)
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
              matmul_fn=config.matmul_fn())

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
