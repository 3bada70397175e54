"""Jacobi eigendecomposition engine (the paper's Jacobian Unit + MM-Engine).

Three pivot strategies:

  * ``"paper"``    -- classical max-pivot Jacobi: per rotation the DLE scans
                      for the largest |off-diagonal| element (Sec. V/VI-C).
                      Latency-optimal on the FPGA, strictly serial on TPU;
                      kept as the faithful validation baseline.
  * ``"cyclic"``   -- row-cyclic sweeps (the paper's Cyclic Jacobi Method,
                      Sec. III): all n(n-1)/2 pivots in fixed order.
  * ``"parallel"`` -- round-robin tournament ordering (Brent-Luk [34], cited
                      by the paper as its algorithmic foundation): n/2
                      disjoint pivots per step, n-1 steps per sweep.  This is
                      the TPU-native schedule.

Two rotation-application modes:

  * ``"matmul"`` -- build the (block-)rotation matrix J and update
                    C <- J^T C J, V <- V J through the matmul engine: the
                    paper's unified-datapath mode (rotations re-use the
                    MM-Engine, Sec. VI-A).
  * ``"rowcol"`` -- update only the touched row/column pairs (O(n^2) per
                    parallel step instead of O(n^3)); beyond-paper fast path.

Convergence: fixed deterministic sweep count (default 50, the paper's safety
schedule) with optional software early-exit tolerance.

Name scopes ``jacobi_sweep``, ``jacobi_round`` and ``jacobi_rotation``
mark one sweep, one pivot round and the rotation apply in the ``op_name``
of the compiled operations, so a device trace can be reduced by stage.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .cordic import ANGLE_MODES
from . import dle as dle_mod

DEFAULT_SWEEPS = 50  # paper Sec. VII-D: fixed 50-sweep factor-of-safety


class EighResult(NamedTuple):
    eigenvalues: jnp.ndarray    # (n,) descending
    eigenvectors: jnp.ndarray   # (n, n), column i pairs with eigenvalue i
    off_norm: jnp.ndarray       # final relative off-diagonal Frobenius norm
    history: Optional[jnp.ndarray]  # (sweeps+1,) relative off-norm per sweep


def offdiag_frobenius(C):
    """E_off(A) = sqrt(sum_{i != j} a_ij^2)  (paper eq. 11)."""
    n = C.shape[0]
    off = C * (1.0 - jnp.eye(n, dtype=C.dtype))
    return jnp.sqrt(jnp.sum(off * off))


def relative_offdiag(C):
    return offdiag_frobenius(C) / jnp.maximum(
        jnp.sqrt(jnp.sum(C * C)), jnp.asarray(1e-30, C.dtype)
    )


@functools.lru_cache(maxsize=64)
def round_robin_rounds(n: int) -> np.ndarray:
    """(n-1, n//2, 2) disjoint pivot pairs per round (circle method).

    ``n`` must be even; every unordered pair appears exactly once per sweep.
    """
    assert n % 2 == 0, "round-robin ordering needs even n (pad first)"
    players = list(range(n))
    rounds = []
    for _ in range(n - 1):
        pairs = []
        for i in range(n // 2):
            a, b = players[i], players[n - 1 - i]
            pairs.append((min(a, b), max(a, b)))
        rounds.append(pairs)
        players = [players[0]] + [players[-1]] + players[1:-1]
    return np.asarray(rounds, dtype=np.int32)


@functools.lru_cache(maxsize=64)
def cyclic_pairs(n: int) -> np.ndarray:
    """(n(n-1)/2, 1, 2) row-cyclic pivot order."""
    pairs = [(p, q) for p in range(n - 1) for q in range(p + 1, n)]
    return np.asarray(pairs, dtype=np.int32).reshape(-1, 1, 2)


def _build_rotation(n: int, p, q, c, s, dtype):
    """Dense block-rotation J (identity + embedded 2x2s, paper eq. 7).

    Degenerate pivots with p == q (the DLE's answer on an already-diagonal
    matrix) carry c = 1, s = 0 from ``_null_pivot_guard``; route the
    off-diagonal writes through ``where`` so they land on the diagonal as c
    instead of zeroing it.
    """
    J = jnp.eye(n, dtype=dtype)
    J = J.at[p, p].set(c.astype(dtype))
    J = J.at[q, q].set(c.astype(dtype))
    J = J.at[p, q].set(jnp.where(p == q, c, s).astype(dtype))
    J = J.at[q, p].set(jnp.where(p == q, c, -s).astype(dtype))
    return J


def _null_pivot_guard(p, q, apq, c, s):
    """Force the exact identity rotation on null pivots.

    Two cases: (a) apq == 0 -- nothing to annihilate.  The float angle
    formulas already return s = 0 here, but atan2/CORDIC do not (atan2(0, x)
    is pi for x < 0; the fixed-point CORDIC leaves ~2^-29 angle noise), so
    without the guard a zero-padded coordinate could mix with live ones.
    This is what makes bucket padding *exact*: a matrix embedded in a larger
    zero-padded bucket keeps its padded rows/cols at exactly zero through
    every sweep, for every pivot strategy and angle mode.  (b) p == q -- the
    max-pivot DLE degenerates to argmax index 0 on an all-zero off-diagonal;
    rotating "coordinate p against itself" must be a no-op.
    """
    null = (apq == 0.0) | (p == q)
    c = jnp.where(null, jnp.ones_like(c), c)
    s = jnp.where(null, jnp.zeros_like(s), s)
    return c, s


@jax.named_scope("jacobi_rotation")
def _apply_rotations_rowcol(C, V, p, q, c, s):
    """Apply commuting rotations for disjoint pivot sets (vectorised).

    Convention (paper R, eq. 7): R[p,p]=R[q,q]=c, R[p,q]=s, R[q,p]=-s;
    C' = R^T C R, V' = V R.
    """
    c_ = c[:, None]
    s_ = s[:, None]
    rows_p = C[p, :]
    rows_q = C[q, :]
    C = C.at[p, :].set(c_ * rows_p - s_ * rows_q)
    C = C.at[q, :].set(s_ * rows_p + c_ * rows_q)
    cols_p = C[:, p]
    cols_q = C[:, q]
    C = C.at[:, p].set(c * cols_p - s * cols_q)
    C = C.at[:, q].set(s * cols_p + c * cols_q)
    vp = V[:, p]
    vq = V[:, q]
    V = V.at[:, p].set(c * vp - s * vq)
    V = V.at[:, q].set(s * vp + c * vq)
    return C, V


@jax.named_scope("jacobi_rotation")
def _apply_rotations_matmul(C, V, p, q, c, s, matmul_fn):
    n = C.shape[0]
    J = _build_rotation(n, p, q, c, s, C.dtype)
    C = matmul_fn(matmul_fn(J.T, C), J)
    V = matmul_fn(V, J)
    return C, V


def _sweep_scan(C, V, rounds, angle_fn, rotation, matmul_fn,
                fused: bool = False, angle: str = "rutishauser",
                fused_backend: Optional[str] = None):
    """One full sweep: scan over pivot rounds.

    ``fused`` routes each round through the ``jacobi_sweep`` registry op --
    gather + angle + null-pivot guard + row/col rotation in one kernel
    launch (paper's fused Jacobian Unit) instead of a chain of XLA ops with
    C and V round-tripping HBM between them.  The fused round is
    bitwise-identical to the unfused body for every angle mode; it applies
    to ``rotation="rowcol"`` only (the "matmul" datapath deliberately
    routes rotations through the MM-Engine, so it stays unfused).
    """
    if fused and rotation == "rowcol":
        from repro.kernels import ops as kops

        @jax.named_scope("jacobi_round")
        def body(carry, pairs):
            C, V = carry
            C, V = kops.jacobi_sweep(C, V, pairs, angle=angle,
                                     backend=fused_backend)
            return (C, V), None
    else:
        @jax.named_scope("jacobi_round")
        def body(carry, pairs):
            C, V = carry
            p = pairs[:, 0]
            q = pairs[:, 1]
            apq = C[p, q]
            app = C[p, p]
            aqq = C[q, q]
            _, c, s = angle_fn(apq, app, aqq)
            c, s = _null_pivot_guard(p, q, apq, c, s)
            c = c.astype(C.dtype)
            s = s.astype(C.dtype)
            if rotation == "rowcol":
                C, V = _apply_rotations_rowcol(C, V, p, q, c, s)
            else:
                C, V = _apply_rotations_matmul(C, V, p, q, c, s, matmul_fn)
            return (C, V), None

    (C, V), _ = lax.scan(body, (C, V), rounds)
    return C, V


def _max_pivot_sweep(C, V, n_rot: int, angle_fn, rotation, matmul_fn,
                     pivot_fn=dle_mod.find_pivot):
    """n_rot classical max-pivot rotations (DLE lookup per rotation)."""

    @jax.named_scope("jacobi_round")
    def body(_, carry):
        C, V = carry
        piv = pivot_fn(C)
        _, c, s = angle_fn(piv.apq, piv.app, piv.aqq)
        c, s = _null_pivot_guard(piv.p, piv.q, piv.apq, c, s)
        c = c.astype(C.dtype)
        s = s.astype(C.dtype)
        p = piv.p[None]
        q = piv.q[None]
        if rotation == "rowcol":
            C, V = _apply_rotations_rowcol(C, V, p, q, c[None], s[None])
        else:
            C, V = _apply_rotations_matmul(C, V, p, q, c[None], s[None], matmul_fn)
        return C, V

    return lax.fori_loop(0, n_rot, body, (C, V))


def jacobi_eigh(
    C,
    sweeps: int = DEFAULT_SWEEPS,
    pivot: str = "parallel",
    rotation: str = "rowcol",
    angle: str = "rutishauser",
    matmul_fn: Optional[Callable] = None,
    tol: Optional[float] = None,
    track_history: bool = False,
    sort: bool = True,
    fused: bool = False,
    fused_backend: Optional[str] = None,
) -> EighResult:
    """Symmetric eigendecomposition via Jacobi rotations.

    Args:
      C: (n, n) symmetric matrix (float32/float64).
      sweeps: deterministic sweep budget (paper default: 50).
      pivot: "parallel" | "cyclic" | "paper" (max-pivot).
      rotation: "rowcol" | "matmul" (unified MM-Engine datapath).
      angle: "rutishauser" | "atan2" | "cordic".
      matmul_fn: matmul used by rotation="matmul" (defaults to jnp.matmul;
        inject ``kernels.ops.mm_engine_matmul`` for the Pallas path).
      tol: optional early-exit relative off-diagonal tolerance. When set,
        a while_loop replaces the fixed schedule (software mode).
      track_history: record the relative off-norm after every sweep.
      fused: run each pivot round through the fused ``jacobi_sweep``
        registry op (one launch per round; bitwise-identical to the
        unfused path).  Applies to the "parallel"/"cyclic" strategies with
        rotation="rowcol"; "paper" (max-pivot DLE) and the "matmul"
        rotation datapath fall back to the unfused chain.
      fused_backend: registry backend for the fused op (None = resolution
        order: pallas on TPU, interpret elsewhere).
    Returns:
      EighResult with eigenvalues (descending) and column eigenvectors.
    """
    if pivot not in ("parallel", "cyclic", "paper"):
        raise ValueError(f"unknown pivot strategy {pivot!r}")
    if rotation not in ("rowcol", "matmul"):
        raise ValueError(f"unknown rotation mode {rotation!r}")
    angle_fn = ANGLE_MODES[angle]
    matmul_fn = matmul_fn or jnp.matmul

    C = jnp.asarray(C)
    n_in = C.shape[0]
    if n_in == 1:  # trivial 1x1 problem
        return EighResult(jnp.diagonal(C), jnp.ones((1, 1), C.dtype),
                          jnp.zeros((), C.dtype), None)
    # round-robin needs even n: zero-pad one row/col (exact: the padded
    # coordinate never mixes -- its pivots have apq = 0 -> theta = 0).
    padded = pivot == "parallel" and n_in % 2 == 1
    if padded:
        C = jnp.pad(C, ((0, 1), (0, 1)))
    n = C.shape[0]
    V = jnp.eye(n, dtype=C.dtype)

    if pivot == "parallel":
        rounds = jnp.asarray(round_robin_rounds(n))
        rot_per_sweep = None
    elif pivot == "cyclic":
        rounds = jnp.asarray(cyclic_pairs(n))
        rot_per_sweep = None
    else:
        rounds = None
        rot_per_sweep = (n_in * (n_in - 1)) // 2  # one "sweep" worth

    @jax.named_scope("jacobi_sweep")
    def one_sweep(C, V):
        if pivot == "paper":
            return _max_pivot_sweep(C, V, rot_per_sweep, angle_fn, rotation,
                                    matmul_fn)
        return _sweep_scan(C, V, rounds, angle_fn, rotation, matmul_fn,
                           fused=fused, angle=angle,
                           fused_backend=fused_backend)

    if tol is not None:
        def cond(state):
            i, C, V = state
            return (i < sweeps) & (relative_offdiag(C) > tol)

        def body(state):
            i, C, V = state
            C, V = one_sweep(C, V)
            return i + 1, C, V

        _, C, V = lax.while_loop(cond, body, (jnp.int32(0), C, V))
        history = None
    elif track_history:
        hist0 = relative_offdiag(C)

        def body(carry, _):
            C, V = carry
            C, V = one_sweep(C, V)
            return (C, V), relative_offdiag(C)

        (C, V), hist = lax.scan(body, (C, V), None, length=sweeps)
        history = jnp.concatenate([hist0[None], hist])
    else:
        def body(carry, _):
            C, V = carry
            return one_sweep(C, V), None

        (C, V), _ = lax.scan(body, (C, V), None, length=sweeps)
        history = None

    off = relative_offdiag(C)
    eigvals = jnp.diagonal(C)
    if padded:
        eigvals = eigvals[:n_in]
        V = V[:n_in, :n_in]
    if sort:
        order = jnp.argsort(-eigvals)
        eigvals = eigvals[order]
        V = V[:, order]
    return EighResult(eigvals, V, off, history)


def jacobi_svd(A, matmul_fn: Optional[Callable] = None,
               fused: bool = False, fused_backend: Optional[str] = None,
               precision: str = "fp32", **kwargs):
    """SVD of A via eigendecomposition of the Gram matrix A^T A (the PCA
    path: singular values = sqrt(eigenvalues), V = right singular vectors).
    Returns (U, S, Vt) with the thin convention.

    The Gram product and the U = A V back-projection go through the same
    injected ``matmul_fn`` as the rotations: all three matmuls of the SVD
    share the unified MM-Engine datapath (paper Sec. VI-A).  ``fused``
    routes the Gram through the one-pass ``covariance`` registry op and the
    sweeps through the fused ``jacobi_sweep`` op; ``precision`` selects the
    Gram operand-streaming dtype (``repro.core.precision`` -- rotations and
    the back-projection always stay fp32).
    """
    mm = matmul_fn or jnp.matmul
    if fused:
        from repro.kernels import ops as kops
        gram = kops.covariance(A, precision=precision,
                               backend=fused_backend)
    else:
        gram = mm(A.T, A)
    res = jacobi_eigh(gram, matmul_fn=matmul_fn, fused=fused,
                      fused_backend=fused_backend, **kwargs)
    s = jnp.sqrt(jnp.maximum(res.eigenvalues, 0.0))
    V = res.eigenvectors
    safe = jnp.maximum(s, 1e-30)
    U = mm(A, V) / safe[None, :]
    return U, s, V.T
