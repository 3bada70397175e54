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

Parallel pivots with row/column rotations (unfused) run on the Brent-Luk
tournament layout: C as four blocks and V transposed, in the slots of
``tournament_slots``, so each round rotates top slot i against bottom slot
i elementwise and then moves the players by a static shift.  Every other
combination indexes its pivots' rows and columns.

Name scopes ``jacobi_sweep``, ``jacobi_round``, ``jacobi_rotation`` and
``jacobi_shift`` mark one sweep, one pivot round, the rotation apply and
the tournament's move between rounds in the ``op_name`` of the compiled
operations, so a device trace can be reduced by stage.
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
def tournament_slots(n: int) -> np.ndarray:
    """(n-1, 2, n//2) players in the top and bottom slots of each round.

    The circle method of ``round_robin_rounds`` held as two rows of slots,
    ``top = players[:n/2]`` and ``bot = players[n-1:n/2-1:-1]``, so that
    round r pairs top slot i with bottom slot i: the same unordered pairs,
    round by round.  Between rounds ``_shift`` moves the players; after
    n-1 moves they are back in their starting slots.
    """
    assert n % 2 == 0, "round-robin ordering needs even n (pad first)"
    h = n // 2
    top, bot = list(range(h)), list(range(n - 1, h - 1, -1))
    rounds = []
    for _ in range(n - 1):
        rounds.append((top, bot))
        if n > 2:
            top, bot = [top[0], bot[0]] + top[1:-1], bot[1:] + [top[-1]]
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


def _diagonal(X):
    """Diagonals of the trailing square axes, gathered by no index: a masked
    sum whose fill and start are -0.0, so each sum returns its one entry
    exactly, the sign of a zero included."""
    n = X.shape[-1]
    neg0 = np.array(-0.0, X.dtype)
    masked = jnp.where(jnp.eye(n, dtype=bool), X, neg0)
    return lax.reduce(masked, neg0, lax.add, (X.ndim - 1,))


def _rotate_pairs(top, bot, c, s, top_is_p):
    """``_apply_rotations_rowcol``'s update of each slot pair, written from
    the side of p: p' = c p - s q, q' = s p + c q.

    Each output is one sum of two products whose first product is the
    indexed round's first, picked by ``where`` from the slot holding p, so
    a compiler that fuses a product into the sum (an FMA) meets the same
    expression as on the indexed path (writing q' = c q + s p, say, moved
    the CPU results off the indexed ones in the last bit).
    """
    p = jnp.where(top_is_p, top, bot)
    q = jnp.where(top_is_p, bot, top)
    new_p = c * p + (-s) * q
    new_q = s * p + c * q
    return (jnp.where(top_is_p, new_p, new_q),
            jnp.where(top_is_p, new_q, new_p))


def _shift(top, bot, axis: int):
    """The circle method's move between rounds along ``axis`` of the two rows
    of slots: top' = [top0, bot0, top1..top(h-2)], bot' = [bot1..bot(h-1),
    top(h-1)].  Each output selects, slot by slot, from its inputs moved by
    one slot (a static pad), so the move fuses with what produced them; at
    h = 1 nothing moves."""
    h = top.shape[axis]
    if h == 1:
        return top, bot

    def moved(x, by):
        cfg = [(0, 0, 0)] * x.ndim
        cfg[axis] = (by, -by, 0)
        return lax.pad(x, np.array(0, x.dtype), cfg)

    slot = lax.broadcasted_iota(jnp.int32, top.shape, axis)
    down_top, down_bot = moved(top, 1), moved(bot, 1)
    new_top = jnp.where(slot == 0, top,
                        jnp.where(slot == 1, down_bot, down_top))
    new_bot = jnp.where(slot == h - 1, top, moved(bot, -1))
    return new_top, new_bot


def _enter_tournament(C, V):
    """C and V in the starting slots of ``tournament_slots``: top slot i holds
    coordinate i, bottom slot i holds n-1-i.

    C becomes its four (n/2, n/2) blocks, ``Cb[a, b]`` = the rows of half a
    against the columns of half b, so a pair's two rows (and two columns)
    sit at the same offset of two blocks; V is held transposed, ``Vt[a, i]``
    = the column of V in slot i of half a, so its rotation acts on rows.
    """
    h = C.shape[0] // 2

    def halves(x, axis):
        cut = functools.partial(lax.slice_in_dim, x, axis=axis)
        return cut(0, h), jnp.flip(cut(h, 2 * h), axis)

    top, bot = halves(C, 0)
    Cb = jnp.stack([jnp.stack(halves(top, 1)), jnp.stack(halves(bot, 1))])
    return Cb, jnp.stack(halves(V.T, 0))


def _leave_tournament(Cb, Vt):
    """Inverse of ``_enter_tournament``: C and V in natural order."""
    def join(a, b, axis):
        return jnp.concatenate([a, jnp.flip(b, axis)], axis)

    C = join(join(Cb[0, 0], Cb[0, 1], 1), join(Cb[1, 0], Cb[1, 1], 1), 0)
    return C, join(Vt[0], Vt[1], 0).T


@jax.named_scope("jacobi_rotation")
def _rotate_tournament(Cb, Vt, c, s, top_is_p):
    """Rotate every top slot against its bottom slot: C's rows, then its
    columns, then V's columns -- elementwise, c and s broadcast per slot."""
    col = (c[:, None], s[:, None], top_is_p[:, None])
    rows = jnp.stack(_rotate_pairs(Cb[0], Cb[1], *col))
    Cb = jnp.stack(_rotate_pairs(rows[:, 0], rows[:, 1], c, s, top_is_p),
                   axis=1)
    return Cb, jnp.stack(_rotate_pairs(Vt[0], Vt[1], *col))


def _tournament_sweep(Cb, Vt, angle_fn):
    """One parallel sweep on the tournament layout (``_enter_tournament``).

    Round r rotates top slot i against bottom slot i, whose players are
    ``tournament_slots(n)[r]``: the pairs of ``round_robin_rounds(n)[r]``.
    Each pair keeps p = min, q = max: the angle reads (apq, app, aqq) from
    the block diagonals that hold C[p, q], C[p, p] and C[q, q], and
    ``_rotate_pairs`` writes the update from the side of whichever slot
    holds p.  So every entry is computed by the indexed round's expression,
    bit for bit, with no gather or scatter; then ``_shift`` moves the
    players (``jacobi_shift``).  The block diagonals are read at the end of
    the round that wrote the blocks and carried to the next: on a TPU the
    compiler then keeps the whole loop's C and V in VMEM.
    """
    h = Cb.shape[-1]
    slots = jnp.asarray(tournament_slots(2 * h))

    @jax.named_scope("jacobi_round")
    def body(carry, players):
        Cb, Vt, d = carry
        top, bot = players[0], players[1]
        top_is_p = top < bot
        apq = jnp.where(top_is_p, d[0, 1], d[1, 0])
        app = jnp.where(top_is_p, d[0, 0], d[1, 1])
        aqq = jnp.where(top_is_p, d[1, 1], d[0, 0])
        _, c, s = angle_fn(apq, app, aqq)
        c, s = _null_pivot_guard(jnp.minimum(top, bot), jnp.maximum(top, bot),
                                 apq, c, s)
        c = c.astype(Cb.dtype)
        s = s.astype(Cb.dtype)
        Cb, Vt = _rotate_tournament(Cb, Vt, c, s, top_is_p)
        with jax.named_scope("jacobi_shift"):
            Cb = jnp.stack(_shift(Cb[0], Cb[1], 1))
            Cb = jnp.stack(_shift(Cb[:, 0], Cb[:, 1], 2), axis=1)
            Vt = jnp.stack(_shift(Vt[0], Vt[1], 0))
        return (Cb, Vt, _diagonal(Cb)), None

    (Cb, Vt, _), _ = lax.scan(body, (Cb, Vt, _diagonal(Cb)), slots)
    return Cb, Vt


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

    # parallel pivots rotated row/column-wise run on the tournament layout,
    # entered once here and left once after the last sweep
    tournament = pivot == "parallel" and rotation == "rowcol" and not fused
    state = _enter_tournament(C, V) if tournament else (C, V)

    def natural(state):
        return _leave_tournament(*state) if tournament else state

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
    def one_sweep(state):
        if tournament:
            return _tournament_sweep(*state, angle_fn)
        C, V = state
        if pivot == "paper":
            return _max_pivot_sweep(C, V, rot_per_sweep, angle_fn, rotation,
                                    matmul_fn)
        return _sweep_scan(C, V, rounds, angle_fn, rotation, matmul_fn,
                           fused=fused, angle=angle,
                           fused_backend=fused_backend)

    if tol is not None:
        def cond(carry):
            i, state = carry
            return (i < sweeps) & (relative_offdiag(natural(state)[0]) > tol)

        def body(carry):
            i, state = carry
            return i + 1, one_sweep(state)

        _, state = lax.while_loop(cond, body, (jnp.int32(0), state))
        history = None
    elif track_history:
        hist0 = relative_offdiag(C)

        def body(state, _):
            state = one_sweep(state)
            return state, relative_offdiag(natural(state)[0])

        state, hist = lax.scan(body, state, None, length=sweeps)
        history = jnp.concatenate([hist0[None], hist])
    else:
        def body(state, _):
            return one_sweep(state), None

        state, _ = lax.scan(body, state, None, length=sweeps)
        history = None

    C, V = natural(state)
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
