"""Pure-jnp oracles for every Pallas kernel (the allclose references), and
the reference Gram and indexed Jacobi round the core paths are tested
against."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import dle as core_dle
from repro.core.cordic import rotation_params


def mm_engine(a, b, out_dtype=None):
    """fp32-accumulated matmul."""
    out_dtype = out_dtype or a.dtype
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(out_dtype)


def covariance_gram(x, acc_dtype=jnp.float32, out_dtype=None):
    """One-dot Gram matrix C = x^T x with explicit accumulator dtype."""
    out_dtype = out_dtype or acc_dtype
    return lax.dot_general(
        x, x, dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=acc_dtype).astype(out_dtype)


def jacobi_sweep_step(C, V, pairs, angle: str = "rutishauser"):
    """One pivot round, indexed by its pairs: the ``core.jacobi``
    round that the tournament layout reproduces bit for bit."""
    from repro.core.cordic import ANGLE_MODES
    from repro.core.jacobi import _apply_rotations_rowcol, _null_pivot_guard
    p = pairs[:, 0]
    q = pairs[:, 1]
    apq = C[p, q]
    app = C[p, p]
    aqq = C[q, q]
    _, c, s = ANGLE_MODES[angle](apq, app, aqq)
    c, s = _null_pivot_guard(p, q, apq, c, s)
    c = c.astype(C.dtype)
    s = s.astype(C.dtype)
    return _apply_rotations_rowcol(C, V, p, q, c, s)


def dle_scan(c):
    """(max |off-diag|, flat index) over a symmetric matrix."""
    piv = core_dle.find_pivot(c)
    n = c.shape[0]
    return jnp.abs(piv.apq).astype(jnp.float32), (piv.p * n + piv.q).astype(jnp.int32)


def cordic_rotation_params(apq, app, aqq):
    """Float-exact rotation parameters (theta, cos, sin)."""
    th, c, s = rotation_params(jnp.asarray(apq, jnp.float32),
                               jnp.asarray(app, jnp.float32),
                               jnp.asarray(aqq, jnp.float32))
    return th, c, s


def flash_attention(q, k, v, *, causal=True, scale=None, q_offset=0):
    """Dense softmax attention, fp32 math. q/k/v: (BH, S, D)."""
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, skv = q.shape[1], k.shape[1]
        rows = jnp.arange(sq)[:, None] + q_offset
        cols = jnp.arange(skv)[None, :]
        s = jnp.where(rows >= cols, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def mamba_scan(u, delta, A, B, C, D_skip):
    """Sequential lax.scan oracle for the selective scan."""

    def step(x, inputs):
        u_t, dt_t, b_t, c_t = inputs
        decay = jnp.exp(dt_t[:, :, None] * A[None])          # (B, D, N)
        x = decay * x + (dt_t * u_t)[:, :, None] * b_t[:, None, :]
        y = jnp.sum(x * c_t[:, None, :], axis=2) + D_skip[None, :] * u_t
        return x, y

    bsz, L, d = u.shape
    n = A.shape[1]
    x0 = jnp.zeros((bsz, d, n), jnp.float32)
    xs = (u.swapaxes(0, 1).astype(jnp.float32),
          delta.swapaxes(0, 1).astype(jnp.float32),
          B.swapaxes(0, 1).astype(jnp.float32),
          C.swapaxes(0, 1).astype(jnp.float32))
    _, ys = lax.scan(step, x0, xs)
    return ys.swapaxes(0, 1).astype(u.dtype)
