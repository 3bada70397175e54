"""Rotation-parameter computation for Jacobi sweeps.

The paper computes theta = 1/2 * atan(2*c_pq / (c_pp - c_qq)) with a pipelined
CORDIC arctangent unit followed by a 1-bit right shift, then sin/cos with two
parallel CORDIC rotators (Sec. VI-C).  On TPU there is no CORDIC block; the VPU
executes the shift-add iterations SIMD-style across every concurrent pivot.
This module provides

  * ``rotation_params``            -- float atan2 formulation (fast mode)
  * ``rotation_params_rutishauser``-- Golub&Van-Loan stable t-formula
  * ``rotation_params_cordic``     -- fixed-point (Q2.29) CORDIC, bit-faithful
                                      to the hardware datapath
  * ``cordic_atan2`` / ``cordic_sincos`` -- the underlying engines

Sign convention (note: the paper's eq.(6)+(7) pair has a sign slip -- applying
R from eq.(7) with theta from eq.(6) does NOT annihilate c_pq; see DESIGN.md):
we keep the paper's R (R[p,p]=R[q,q]=cos, R[p,q]=sin, R[q,p]=-sin) and use

    theta = -1/2 * atan2(2*c_pq, c_pp - c_qq)

which zeroes the pivot exactly under C' = R^T C R.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

# Number of CORDIC micro-rotations (paper: pipelined stages).  30 iterations
# in Q2.29 reaches ~2^-29 angle granularity, comfortably below fp32 eps for
# the downstream rotation.
CORDIC_ITERS = 30
_FRAC_BITS = 29
_ONE = np.int64(1) << _FRAC_BITS
# CORDIC gain K = prod(sqrt(1 + 2^-2i)); we multiply by 1/K up front.
_GAIN = float(np.prod([np.sqrt(1.0 + 2.0 ** (-2 * i)) for i in range(CORDIC_ITERS)]))
_ATAN_TABLE = np.array(
    [np.arctan(2.0 ** -i) for i in range(CORDIC_ITERS)], dtype=np.float64
)
_ATAN_FIXED = np.round(_ATAN_TABLE * _ONE).astype(np.int32)


def rotation_params(apq, app, aqq):
    """theta, cos, sin such that R^T C R zeroes c_pq (paper R convention)."""
    theta = -0.5 * jnp.arctan2(2.0 * apq, app - aqq)
    return theta, jnp.cos(theta), jnp.sin(theta)


def rotation_params_rutishauser(apq, app, aqq):
    """Numerically-stable small-angle rotation (|theta| <= pi/4).

    Solves t^2 + 2*tau*t - 1 = 0 with tau = (app - aqq) / (2*apq) for the
    root of smaller magnitude.  Matches the paper's R convention: with
    s = t*c the update C' = R^T C R zeroes c_pq.
    """
    safe = jnp.abs(apq) > 0.0
    tau = (app - aqq) / jnp.where(safe, 2.0 * apq, 1.0)
    sgn = jnp.where(tau >= 0.0, 1.0, -1.0)
    t = sgn / (jnp.abs(tau) + jnp.sqrt(1.0 + tau * tau))
    # sign fix: for our convention theta = -1/2 atan2(2 apq, app-aqq);
    # the G&VL root corresponds to s_gvl = -s_ours, so negate.
    t = jnp.where(safe, -t, 0.0)
    c = 1.0 / jnp.sqrt(1.0 + t * t)
    s = t * c
    theta = jnp.arctan(t)
    return theta, c, s


# ---------------------------------------------------------------------------
# Fixed-point CORDIC (vectorised; mirrors the RTL datapath)
# ---------------------------------------------------------------------------

# Q2.29 in int32: |values| stay below 2^31 through both CORDIC modes
# (vectoring norm growth <= K*sqrt(2)*2^29 ~ 1.25e9), matching the 32-bit
# RTL datapath.


def _to_fixed(x):
    return jnp.round(x * float(_ONE)).astype(jnp.int32)


def _from_fixed(x):
    return x.astype(jnp.float32) / float(_ONE)


def cordic_atan2(y, x, iters: int = CORDIC_ITERS):
    """Vectorised vectoring-mode CORDIC: atan2(y, x) for x of any sign.

    Inputs are floats; they are normalised into Q2.29 exactly as the RTL
    front-end scales operands into its fixed-point format (a shared scale
    leaves the angle unchanged).
    """
    y = jnp.asarray(y, jnp.float32)
    x = jnp.asarray(x, jnp.float32)
    mag = jnp.maximum(jnp.maximum(jnp.abs(y), jnp.abs(x)), 1e-30)
    # shared power-of-two normalisation (a barrel shift in hardware)
    scale = jnp.exp2(-jnp.ceil(jnp.log2(mag)))
    yn = y * scale
    xn = x * scale
    # quadrant fold: vectoring CORDIC converges for x > 0
    neg_x = xn < 0
    xq = jnp.where(neg_x, -xn, xn)
    yq = jnp.where(neg_x, -yn, yn)
    xi = _to_fixed(xq)
    yi = _to_fixed(yq)
    zi = jnp.zeros_like(xi)
    atan_tab = jnp.asarray(_ATAN_FIXED)

    def body(i, carry):
        xi, yi, zi = carry
        d = jnp.where(yi >= 0, 1, -1).astype(jnp.int32)
        x_new = xi + d * (yi >> i)
        y_new = yi - d * (xi >> i)
        z_new = zi + d * atan_tab[i]
        return x_new, y_new, z_new

    xi, yi, zi = lax.fori_loop(0, iters, body, (xi, yi, zi))
    ang = _from_fixed(zi)
    # unfold quadrant: atan2(y,x) = atan2(-y,-x) +/- pi
    pi = jnp.float32(np.pi)
    ang = jnp.where(neg_x, jnp.where(y >= 0, ang + pi, ang - pi), ang)
    return ang


def cordic_sincos(theta, iters: int = CORDIC_ITERS):
    """Vectorised rotation-mode CORDIC: (sin, cos) of theta in (-pi, pi]."""
    theta = jnp.asarray(theta, jnp.float32)
    half_pi = jnp.float32(np.pi / 2)
    # fold into (-pi/2, pi/2]; CORDIC rotation converges for |z| < ~1.74 rad
    fold_hi = theta > half_pi
    fold_lo = theta < -half_pi
    th = jnp.where(fold_hi, theta - jnp.float32(np.pi),
                   jnp.where(fold_lo, theta + jnp.float32(np.pi), theta))
    flip = fold_hi | fold_lo

    zi = _to_fixed(th)
    xi = jnp.broadcast_to(_to_fixed(jnp.float32(1.0 / _GAIN)), zi.shape).astype(jnp.int32)
    yi = jnp.zeros_like(xi)
    atan_tab = jnp.asarray(_ATAN_FIXED)

    def body(i, carry):
        xi, yi, zi = carry
        d = jnp.where(zi >= 0, 1, -1).astype(jnp.int32)
        x_new = xi - d * (yi >> i)
        y_new = yi + d * (xi >> i)
        z_new = zi - d * atan_tab[i]
        return x_new, y_new, z_new

    xi, yi, zi = lax.fori_loop(0, iters, body, (xi, yi, zi))
    sin = _from_fixed(yi)
    cos = _from_fixed(xi)
    sign = jnp.where(flip, -1.0, 1.0).astype(jnp.float32)
    return sin * sign, cos * sign


def rotation_params_cordic(apq, app, aqq, iters: int = CORDIC_ITERS):
    """Paper-faithful datapath: CORDIC atan -> 1-bit right shift -> CORDIC
    sin/cos (two rotators in parallel in the RTL; one fused call here)."""
    full = cordic_atan2(2.0 * apq, app - aqq, iters)
    theta = -0.5 * full  # the RTL 1-bit arithmetic right shift (sign-fixed)
    s, c = cordic_sincos(theta, iters)
    return theta, c, s


ANGLE_MODES = {
    "atan2": rotation_params,
    "rutishauser": rotation_params_rutishauser,
    "cordic": rotation_params_cordic,
}
