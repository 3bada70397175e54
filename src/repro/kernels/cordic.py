"""Pipelined CORDIC Pallas kernel (paper Sec. VI-C).

Computes theta = -1/2*atan2(2*c_pq, c_pp - c_qq), sin(theta), cos(theta) for
a *batch* of pivots in Q2.29 fixed point -- the vectorised analogue of the
paper's pipelined CORDIC arctangent unit, 1-bit right shifter, and parallel
sin/cos rotators.  On TPU the VPU executes each shift-add micro-rotation
across all lanes at once; the pipeline depth of the RTL becomes the
fori_loop trip count.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.cordic import CORDIC_ITERS, _ATAN_FIXED, _GAIN, _FRAC_BITS

from . import compat
from .compat import pl

_ONE_F = float(1 << _FRAC_BITS)
_LANES, _SUBLANES = 128, 8


def _cordic_kernel(apq_ref, app_ref, aqq_ref, th_ref, c_ref, s_ref, *,
                   iters: int):
    y = 2.0 * apq_ref[...]
    x = app_ref[...] - aqq_ref[...]

    # front-end barrel shift: shared power-of-two normalisation into Q2.29
    mag = jnp.maximum(jnp.maximum(jnp.abs(y), jnp.abs(x)), 1e-30)
    scale = jnp.exp2(-jnp.ceil(jnp.log2(mag)))
    yn = y * scale
    xn = x * scale
    neg_x = xn < 0
    xi = jnp.round(jnp.where(neg_x, -xn, xn) * _ONE_F).astype(jnp.int32)
    yi = jnp.round(jnp.where(neg_x, -yn, yn) * _ONE_F).astype(jnp.int32)
    zi = jnp.zeros_like(xi)

    # unrolled pipeline stages (as in the RTL); the atan table entries are
    # per-stage scalar constants, not a captured array
    for i in range(iters):
        d = jnp.where(yi >= 0, 1, -1).astype(jnp.int32)
        xi, yi, zi = (xi + d * (yi >> i), yi - d * (xi >> i),
                      zi + d * jnp.int32(int(_ATAN_FIXED[i])))
    ang = zi.astype(jnp.float32) / _ONE_F
    pi = jnp.float32(np.pi)
    ang = jnp.where(neg_x, jnp.where(y >= 0, ang + pi, ang - pi), ang)

    # the 1-bit right shift (sign-corrected, see core/cordic.py)
    theta = -0.5 * ang

    # rotation mode: parallel sin/cos lanes
    zr = jnp.round(theta * _ONE_F).astype(jnp.int32)
    xr = jnp.full(zr.shape, np.int32(round(_ONE_F / _GAIN)), jnp.int32)
    yr = jnp.zeros_like(xr)

    for i in range(iters):
        d = jnp.where(zr >= 0, 1, -1).astype(jnp.int32)
        xr, yr, zr = (xr - d * (yr >> i), yr + d * (xr >> i),
                      zr - d * jnp.int32(int(_ATAN_FIXED[i])))
    th_ref[...] = theta
    c_ref[...] = xr.astype(jnp.float32) / _ONE_F
    s_ref[...] = yr.astype(jnp.float32) / _ONE_F


def cordic_rotation_params(
    apq: jax.Array,
    app: jax.Array,
    aqq: jax.Array,
    *,
    block: int = 256,
    iters: int = CORDIC_ITERS,
    interpret: bool = False,
):
    """(theta, cos, sin) for each pivot; 1-D inputs of any common length.

    The pivots are laid out lane-dense as a (rows, 128) slab, tiled in
    row panels of ``block`` pivots (rounded up to whole (8, 128) vreg
    tiles; a slab smaller than one panel is a single full-array block).
    A 1-D block is refused by the TPU compiler as soon as XLA picks a
    wider 1-D tiling for the operand than the block (length 448 pads to
    512 and gets ``T(512)`` against a 256 block); 2-D (8, 128) tiling is
    what both sides agree on at every length.
    """
    (k,) = apq.shape
    rows = max(1, -(-k // _LANES))
    panel = max(_SUBLANES, -(-block // (_LANES * _SUBLANES)) * _SUBLANES)
    panel = min(panel, rows)
    rows = -(-rows // panel) * panel
    pad = rows * _LANES - k

    def slab(x, fill=0.0):
        x = jnp.pad(x.astype(jnp.float32), (0, pad), constant_values=fill)
        return x.reshape(rows, _LANES)

    spec = pl.BlockSpec((panel, _LANES), lambda i: (i, 0))
    th, c, s = pl.pallas_call(
        functools.partial(_cordic_kernel, iters=iters),
        grid=(rows // panel,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.float32)] * 3,
        interpret=interpret,
        name="cordic",
        **compat.compiler_params(dimension_semantics=("parallel",)),
    )(slab(apq), slab(app, 1.0), slab(aqq))
    return (th.reshape(-1)[:k], c.reshape(-1)[:k], s.reshape(-1)[:k])
