"""Jit'd public wrappers around the kernel layer, dispatched through the
backend registry (``repro.backends``).

Each public op (a) pads arbitrary shapes up to block multiples (the paper's
Matrix Padding Unit at the cache/MM-Engine interface) and (b) resolves a
named backend implementation per call:

  ``pallas``     compiled Pallas TPU kernel
  ``interpret``  the same kernel under the Pallas interpreter (any host)
  ``ref``        the pure-jnp XLA oracle (``repro.kernels.ref``)

``backend=None`` follows the registry's resolution order (process default,
``REPRO_KERNEL_BACKEND``, else pallas-on-TPU / interpret-elsewhere).  The
legacy ``interpret=`` flag is kept as an alias: ``interpret=True`` means
``backend="interpret"``, ``interpret=False`` means ``backend="pallas"``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.backends import registry

from . import mm_engine as _mm
from . import dle as _dle
from . import cordic as _cordic
from . import flash_attention as _fa
from . import mamba_scan as _ms
from . import ref as _ref


def _backend_name(backend: str | None, interpret: bool | None) -> str:
    if backend is None and interpret is not None:
        backend = "interpret" if interpret else "pallas"
    return registry.default_backend() if backend is None else backend


def _pad_to(x, mults):
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


# -- mm_engine_matmul -------------------------------------------------------

def _mm_kernel_impl(a, b, *, block: int, interpret: bool):
    m, k = a.shape
    _, n = b.shape
    ap = _pad_to(a, (block, block))
    bp = _pad_to(b, (block, block))
    out = _mm.mm_engine(ap, bp, block_m=block, block_n=block, block_k=block,
                        interpret=interpret)
    return out[:m, :n]


registry.register("mm_engine_matmul", "pallas")(
    functools.partial(_mm_kernel_impl, interpret=False))
registry.register("mm_engine_matmul", "interpret")(
    functools.partial(_mm_kernel_impl, interpret=True))


@registry.register("mm_engine_matmul", "ref")
def _mm_ref_impl(a, b, *, block: int = 0):
    del block
    return _ref.mm_engine(a, b)


@functools.partial(jax.jit, static_argnames=("block", "backend"))
def _mm_dispatch(a, b, block, backend):
    return registry.resolve("mm_engine_matmul", backend)(a, b, block=block)


def mm_engine_matmul(a, b, block: int = 128, *,
                     backend: str | None = None,
                     interpret: bool | None = None):
    """Block-streamed a @ b for arbitrary shapes (paper tile size T=block)."""
    return _mm_dispatch(a, b, block, _backend_name(backend, interpret))


# -- dle_find_pivot ---------------------------------------------------------

def _dle_kernel_impl(c, *, tile: int, interpret: bool):
    from repro.core.dle import Pivot
    n = c.shape[0]
    _, idx = _dle.dle_scan(c, tile=tile, interpret=interpret)
    p = (idx // n).astype(jnp.int32)
    q = (idx % n).astype(jnp.int32)
    d = jnp.diagonal(c)
    return Pivot(p, q, c[p, q], d[p], d[q])


registry.register("dle_find_pivot", "pallas")(
    functools.partial(_dle_kernel_impl, interpret=False))
registry.register("dle_find_pivot", "interpret")(
    functools.partial(_dle_kernel_impl, interpret=True))


@registry.register("dle_find_pivot", "ref")
def _dle_ref_impl(c, *, tile: int = 0):
    del tile
    from repro.core import dle as core_dle
    return core_dle.find_pivot(c)


@functools.partial(jax.jit, static_argnames=("tile", "backend"))
def _dle_dispatch(c, tile, backend):
    return registry.resolve("dle_find_pivot", backend)(c, tile=tile)


def dle_find_pivot(c, tile: int = 128, *, backend: str | None = None,
                   interpret: bool | None = None):
    """Pivot for the Jacobi step: (p, q, c_pq, c_pp, c_qq) via one scan."""
    return _dle_dispatch(c, tile, _backend_name(backend, interpret))


# -- cordic_rotate ----------------------------------------------------------

def _cordic_kernel_impl(apq, app, aqq, *, block: int, interpret: bool):
    return _cordic.cordic_rotation_params(
        jnp.atleast_1d(apq), jnp.atleast_1d(app), jnp.atleast_1d(aqq),
        block=block, interpret=interpret)


registry.register("cordic_rotate", "pallas")(
    functools.partial(_cordic_kernel_impl, interpret=False))
registry.register("cordic_rotate", "interpret")(
    functools.partial(_cordic_kernel_impl, interpret=True))


@registry.register("cordic_rotate", "ref")
def _cordic_ref_impl(apq, app, aqq, *, block: int = 0):
    del block
    return _ref.cordic_rotation_params(
        jnp.atleast_1d(apq), jnp.atleast_1d(app), jnp.atleast_1d(aqq))


@functools.partial(jax.jit, static_argnames=("block", "backend"))
def _cordic_dispatch(apq, app, aqq, block, backend):
    return registry.resolve("cordic_rotate", backend)(apq, app, aqq,
                                                      block=block)


def cordic_rotation_params(apq, app, aqq, block: int = 256, *,
                           backend: str | None = None,
                           interpret: bool | None = None):
    return _cordic_dispatch(apq, app, aqq, block,
                            _backend_name(backend, interpret))


cordic_rotate = cordic_rotation_params  # registry op name alias


# -- flash_attention --------------------------------------------------------

def _fa_kernel_impl(q, k, v, *, causal, scale, block_q, block_k, q_offset,
                    interpret):
    sq, skv = q.shape[1], k.shape[1]
    qp = _pad_to(q, (1, block_q, 1))
    kp = _pad_to(k, (1, block_k, 1))
    vp = _pad_to(v, (1, block_k, 1))
    if kp.shape[1] != skv:
        # padded KV positions must not attract attention: rely on causal
        # masking when causal, else mask via huge negative bias is needed --
        # we simply require multiples for non-causal.
        assert causal, "non-causal flash requires Skv % block_k == 0"
    out = _fa.flash_attention(qp, kp, vp, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              q_offset=q_offset, interpret=interpret)
    return out[:, :sq, :]


registry.register("flash_attention", "pallas")(
    functools.partial(_fa_kernel_impl, interpret=False))
registry.register("flash_attention", "interpret")(
    functools.partial(_fa_kernel_impl, interpret=True))


@registry.register("flash_attention", "ref")
def _fa_ref_impl(q, k, v, *, causal, scale, block_q=0, block_k=0,
                 q_offset=0):
    del block_q, block_k
    return _ref.flash_attention(q, k, v, causal=causal, scale=scale,
                                q_offset=q_offset)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "q_offset", "backend"))
def _fa_dispatch(q, k, v, causal, scale, block_q, block_k, q_offset,
                 backend):
    return registry.resolve("flash_attention", backend)(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, q_offset=q_offset)


def flash_attention(q, k, v, causal: bool = True, scale=None,
                    block_q: int = 128, block_k: int = 128,
                    q_offset: int = 0, *, backend: str | None = None,
                    interpret: bool | None = None):
    """q (BH, Sq, D), k/v (BH, Skv, D); pads sequence dims as needed."""
    return _fa_dispatch(q, k, v, causal, scale, block_q, block_k, q_offset,
                        _backend_name(backend, interpret))


# -- mamba_scan -------------------------------------------------------------

def _ms_kernel_impl(u, delta, A, B, C, D_skip, *, chunk, interpret):
    L = u.shape[1]
    up = _pad_to(u, (1, chunk, 1))
    dp = _pad_to(delta, (1, chunk, 1))
    bp = _pad_to(B, (1, chunk, 1))
    cp = _pad_to(C, (1, chunk, 1))
    y = _ms.mamba_scan(up, dp, A, bp, cp, D_skip, chunk=chunk,
                       interpret=interpret)
    return y[:, :L, :]


registry.register("mamba_scan", "pallas")(
    functools.partial(_ms_kernel_impl, interpret=False))
registry.register("mamba_scan", "interpret")(
    functools.partial(_ms_kernel_impl, interpret=True))


@registry.register("mamba_scan", "ref")
def _ms_ref_impl(u, delta, A, B, C, D_skip, *, chunk: int = 0):
    del chunk
    return _ref.mamba_scan(u, delta, A, B, C, D_skip)


@functools.partial(jax.jit, static_argnames=("chunk", "backend"))
def _ms_dispatch(u, delta, A, B, C, D_skip, chunk, backend):
    return registry.resolve("mamba_scan", backend)(u, delta, A, B, C,
                                                   D_skip, chunk=chunk)


def mamba_scan(u, delta, A, B, C, D_skip, chunk: int = 128, *,
               backend: str | None = None, interpret: bool | None = None):
    return _ms_dispatch(u, delta, A, B, C, D_skip, chunk,
                        _backend_name(backend, interpret))


ref = _ref
