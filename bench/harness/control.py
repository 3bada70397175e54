"""The lower-precision control: the plain reference put in the program's
place, with its matmuls on bfloat16 operands, to show that the comparison
in ``reference`` fails it.

The step is spelled out in the operands, so it is the same arithmetic on
any backend (a TPU and the CPU of a test run alike):

  bf16      one pass of bfloat16 operands: what ``Precision.DEFAULT``
            computes for float32 on a TPU, and what the program's own
            ``bf16_fp32acc`` policy streams.

The step between, XLA's three-pass ``Precision.HIGH``, is not a control:
at the configurations' sizes its answers lie closer to float64 than the
program's own at ``HIGHEST`` (see PERF.md), so no limit can fail it.

Each product of bfloat16 values is exact in float32 and is accumulated in
float32.  The eigensolver is ``jnp.linalg.eigh`` in float32 at full
precision; only the reference's own matmuls (covariance, Gram, the svd
back-projection) take the lower precision.
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

import numpy as np

def mm(a, b):
    """a @ b on bfloat16 operands, each product exact and accumulated in
    float32."""
    import jax
    import jax.numpy as jnp

    def bf16(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.dot(bf16(a), bf16(b), precision=jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=None)
def _solver(op: str):
    import jax
    import jax.numpy as jnp

    def eigh_desc(c):
        with jax.default_matmul_precision("highest"):
            w, V = jnp.linalg.eigh(c)
        return w[::-1], V[:, ::-1]

    def fn(a):
        if op == "eigh":
            return eigh_desc(a)
        if op == "pca":
            mean = a.mean(0)
            std = a.std(0)
            std = jnp.where(std < 1e-8, 1.0, std)
            xs = (a - mean) / std
            w, V = eigh_desc(mm(xs.T, xs))
            return w, V, mean, std
        w, V = eigh_desc(mm(a.T, a))
        s = jnp.sqrt(jnp.maximum(w, 0.0))
        return mm(a, V) / jnp.maximum(s, 1e-30)[None, :], s, V.T
    return jax.jit(fn)


def served(op: str, matrix: np.ndarray):
    """The control's answer, shaped as the server's answer for ``op``."""
    out = [np.asarray(x) for x in _solver(op)(np.asarray(matrix,
                                                         np.float32))]
    if op == "eigh":
        return SimpleNamespace(eigenvalues=out[0], eigenvectors=out[1])
    if op == "pca":
        return SimpleNamespace(eigenvalues=out[0], components=out[1],
                               mean=out[2], scale=out[3])
    return SimpleNamespace(U=out[0], S=out[1], Vt=out[2])
