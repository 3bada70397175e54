"""Covariance computation C = X^T X with block streaming (paper Sec. VI-A).

The contraction dimension of X^T X is the *sample* axis M, so streaming
T-sized sample blocks keeps the on-chip working set constant regardless of
dataset size -- the paper's scale-invariance claim.  Three paths, each
under the ``covariance`` name scope (the ``op_name`` of its operations in
HLO metadata and in device traces):

  * ``covariance``            -- plain jnp (oracle / CPU path)
  * ``blocked_covariance``    -- explicit block-streaming accumulation
                                 (structure of the MM-Engine schedule)
  * ``distributed_covariance``-- the same block streaming lifted across a
                                 mesh: each data shard accumulates its local
                                 X_i^T X_i and a psum over the data axis
                                 completes the accumulation.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .precision import matmul_precision


def standardize(X, eps: float = 1e-8) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Zero-mean / unit-variance per feature (paper eq. 1).

    MANOJAVAM assumes pre-standardized input; this is the host-side step.
    """
    mean = jnp.mean(X, axis=0)
    std = jnp.std(X, axis=0)
    std = jnp.where(std < eps, 1.0, std)
    return (X - mean) / std, mean, std


@jax.named_scope("covariance")
def covariance(X, normalize: bool = False) -> jnp.ndarray:
    """C = X^T X (paper eq. 2); ``normalize`` divides by (M - 1)."""
    C = X.T @ X
    if normalize:
        C = C / jnp.maximum(X.shape[0] - 1, 1)
    return C


@jax.named_scope("covariance")
def blocked_covariance(
    X,
    block_m: int = 128,
    matmul_fn: Optional[Callable] = None,
    normalize: bool = False,
    precision: str = "fp32",
) -> jnp.ndarray:
    """Stream sample blocks of T rows, accumulating partial products --
    the MM-Engine dataflow (matrix accumulators keep the output tile
    stationary while operand tiles stream through).

    ``precision`` names the matmul precision of the default datapath
    (``repro.core.precision``); an injected ``matmul_fn`` sets its own.
    """
    mm = matmul_fn or functools.partial(
        jnp.matmul, precision=matmul_precision(precision))
    m, n = X.shape
    pad = (-m) % block_m
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
    nblocks = X.shape[0] // block_m
    Xb = X.reshape(nblocks, block_m, n)

    def body(acc, xb):
        return acc + mm(xb.T, xb), None

    # first block initialises the accumulator (keeps the carry type
    # data-derived, so the scan also works inside shard_map)
    init = mm(Xb[0].T, Xb[0])
    if nblocks > 1:
        C, _ = jax.lax.scan(body, init, Xb[1:])
    else:
        C = init
    if normalize:
        C = C / jnp.maximum(m - 1, 1)
    return C


def distributed_covariance(
    X,
    mesh: Mesh,
    data_axis: str = "data",
    matmul_fn: Optional[Callable] = None,
    block_m: int = 128,
) -> jnp.ndarray:
    """Block streaming across the mesh: rows sharded over ``data_axis``;
    each shard runs the local MM-Engine accumulation, then one psum
    completes C.  The result is replicated (C is small: d x d)."""

    def local(x):
        c = blocked_covariance(x, block_m=block_m, matmul_fn=matmul_fn)
        return jax.lax.psum(c, axis_name=data_axis)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=P(data_axis, None),
        out_specs=P(),
        check_vma=True,
    )
    return fn(X)
