"""Pallas TPU kernels for the perf-critical compute layers.

  mm_engine       -- block-streaming tiled matmul (the paper's MM-Engine)
  dle             -- single-scan max-|off-diagonal| pivot search (DLE)
  cordic          -- fixed-point rotation-parameter pipeline
  flash_attention -- online-softmax blockwise attention (framework hot spot)
  mamba_scan      -- chunked selective-scan for SSM architectures

Import ``repro.kernels.ops`` for the jit'd padded wrappers (each dispatches
through the ``repro.backends`` registry to a ``pallas`` / ``interpret`` /
``ref`` implementation) and ``repro.kernels.ref`` for the pure-jnp oracles.
``repro.kernels.compat`` is the one import point of the Pallas TPU API;
kernel modules must import ``pl`` / memory spaces / compiler params from it
rather than from ``jax.experimental.pallas.tpu`` directly.
"""
from . import compat, ops, ref  # noqa: F401
