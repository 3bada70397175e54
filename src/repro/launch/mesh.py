"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The single-pod production mesh is 16x16
(256 chips, v5e pod); multi-pod adds a leading "pod" axis (2x16x16 = 512).
"""
from __future__ import annotations

import jax

from repro.parallel.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
