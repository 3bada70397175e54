"""The single import point for the Pallas TPU API surface.

Every kernel module imports ``pl`` and builds its ``compiler_params`` and
scratch memory spaces through this module -- it is the ONLY place in the
repo that imports ``jax.experimental.pallas.tpu`` directly, so a future API
move is a one-file fix.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from jax.experimental import pallas as pl  # noqa: F401  (re-exported)
from jax.experimental.pallas import tpu as pltpu

# scratch memory spaces, re-exported so kernels never touch pltpu directly
VMEM = pltpu.VMEM
SMEM = pltpu.SMEM


def compiler_params(
    dimension_semantics: Optional[Sequence[str]] = None,
    **kwargs: Any,
) -> Dict[str, Any]:
    """kwargs for ``pl.pallas_call`` selecting the TPU compiler parameters.

    Returns ``{"compiler_params": pltpu.CompilerParams(...)}`` (or ``{}``
    when nothing was requested) so call sites splat it:

        pl.pallas_call(kernel, ..., **compat.compiler_params(
            dimension_semantics=("parallel", "arbitrary")))
    """
    if dimension_semantics is None and not kwargs:
        return {}
    dims = tuple(dimension_semantics) if dimension_semantics else None
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=dims, **kwargs)}


def describe() -> str:
    """One-line API summary for CI logs."""
    import jax
    return f"jax {jax.__version__}: compiler params via pltpu.CompilerParams"
