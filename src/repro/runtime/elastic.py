"""Elastic restart: resume training on whatever mesh is currently healthy.

Checkpoints store *logical* (global) arrays, so resuming only needs a new
sharding tree for the new mesh -- ``checkpointer.restore`` device_puts each
leaf onto it.  ``pick_mesh`` chooses the largest (data x model) grid the
surviving device set supports with model-dim divisibility constraints, and
``resume_or_init`` wires it together.  Data-pipeline cursors live in
checkpoint metadata, so no examples are skipped or repeated on restart.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.checkpoint import checkpointer
from repro.parallel.sharding import make_mesh


def pick_mesh(model_parallel: int, devices=None, global_batch=None):
    """Largest (data, model) mesh over the available devices.

    ``global_batch`` caps the data axis: batch-dim sharding needs
    ``global_batch % dp == 0``, so dp shrinks to the largest divisor of the
    batch that the devices support (a reduced 4-sample smoke on an 8-device
    host gets a (4, tp) mesh and leaves the surplus devices idle, instead
    of failing the divisibility check at dispatch).
    """
    devices = devices if devices is not None else jax.devices()
    n = len(devices)
    tp = model_parallel
    while tp > 1 and (n % tp or model_parallel % tp):
        tp -= 1
    dp = n // tp
    if global_batch is not None:
        dp = min(dp, global_batch)
        while dp > 1 and global_batch % dp:
            dp -= 1
    return make_mesh((dp, tp), ("data", "model"),
                     devices=devices[: dp * tp])


def resume_or_init(ckpt_dir, state_like, shardings, init_fn,
                   step: Optional[int] = None):
    """Restore the latest checkpoint onto the current mesh, or initialise.

    Returns (state, metadata, resumed: bool).
    """
    latest = checkpointer.latest_step(ckpt_dir)
    if latest is None:
        return init_fn(), {}, False
    state, meta = checkpointer.restore(ckpt_dir, state_like, step=step,
                                       shardings=shardings)
    return state, meta, True
