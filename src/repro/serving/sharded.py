"""Flush executors: where a bucket's microbatch actually runs.

The paper scales MANOJAVAM by replicating S systolic arrays behind one
fabric; ``MeshExecutor`` is the next rung of that ladder -- replicate the
*whole fabric* across a device mesh and shard the microbatch (S) axis over
it, so one flush retires ``S x n_devices`` requests.  ``PCAServer`` owns
queueing, bucketing and deadlines and delegates compile/placement/dispatch
to an executor:

  * ``LocalExecutor`` -- the original single-device path: plain ``jax.jit``
    per (op, bucket, batch, config).  The default; zero distribution cost.
  * ``MeshExecutor`` -- owns a ``jax.sharding.Mesh`` and jits the batched
    solvers with batch-axis ``NamedSharding`` in/out specs resolved through
    the ``parallel.sharding`` ``Rules`` machinery ("batch" role -> data
    axis).  Partial flushes are padded up to a multiple of the data-axis
    size so every shard receives an identical slab and the executable never
    sees a ragged batch.

Executables cache under a key that includes ``cache_token()`` (mesh axis
sizes + device ids), so one server can swap meshes -- or route some buckets
locally and others onto the mesh -- without ever reusing an executable
compiled for different placement.

The executor seam is where the "async device streams" follow-on landed:
``submit`` launches a flush without blocking (JAX async dispatch returns
device futures the moment the computation is enqueued) and hands back an
``inflight.InFlightFlush`` whose ``ready()``/``result()`` the engine's
in-flight and retire stages drive.  ``run`` remains as the blocking
compatibility path -- exactly ``submit(...).result()``.
"""
from __future__ import annotations

import time
from typing import Callable, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core.pca import PCAConfig
from repro.obs.tracing import stage
from repro.parallel.sharding import (batch_axes, pad_to_multiple,
                                     rules_for_mesh)
from .cache import SolverKey
from .inflight import InFlightFlush
from .solver import build_solver_fn


def solver_structs(bucket: Tuple[int, ...],
                   batch: int) -> Tuple[jax.ShapeDtypeStruct, ...]:
    """Abstract input signature of one flush: the padded slab plus one
    int32 per-problem true-size vector per bucket dimension (the uniform
    ``build_solver_fn`` calling convention)."""
    return (
        jax.ShapeDtypeStruct((batch, *bucket), jnp.float32),
        *(jax.ShapeDtypeStruct((batch,), jnp.int32) for _ in bucket),
    )


def _donate_kwargs() -> dict:
    """Donate the flush's input slab to its executable.

    The donated buffer is the device copy ``submit`` makes of the host
    slab, which no later flush reads, so XLA may alias it for outputs --
    one less allocation per in-flight flush, which is what keeps a deep
    pipeline's memory footprint flat on accelerators.  The host slab
    itself is reused once its flush retires (``batching.StagingPool``),
    so donation must never reach it: on the CPU the device array may
    alias the host buffer, and CPU PJRT cannot alias host buffers anyway
    (it logs a warning per compiled executable), so donation is reserved
    for real device backends.
    """
    if jax.default_backend() == "cpu":
        return {}
    return {"donate_argnums": (0,)}


class LocalExecutor:
    """Single-device flush execution (the seed behavior).

    Near-stateless: the engine owns the executable cache; the executor
    decides batch rounding, compilation and dispatch, and memoizes its
    shape-polymorphic ``jax.jit`` wrappers per solver (see ``compile``).
    """

    n_shards: int = 1

    def cache_token(self):
        """Executor identity mixed into the engine's executable-cache key."""
        return None

    def round_batch(self, b: int) -> int:
        """Device batch the engine must pad a b-request flush up to."""
        return b

    def compile(self, op: str, config: PCAConfig,
                bucket: Tuple[int, ...], batch: int) -> Callable:
        del bucket, batch  # single device: shape-polymorphic jit is enough
        # one wrapper per solver, NOT per call: the engine's cache keys on
        # (op, bucket, batch, ...) and used to receive a fresh jit wrapper
        # for every key -- so two batch sizes of one bucket (or two buckets
        # of one solver) each re-built and re-traced an identical solver
        # closure with its own private jit trace cache.  Memoizing on the
        # solver identity hands every key the *same* wrapper, whose shared
        # trace cache compiles each distinct input shape exactly once no
        # matter how many engine keys route through it.
        memo = self.__dict__.setdefault("_solvers", {})
        key = (op, SolverKey.from_config(config))
        fn = memo.get(key)
        if fn is None:
            fn = memo[key] = jax.jit(build_solver_fn(op, config),
                                     **_donate_kwargs())
        return fn

    def aot_compile(self, op: str, config: PCAConfig,
                    bucket: Tuple[int, ...], batch: int):
        """Ahead-of-time compile one concrete (bucket, batch) executable.

        The ``jax.stages.Compiled`` this returns is what the persistent
        cache tier serializes (``serving.cache.DiskCache``) and what a
        ``pad_batches`` server builds for every rung of a bucket: calling
        it runs zero tracing and zero XLA work.  It shares the memoized
        polymorphic wrapper, so a later same-shape JIT call reuses the
        identical compilation.
        """
        return self.compile(op, config, bucket, batch).lower(
            *solver_structs(bucket, batch)).compile()

    def submit(self, fn: Callable, batch, n_active,
               clock: Callable[[], float] = time.monotonic,
               start: Optional[float] = None) -> InFlightFlush:
        """Launch a flush without blocking (the pipeline's dispatch stage).

        JAX async dispatch returns the output tree as device futures, so
        the host goes straight back to batching while the device crunches.
        The returned handle exposes ``ready()`` for completion detection
        and ``result()`` for the single host gather -- per-request slicing
        happens on the host copy, because slicing a device array per ticket
        is O(batch) dispatches, and on a sharded array each one is a
        cross-device gather that costs more than the flush's compute
        (measured ~3x the solve time at 8 host devices).

        The copy in (``put``) and the call (``launch``) are timed as two
        stages on ``clock``, from ``start`` when the caller has the stamp;
        the handle carries their ends as ``t_put`` and ``t_launched``.
        """
        with stage("put", clock, start) as put:
            args = self.place(batch, n_active)
        with stage("launch", clock, put.end) as launch:
            out = fn(*args)
        flush = InFlightFlush(out, n_shards=self.n_shards)
        flush.t_put, flush.t_launched = put.end, launch.end
        return flush

    def place(self, batch, n_active) -> Tuple[jax.Array, ...]:
        """Copy one flush's slab and its true-size vectors to the device."""
        return (jnp.asarray(batch), *map(jnp.asarray, n_active))

    def run(self, fn: Callable, batch, n_active):
        """Blocking compatibility path: ``submit(...).result()``."""
        return self.submit(fn, batch, n_active).result()

    def describe(self) -> str:
        return "local(1 device)"


class MeshExecutor(LocalExecutor):
    """Shard the flush's batch (S) axis across a named device mesh.

    Args:
      mesh: mesh to run on; ``data_axis`` must be one of its axis names.
        Default: a 1-D "data" mesh over ``devices`` (or every visible
        device), i.e. pure data parallelism over the sample axis -- the
        regime where PCA throughput actually scales (Martel et al.).
      devices: devices for the default mesh (ignored when ``mesh`` given).
      data_axis: mesh axis the batch dim shards over.

    Each problem in the batch lives entirely on one shard (the batch dim
    is the only sharded dim), so a sharded flush runs the same math as the
    single-device flush on every problem.  It is a separate compile,
    though, and XLA may fuse and round it differently: results agree to
    rounding (relative 1e-5), not bit for bit, and the solver's canonical
    eigenvector sign keeps columns from flipping -- parity is tested per
    op in ``tests/test_sharded_serving.py``.
    """

    def __init__(self, mesh: Optional[Mesh] = None,
                 devices: Optional[Sequence] = None,
                 data_axis: str = "data"):
        if mesh is None:
            devs = list(devices if devices is not None else jax.devices())
            mesh = Mesh(np.asarray(devs), (data_axis,))
        if data_axis not in mesh.axis_names:
            raise ValueError(
                f"data_axis {data_axis!r} not in mesh axes {mesh.axis_names}")
        self.mesh = mesh
        self.data_axis = data_axis
        self.rules = rules_for_mesh(mesh)
        axes = self.rules.axis("batch")
        if not axes or data_axis not in (
                (axes,) if isinstance(axes, str) else tuple(axes)):
            raise ValueError(
                "the batch role must resolve onto the data axis; name the "
                f"mesh axis 'data' (got mesh axes {mesh.axis_names})")
        self.n_shards = int(np.prod(
            [mesh.shape[a] for a in ((axes,) if isinstance(axes, str)
                                     else axes)]))

    def cache_token(self):
        # axis sizes + concrete device ids: same-shaped meshes over
        # different devices must not share executables
        return ("mesh", tuple(self.mesh.shape.items()),
                tuple(d.id for d in self.mesh.devices.flat))

    def round_batch(self, b: int) -> int:
        return pad_to_multiple(max(b, 1), self.n_shards)

    def compile(self, op: str, config: PCAConfig,
                bucket: Tuple[int, ...], batch: int) -> Callable:
        if batch % self.n_shards:
            raise ValueError(
                f"batch {batch} not a multiple of the data-axis size "
                f"{self.n_shards}; round with round_batch() first")
        fn = build_solver_fn(op, config)
        in_struct = solver_structs(bucket, batch)
        out_struct = jax.eval_shape(fn, *in_struct)
        in_sh = self.rules.sharding_tree(batch_axes(in_struct), self.mesh)
        out_sh = self.rules.sharding_tree(batch_axes(out_struct), self.mesh)
        return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                       **_donate_kwargs())

    def place(self, batch, n_active) -> Tuple[jax.Array, ...]:
        """Copy the flush straight onto the data axis, in the shardings
        the executable was compiled with: an ahead-of-time executable
        given single-device arrays would compile a reshard on its first
        call."""
        arrays = (batch, *n_active)
        return jax.device_put(arrays, self.rules.sharding_tree(
            batch_axes(arrays), self.mesh))

    def describe(self) -> str:
        shape = "x".join(f"{k}={v}" for k, v in self.mesh.shape.items())
        return f"mesh({shape}; {self.n_shards} shards)"


def host_mesh(n_devices: Optional[int] = None,
              data_axis: str = "data") -> Mesh:
    """A 1-D data mesh over the first ``n_devices`` visible devices
    (None/0 = all).  Asking for more devices than are visible raises:
    a run that asked for N devices and got fewer would report numbers
    for a mesh it never ran on."""
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(
            f"a {n}-device mesh was requested but only {len(devs)} "
            f"{devs[0].platform} device(s) are visible")
    return Mesh(np.asarray(devs[:n]), (data_axis,))


def mesh_executor(spec) -> LocalExecutor:
    """Executor from a CLI-style mesh spec.

    ``None``/``"none"``/``"1"`` -> ``LocalExecutor``; ``"auto"`` -> a mesh
    over every visible device; an int(-string) N -> a mesh over the first
    N devices (``ValueError`` when fewer are visible).
    """
    if spec is None or spec in ("none", "local"):
        return LocalExecutor()
    if spec == "auto":
        n = None
    else:
        n = int(spec)
        if n <= 1:
            return LocalExecutor()
    return MeshExecutor(mesh=host_mesh(n))
