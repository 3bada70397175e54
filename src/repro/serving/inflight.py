"""In-flight flushes: the pipeline stage between dispatch and retire.

MANOJAVAM's throughput hinges on keeping the S systolic arrays busy while
the memory hierarchy streams the next block in -- the paper's
block-streaming MM path exists to hide data movement behind compute.  The
serving engine mirrors that with a three-stage software pipeline:

  dispatch   stack / pad / compile / launch.  Non-blocking: JAX async
             dispatch returns device futures the moment the computation is
             enqueued, so the host immediately goes back to batching.
  in-flight  a bounded, dispatch-ordered queue of ``InFlightFlush``
             handles.  ``ready()`` is the completion detector (no host
             block); the bound (``PCAServer(max_inflight=...)``) is the
             back-pressure valve that keeps memory and queueing honest.
  retire     block until the device is done, force one flush's results
             to host (a single gather), unpack them into tickets, record
             telemetry.

``InFlightFlush`` is created by an executor (``sharded.LocalExecutor
.submit`` / ``MeshExecutor.submit``) around the raw device output tree;
the engine then annotates it with its bookkeeping (which requests rode the
flush, dispatch timestamp, cache/backend/shard facts) and links
``retire()`` back to its own retire stage, so a ``Ticket`` can force
exactly its own flush home without draining the whole server.

Retirement is *ordered*: the queue always offers flushes oldest-first
(dispatch order), so blocking back-pressure drains deterministically, while
``retire_ready`` lets later flushes that finished early retire out of
dispatch order -- each flush only fulfils its own tickets, so out-of-order
completion is safe by construction.

With ``max_inflight=1`` the pipeline degrades exactly to the synchronous
flush the engine had before this stage existed: every dispatch is
immediately followed by the blocking retirement of the flush it launched.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np
import jax


def _leaf_ready(leaf) -> bool:
    """Non-blocking per-leaf completion probe (True when unknowable)."""
    probe = getattr(leaf, "is_ready", None)
    return bool(probe()) if probe is not None else True


class InFlightFlush:
    """Handle for one dispatched microbatch awaiting retirement.

    Executors construct it around the just-launched device output tree;
    the engine attaches its bookkeeping at dispatch time.  The device
    buffers are gathered to host exactly once (``result``), then released.
    """

    __slots__ = ("seq", "key", "entries", "t_dispatch", "t_put",
                 "t_launched", "stack_s", "lookup_s", "backend",
                 "batch_size", "padded_batch", "slab", "slab_reused",
                 "cache_hit", "inflight_depth", "n_shards", "retired",
                 "span_id", "_out", "_host", "_retire_cb")

    def __init__(self, out, n_shards: int = 1):
        self._out = out            # device result tree (async futures)
        self._host = None          # host copy, gathered once on demand
        self.n_shards = n_shards
        self.retired = False
        # engine bookkeeping, attached by PCAServer at dispatch time
        self.seq = -1
        self.key: Optional[Tuple] = None
        self.entries: Tuple = ()
        self.t_dispatch = 0.0      # dispatch stage began (pre-stack)
        self.t_put = 0.0           # slab handed to the device (executor)
        self.t_launched = 0.0      # executable call returned (host free)
        self.stack_s = 0.0         # host time stacking and padding
        self.lookup_s = 0.0        # host time finding the executable
        self.backend: Optional[str] = None
        self.batch_size = 0
        self.padded_batch = 0      # device batch after padding/rounding
        self.slab = None           # host staging slab (batching.StagingSlab)
        self.slab_reused = False   # the slab was kept from an earlier flush
        self.cache_hit = False
        self.inflight_depth = 1
        self.span_id: Optional[int] = None  # reserved flush-span id (obs)
        self._retire_cb: Optional[Callable] = None

    def ready(self) -> bool:
        """Completion detection without blocking the host."""
        if self.retired or self._host is not None:
            return True
        return all(_leaf_ready(leaf) for leaf in jax.tree.leaves(self._out))

    def block_until_ready(self) -> "InFlightFlush":
        """Block until the device batch finished (results stay on device)."""
        if not self.retired and self._host is None:
            jax.block_until_ready(self._out)
        return self

    def result(self):
        """The flush's results as one host tree (blocks until complete).

        The whole tree is gathered in a single transfer -- per-request
        slicing happens on the host copy (slicing a device array per
        ticket is O(batch) dispatches, and on a sharded array each one is
        a cross-device gather; see ``sharded.LocalExecutor``).
        """
        if self._host is None:
            self._host = jax.tree.map(np.asarray, self._out)
            self._out = None       # release the device buffers
        return self._host

    def retire(self) -> int:
        """Force this flush through its engine's retire stage.

        Idempotent; returns the number of requests it fulfilled (0 when
        already retired).  Raises if the flush was never attached to an
        engine (executor-level use: call ``result()`` instead).
        """
        if self._retire_cb is None:
            raise RuntimeError(
                "flush is not attached to an engine; use result() for the "
                "raw device batch")
        return self._retire_cb(self)


class InFlightQueue:
    """Dispatch-ordered set of in-flight flushes (the retire stage inbox).

    The engine owns the bound (``max_inflight``); the queue owns ordering
    and the two retirement sweeps: ``retire_ready`` (free -- whatever the
    device already finished, oldest-first) and ``retire_to_depth``
    (blocking back-pressure -- oldest-first until the cap holds).
    """

    def __init__(self):
        self._flushes: List[InFlightFlush] = []

    def __len__(self) -> int:
        return len(self._flushes)

    def __iter__(self):
        return iter(list(self._flushes))

    @property
    def depth(self) -> int:
        return len(self._flushes)

    def requests(self) -> int:
        """Requests riding the currently in-flight flushes."""
        return sum(len(f.entries) for f in self._flushes)

    def push(self, flush: InFlightFlush) -> None:
        self._flushes.append(flush)

    def remove(self, flush: InFlightFlush) -> None:
        self._flushes.remove(flush)

    def oldest(self) -> Optional[InFlightFlush]:
        return self._flushes[0] if self._flushes else None

    def retire_ready(self) -> int:
        """Retire every already-completed flush (non-blocking sweep).

        Oldest-first, but a young finished flush does not wait for an old
        unfinished one -- that is the out-of-order half of the pipeline.
        Returns the number of requests fulfilled.
        """
        done = 0
        for flush in list(self._flushes):
            if flush.ready():
                done += flush.retire()
        return done

    def retire_to_depth(self, depth: int) -> int:
        """Blocking back-pressure: retire oldest-first until at most
        ``depth`` flushes remain in flight.  ``depth=0`` drains the stage.
        Returns the number of requests fulfilled."""
        done = 0
        while len(self._flushes) > depth:
            done += self._flushes[0].retire()
        return done
