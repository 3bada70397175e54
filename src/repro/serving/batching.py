"""Shape bucketing and padding: the software Matrix Padding Unit.

The hardware MPU (paper Sec. VI) zero-pads any input up to the next multiple
of the tile size T so a fixed (T, S) fabric can consume "datasets of any
input dimension".  In the serving engine the same trick makes *heterogeneous
traffic batchable*: every incoming matrix is padded up to a T-multiple
bucket, and up to S same-bucket requests stack into one device batch that a
single compiled executable consumes.  Zero padding is exact for the Jacobi
solvers -- see ``core.jacobi._null_pivot_guard`` -- so the bucket never
perturbs the embedded problem.

Two bucket policies:

  * ``"tile"`` -- round each dim up to the next multiple of T.  Minimal
    padding waste, but heterogeneous traffic spreads across many buckets
    (fewer batching opportunities, more executables).
  * ``"pow2"`` -- round the *tile count* up to the next power of two
    (bucket edges T, 2T, 4T, 8T, ...).  Geometric bucketing: more padding
    waste per request, but O(log) distinct buckets, so mixed traffic
    coalesces into full batches and the executable cache stays tiny.

``pow2_cap`` bounds the geometric growth: bucket edges run T, 2T, 4T, ...
up to the cap, and any dimension whose power-of-two bucket would overshoot
it falls back to linear tile rounding.  Geometric padding waste compounds
with the bucket edge (a dim just past cap/2 pays ~2x area), so capping the
doubling where traffic is sparse is one of the knobs the serving-plan
autotuner (``serving.autotune``) searches over.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

POLICIES = ("tile", "pow2")


@dataclasses.dataclass(frozen=True)
class BucketPolicy:
    T: int = 16            # tile edge (paper T); bucket dims are multiples
    mode: str = "tile"     # "tile" | "pow2"
    pow2_cap: Optional[int] = None  # pow2 mode: largest geometric bucket
                                    # edge; beyond it, linear tile rounding

    def __post_init__(self):
        if self.mode not in POLICIES:
            raise ValueError(f"unknown bucket mode {self.mode!r}")
        if self.T < 1:
            raise ValueError("bucket tile size must be >= 1")
        if self.pow2_cap is not None:
            if self.mode != "pow2":
                raise ValueError("pow2_cap only applies to the pow2 mode")
            if self.pow2_cap < self.T or self.pow2_cap % self.T:
                raise ValueError(
                    f"pow2_cap must be a multiple of T={self.T} "
                    f"(got {self.pow2_cap})")

    def bucket_dim(self, n: int) -> int:
        """Smallest bucket edge that holds a dimension of size n."""
        if n < 1:
            raise ValueError("matrix dimensions must be >= 1")
        tiles = math.ceil(n / self.T)
        if self.mode == "pow2":
            p2 = 1 << (tiles - 1).bit_length()
            if self.pow2_cap is None or p2 * self.T <= self.pow2_cap:
                tiles = p2
        return tiles * self.T

    def bucket_shape(self, shape: Sequence[int]) -> Tuple[int, ...]:
        return tuple(self.bucket_dim(int(d)) for d in shape)


def pad_to_bucket(a: np.ndarray, bucket: Sequence[int]) -> np.ndarray:
    """Zero-pad a matrix into its bucket (the MPU's zero fill)."""
    a = np.asarray(a)
    if len(bucket) != a.ndim:
        raise ValueError(f"bucket rank {len(bucket)} != matrix rank {a.ndim}")
    pads = []
    for d, b in zip(a.shape, bucket):
        if d > b:
            raise ValueError(f"matrix dim {d} exceeds bucket dim {b}")
        pads.append((0, b - d))
    if any(p for _, p in pads):
        a = np.pad(a, pads)
    return a


def stack_requests(mats: Sequence[np.ndarray], bucket: Sequence[int]):
    """Stack same-bucket matrices into one device batch.

    Returns ``(batch, n_active)`` where ``batch`` is (B, *bucket) and
    ``n_active`` is a (rank, B) int32 array of true sizes per axis --
    the masks the batched solvers use to keep padded coordinates inert.
    """
    batch = np.stack([pad_to_bucket(m, bucket) for m in mats])
    n_active = np.asarray([[m.shape[ax] for m in mats]
                           for ax in range(len(bucket))], dtype=np.int32)
    return batch, n_active


def _zero_outside(slot: np.ndarray, old: Tuple[int, ...],
                  new: Tuple[int, ...]) -> None:
    """Zero the part of the box ``[0, old)`` of ``slot`` that lies outside
    the box ``[0, new)``: one slice per axis where ``old`` reaches past
    ``new``, the slices disjoint (axes before it clipped to both boxes)."""
    for ax in range(len(old)):
        if old[ax] > new[ax]:
            idx = (tuple(slice(0, min(o, n))
                         for o, n in zip(old[:ax], new[:ax]))
                   + (slice(new[ax], old[ax]),)
                   + tuple(slice(0, o) for o in old[ax + 1:]))
            slot[idx] = 0


class StagingSlab:
    """One host batch slab, (slots, *bucket), zero outside its live data.

    ``extents[i]`` is the shape the last occupant of slot ``i`` wrote
    (``None``: the slot is all zero), so a refill zeroes only what the new
    occupant does not overwrite.  ``batch`` is the leading part of
    ``array`` the last ``fill`` staged: the flush's padded batch, what
    the device is given.
    """

    __slots__ = ("key", "array", "extents", "batch")

    def __init__(self, key: Tuple, bucket: Tuple[int, ...], slots: int,
                 dtype):
        self.key = key
        self.array = np.zeros((slots, *bucket), dtype)
        self.extents: list = [None] * slots
        self.batch = self.array

    def fill(self, mats: Sequence[np.ndarray], bp: int) -> None:
        """Write ``mats`` into the leading slots and make the rest of the
        first ``bp`` filler.  Slots past ``bp`` keep what they held: no
        flush of this batch reads them, and a later fill that reaches
        them zeroes what it must."""
        bucket = self.array.shape[1:]
        if len(mats) > bp or bp > len(self.extents):
            raise ValueError(f"{len(mats)} matrices in a batch of {bp} "
                             f"for a slab of {len(self.extents)} slots")
        for m in mats:
            if m.ndim != len(bucket) or any(
                    d > b for d, b in zip(m.shape, bucket)):
                raise ValueError(
                    f"matrix shape {m.shape} does not fit bucket {bucket}")
        for i in range(bp):
            slot, extent = self.array[i], self.extents[i]
            new = mats[i].shape if i < len(mats) else None
            if new is not None:
                slot[tuple(slice(0, d) for d in new)] = mats[i]
            if extent is not None:
                _zero_outside(slot, extent, new or (0,) * slot.ndim)
            self.extents[i] = new
        self.batch = self.array[:bp]


class StagingPool:
    """Reused, already-zeroed host slabs for the dispatch stage.

    A padded slab built afresh for every flush is allocated, faulted in
    and written whole; for a 70000x784 request at a batch of 4 that is
    about 1.1 GB, nearly all of it zeros the previous flush had already
    written.  The pool keeps slabs of (bucket, slots, dtype) and writes
    each flush's requests into a free one of its key in place; the
    flush's padded batch ``bp`` is the slab's first ``bp`` slots
    (``StagingSlab.batch``), so the server gives every slab of a bucket
    the slots of its largest rung and flushes of every rung share them.
    A slab's batch equals ``stack_requests`` plus zero filler byte for
    byte, the reference the tests hold it to.

    A slab is taken at dispatch and given back (``release``) once its
    flush has retired, never while a flush that read it is in flight: the
    host-to-device copy may still run after the put returns, and on the
    CPU the device array may alias the host buffer.  So the slab itself
    must never be donated to an executable; only the device copy made
    from it may be (``sharded._donate_kwargs``).

    At most ``max_free`` slabs are kept free, over all keys together, the
    least recently released dropped first; the server passes its
    ``max_inflight``, so traffic that moves between buckets keeps no more
    host slabs than one bucket would.
    """

    def __init__(self):
        self._free: list = []      # least recently released first

    def take(self, mats: Sequence[np.ndarray], bucket: Sequence[int],
             bp: int, slots: Optional[int] = None
             ) -> Tuple[StagingSlab, bool]:
        """A slab of ``slots`` slots (default ``bp``) whose batch holds
        ``mats`` in its first slots and zero filler up to ``bp``, and
        whether it was reused (False: freshly allocated)."""
        bucket = tuple(int(d) for d in bucket)
        key = (bucket, max(slots or bp, bp), np.result_type(*mats))
        for i in range(len(self._free) - 1, -1, -1):
            if self._free[i].key == key:
                slab, reused = self._free.pop(i), True
                break
        else:
            slab, reused = StagingSlab(key, bucket, key[1], key[2]), False
        slab.fill(mats, bp)
        return slab, reused

    def release(self, slab: StagingSlab, max_free: int) -> None:
        """Give back the slab of a retired flush, keeping the ``max_free``
        most recently released slabs."""
        self._free.append(slab)
        del self._free[:-max_free]


def batch_rungs(max_batch: int,
                round_batch: Callable[[int], int]) -> Tuple[int, ...]:
    """The padded batches a bucket's flushes dispatch at with
    ``pad_batches``, ascending: the powers of two below ``max_batch`` and
    ``max_batch`` itself, each passed through the executor's
    ``round_batch`` (a mesh's data-axis multiple), without repeats.  A
    flush of b live requests runs at the smallest rung that holds b, so
    a lone request is not solved beside ``max_batch - 1`` zero matrices,
    and a bucket needs one executable per rung."""
    sizes = {1 << i for i in range((max_batch - 1).bit_length())}
    return tuple(sorted({round_batch(b) for b in sizes | {max_batch}}))


def padding_waste(shape: Sequence[int], bucket: Sequence[int]) -> float:
    """Fraction of the bucket area occupied by padding (0 = exact fit)."""
    true = float(np.prod([int(d) for d in shape]))
    padded = float(np.prod([int(b) for b in bucket]))
    return 1.0 - true / padded
