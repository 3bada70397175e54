"""Executable cache: the in-memory LRU tier and the persistent AOT tier.

MANOJAVAM answers MM+SVD traffic at fixed latency from cycle one because
the fabric is *pre-built*; a software replica that JIT-compiles on first
request serves its first minutes at compile speed instead -- fatal for
elastic scale-out, where a fresh replica is spawned precisely because
traffic already exceeds capacity.  This module closes that gap with two
cooperating tiers under ``PCAServer._cache``:

  memory  ``LRUCache`` -- the compiled-callable map the engine always had,
          now bounded: a long-lived server under the autotuner used to
          leak every executable of every plan it ever ran (each
          ``apply_plan`` re-aligned the config and minted fresh keys);
          the cap evicts least-recently-dispatched entries instead.
  disk    ``DiskCache`` -- content-hash-keyed AOT executables serialized
          via ``jit(...).lower().compile()`` + ``jax.experimental
          .serialize_executable`` (the pickled-PJRT-binary path; loading
          skips XLA entirely, ~100-1000x faster than a cold compile).
          Writes are atomic (tmpfile in the same directory, then
          ``os.replace``) so two replicas warming one ``--cache-dir``
          concurrently never see a torn file; loads are
          corruption-tolerant (any deserialize failure quarantines the
          entry and falls back to JIT, which then repairs it); the
          directory is size-capped with oldest-access-first eviction.

Keying is the part the old in-memory tier got wrong and that a persistent
tier would have serialized forever: the engine keyed on the *whole*
``PCAConfig``, but the compiled solver only depends on the numerics subset
(sweeps / pivot / rotation / angle / tol / standardize / backend, plus the
matmul block size when a kernel backend is routed).  ``SolverKey`` is that
subset -- two configs that differ only in scheduling facts (T, S) now share
one executable, which is exactly why a plan hot-swap that preserves
bucketing keeps its whole cache.  The disk tier hashes ``SolverKey``
together with (op, bucket, batch, executor token, jax version, device
backend, device kind), so an entry is invalidated -- cleanly, by never
being looked up -- the moment any of those change.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import os
import pathlib
import pickle
import tempfile
from typing import Callable, Dict, Iterator, Optional, Tuple

import jax

# bump when the on-disk record layout changes; part of the content hash so
# old-format entries are simply never looked up again
# (2: SolverKey grew precision + fused -- pre-mixed-precision executables
# must never serve a precision-keyed request; 3: the record header is the
# whole environment fingerprint, device kind included)
CACHE_FORMAT = 3

# default in-memory cap: generous for steady traffic (a few ops x a few
# buckets x a few batches), small enough that a plan-churning server stays
# bounded
DEFAULT_MAX_ENTRIES = 256

DEFAULT_MAX_DISK_BYTES = 1 << 30    # 1 GiB of serialized executables


def environment_fingerprint() -> Tuple[str, str, str]:
    """(jax version, device backend, device kind) -- the facts that
    invalidate every serialized executable at once when they drift.  An
    XLA binary compiled by one jax for one backend must never load into
    another, and one compiled for one TPU generation (``device_kind``,
    e.g. "TPU v5 lite") must never be offered to another."""
    return (jax.__version__, jax.default_backend(),
            jax.devices()[0].device_kind)


@dataclasses.dataclass(frozen=True)
class SolverKey:
    """The PCAConfig subset a compiled solver actually depends on.

    ``build_solver_fn`` reads sweeps/pivot/rotation/angle/tol/standardize
    and routes matmuls through ``backend`` (whose Pallas block size is
    ``block`` = config.T -- only relevant when a kernel backend is set, so
    it is normalized to None on the plain-XLA datapath).  T and S are
    deliberately absent: they are scheduling facts (bucket tile, flush
    size) that reach the executable through (bucket, batch) in the engine
    key, and keying on them fragmented the cache across every
    ``apply_plan`` re-alignment.
    """
    sweeps: int
    tol: Optional[float]
    pivot: str
    rotation: str
    angle: str
    standardize: bool
    backend: Optional[str]
    block: Optional[int]
    # mixed-precision policy and fused-kernel routing both change the
    # compiled executable (operand dtypes / kernel launch structure), so
    # they are key material like the numerics above
    precision: str = "fp32"
    fused: bool = False

    @classmethod
    def from_config(cls, config) -> "SolverKey":
        return cls(
            sweeps=config.sweeps, tol=config.tol, pivot=config.pivot,
            rotation=config.rotation, angle=config.angle,
            standardize=config.standardize, backend=config.backend,
            block=(config.T if config.backend is not None else None),
            precision=getattr(config, "precision", "fp32"),
            fused=getattr(config, "fused", False))


def content_hash(op: str, bucket: Tuple[int, ...], batch: int,
                 solver: SolverKey, exec_token) -> str:
    """Stable content address of one executable.

    Everything that changes the compiled binary is in the digest: the op,
    the concrete shapes (bucket, batch), the solver numerics, the
    executor placement token (mesh axes + device ids for a mesh), the
    jax version, the device backend and kind, and the record format.  A
    mismatch in any of them lands on a different file -- stale entries are
    never loaded, only eventually evicted by the size cap.
    """
    material = repr((CACHE_FORMAT, op, tuple(bucket), int(batch),
                     dataclasses.astuple(solver), exec_token,
                     environment_fingerprint()))
    return hashlib.sha256(material.encode()).hexdigest()


class LRUCache:
    """Bounded mapping with least-recently-used eviction.

    The engine's in-memory executable tier.  Reads refresh recency (a
    steadily-hit executable never ages out); writes beyond ``max_entries``
    evict the coldest entry.  ``max_entries=None`` is unbounded (the old
    behavior, kept for tests that count entries exactly).
    """

    def __init__(self, max_entries: Optional[int] = DEFAULT_MAX_ENTRIES,
                 on_evict: Optional[Callable] = None):
        if max_entries is not None and max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = max_entries
        self.evictions = 0
        self._on_evict = on_evict
        self._data: "collections.OrderedDict" = collections.OrderedDict()

    def __len__(self) -> int:
        return len(self._data)

    def __iter__(self) -> Iterator:
        return iter(list(self._data))

    def __contains__(self, key) -> bool:
        return key in self._data

    def __getitem__(self, key):
        value = self._data[key]
        self._data.move_to_end(key)
        return value

    def get(self, key, default=None):
        if key not in self._data:
            return default
        return self[key]

    def __setitem__(self, key, value) -> None:
        self._data[key] = value
        self._data.move_to_end(key)
        while (self.max_entries is not None
               and len(self._data) > self.max_entries):
            old_key, old_value = self._data.popitem(last=False)
            self.evictions += 1
            if self._on_evict is not None:
                self._on_evict(old_key, old_value)

    def clear(self) -> None:
        self._data.clear()


class DiskCache:
    """Content-addressed directory of serialized AOT executables.

    One file per executable: ``<sha256>.jexec`` holding a pickled record
    ``{"format", "env", "payload", "in_tree", "out_tree"}``
    (the ``serialize_executable.serialize`` triple plus the header that
    lets a loader reject an entry copied across environments even when the
    file name happens to match).  All failure modes degrade to a miss:

      * write: serialized to a ``tempfile`` in the cache directory, then
        ``os.replace``d into place -- readers see the old bytes or the new
        bytes, never a prefix, so concurrent warmers are safe.
      * read: any exception (truncated pickle, header mismatch, PJRT
        deserialize failure) quarantines the file (best-effort unlink) and
        returns None; the caller JIT-compiles and re-``put``s, repairing
        the entry.
      * size: after each write the directory is evicted down to
        ``max_bytes``, oldest access first (POSIX atime is unreliable, so
        eviction uses mtime and ``get`` re-touches on hit).
    """

    SUFFIX = ".jexec"

    def __init__(self, cache_dir,
                 max_bytes: int = DEFAULT_MAX_DISK_BYTES):
        self.dir = pathlib.Path(cache_dir)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.max_bytes = max_bytes
        self.hits = 0
        self.misses = 0
        self.stores = 0
        self.errors = 0        # corrupt/mismatched entries quarantined

    def _path(self, key_hash: str) -> pathlib.Path:
        return self.dir / f"{key_hash}{self.SUFFIX}"

    def get(self, key_hash: str) -> Optional[Callable]:
        """The deserialized executable, or None (miss / corrupt entry)."""
        path = self._path(key_hash)
        try:
            blob = path.read_bytes()
        except OSError:
            self.misses += 1
            return None
        try:
            record = pickle.loads(blob)
            if (record["format"] != CACHE_FORMAT
                    or tuple(record["env"]) != environment_fingerprint()):
                raise ValueError(
                    f"cache entry from {record.get('env')}, this process "
                    f"is {environment_fingerprint()}")
            from jax.experimental import serialize_executable
            fn = serialize_executable.deserialize_and_load(
                record["payload"], record["in_tree"], record["out_tree"])
        except Exception:
            # corrupt, truncated, version-drifted or undeserializable:
            # quarantine and fall back to JIT (the caller re-puts, which
            # repairs the entry)
            self.errors += 1
            try:
                path.unlink()
            except OSError:
                pass
            return None
        self.hits += 1
        try:                   # refresh recency for mtime-ordered eviction
            os.utime(path)
        except OSError:
            pass
        return fn

    def put(self, key_hash: str, compiled) -> bool:
        """Serialize one AOT executable; atomic, best-effort (a full disk
        or an unserializable executable is a skipped store, not a serving
        failure).  Returns True when the entry landed."""
        try:
            from jax.experimental import serialize_executable
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            blob = pickle.dumps({
                "format": CACHE_FORMAT, "env": environment_fingerprint(),
                "payload": payload,
                "in_tree": in_tree, "out_tree": out_tree,
            })
        except Exception:
            self.errors += 1
            return False
        try:
            fd, tmp = tempfile.mkstemp(dir=self.dir, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, self._path(key_hash))
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
        except OSError:
            self.errors += 1
            return False
        self.stores += 1
        self._evict_to_cap()
        return True

    def entries(self):
        return sorted(self.dir.glob(f"*{self.SUFFIX}"))

    def total_bytes(self) -> int:
        return sum(p.stat().st_size for p in self.entries())

    def _evict_to_cap(self) -> None:
        """Drop oldest-touched entries until the directory fits the cap."""
        try:
            paths = [(p.stat().st_mtime, p.stat().st_size, p)
                     for p in self.entries()]
        except OSError:        # raced a concurrent eviction
            return
        total = sum(size for _, size, _ in paths)
        for _, size, path in sorted(paths, key=lambda t: t[0]):
            if total <= self.max_bytes:
                break
            try:
                path.unlink()
                total -= size
            except OSError:    # another process got there first
                pass

    def summary(self) -> Dict:
        return {
            "dir": str(self.dir),
            "entries": len(self.entries()),
            "bytes": self.total_bytes(),
            "max_bytes": self.max_bytes,
            "hits": self.hits, "misses": self.misses,
            "stores": self.stores, "errors": self.errors,
        }


class ExecutableCache:
    """The engine's two-tier executable cache (what ``PCAServer._cache``
    is now).

    Mapping surface (``len``/``in``/iteration/indexing) is the in-memory
    LRU tier, so everything that introspected the old dict still works;
    ``lookup``/``store`` add the disk tier underneath:

      lookup   memory hit -> (fn, "memory").  Disk hit -> deserialize,
               promote into memory, ("disk").  Otherwise (None, "miss").
      store    memory insert; when the entry is an AOT ``Compiled`` (the
               engine compiles AOT exactly when a disk tier is armed) it
               is also serialized to disk.

    The same LRU instance backs both the engine's steady-state path and
    the disk tier's promotions, so the size cap is shared: warming 500
    executables from disk cannot balloon host memory past the cap either.
    """

    def __init__(self, max_entries: Optional[int] = DEFAULT_MAX_ENTRIES,
                 cache_dir=None,
                 max_disk_bytes: int = DEFAULT_MAX_DISK_BYTES):
        self.mem = LRUCache(max_entries=max_entries)
        self.disk: Optional[DiskCache] = None
        if cache_dir is not None:
            self.disk = DiskCache(cache_dir, max_bytes=max_disk_bytes)

    # -- mapping surface (the old dict's contract) --------------------------
    def __len__(self) -> int:
        return len(self.mem)

    def __iter__(self) -> Iterator:
        return iter(self.mem)

    def __contains__(self, key) -> bool:
        return key in self.mem

    def __getitem__(self, key):
        return self.mem[key]

    def get(self, key, default=None):
        return self.mem.get(key, default)

    @property
    def evictions(self) -> int:
        return self.mem.evictions

    # -- two-tier path ------------------------------------------------------
    def hash_key(self, key) -> str:
        op, bucket, batch, solver, exec_token = key
        return content_hash(op, bucket, batch, solver, exec_token)

    def lookup(self, key) -> Tuple[Optional[Callable], str]:
        """(executable, source) where source is 'memory'|'disk'|'miss'."""
        fn = self.mem.get(key)
        if fn is not None:
            return fn, "memory"
        if self.disk is not None:
            fn = self.disk.get(self.hash_key(key))
            if fn is not None:
                self.mem[key] = fn
                return fn, "disk"
        return None, "miss"

    def store(self, key, fn, persist: bool = False) -> None:
        self.mem[key] = fn
        if persist and self.disk is not None:
            self.disk.put(self.hash_key(key), fn)

    def clear_memory(self) -> None:
        """Drop the in-memory tier only (a fresh replica's view of a warm
        disk cache -- used by cold-start benchmarks and tests)."""
        self.mem.clear()

    def summary(self) -> Dict:
        doc = {
            "entries": len(self.mem),
            "max_entries": self.mem.max_entries,
            "evictions": self.mem.evictions,
            "disk": self.disk.summary() if self.disk is not None else None,
        }
        return doc
