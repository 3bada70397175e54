"""The ``slab_reuse`` reader: the share of the window's flushes staged into
a kept host slab, nothing to read from a server whose records lack the
field, and a traced rehearsal of each cell on the CPU reads 100%: the
warm-up flush allocates every slab the window uses."""
import copy
import time
from types import SimpleNamespace

import jax
import pytest

from harness import cells, session


def ctx_of(flushes):
    return {"records": {"flushes": flushes, "requests": []}}


@pytest.mark.parametrize("flags,expect", [
    ((True, True, True, True), 100.0),
    ((False, True, True, True), 75.0),
    ((False,), 0.0),
])
def test_reader_shares_the_reused_flushes(flags, expect):
    reader = cells.load_module("layers", "slab_reuse")
    flushes = [SimpleNamespace(slab_reused=f) for f in flags]
    assert reader.read(ctx_of(flushes)) == pytest.approx(expect)


def test_reader_finds_nothing_without_the_field():
    """An empty window, and records of a server that keeps no staging pool
    (as the parent program's ``FlushRecord`` does)."""
    reader = cells.load_module("layers", "slab_reuse")
    assert reader.read(ctx_of([])) is None
    bare = SimpleNamespace(t_dispatch=1.0, padded_batch=4, stack_s=0.1)
    assert reader.read(ctx_of([bare, bare])) is None


@pytest.mark.parametrize("workload,suffix,requests", [
    ("mnist-28x28.fit", "fit",
     [{"op": "pca", "rows": 512, "cols": 32, "data": "decay"}]),
    ("mnist-8x8.closed16", "rps", None),
], ids=["fit", "closed16"])
def test_traced_rehearsal_reuses_every_slab(workload, suffix, requests):
    cell = copy.deepcopy(cells.resolve(workload))
    if requests is not None:
        cell.config["requests"] = requests
    cell.traffic["trace_seconds"] = 0.2
    r = session.run_cell(cell, 2**31 + 11, 0.6, True, time.monotonic(),
                         jax.devices())
    assert r["correct"] is True
    assert r["metrics"][f"slab_reuse.{suffix}"] == {"value": 100.0,
                                                    "unit": "%"}
