"""The per-flush stage readers (``stack_ms``, ``put_ms``, ``fetch_ms``,
``unpack_ms``): the mean over the window's flushes in ms on synthetic
records; nothing to read from a server that keeps no such stamps; and a
traced rehearsal of each cell on the CPU reports all four, with stacking
and the copy in inside the dispatch time."""
import copy
import time
from types import SimpleNamespace

import jax
import pytest

from harness import cells, session
from repro.serving.stats import FlushRecord

FAMILIES = ("stack_ms", "put_ms", "fetch_ms", "unpack_ms")


def flush(t0, stack, lookup, put, launch, wait, fetch, unpack):
    """A retired flush whose stages take the given seconds, back to back."""
    t_put = t0 + stack + lookup + put
    t_launched = t_put + launch
    t_ready = t_launched + wait
    t_retire = t_ready + fetch
    return FlushRecord(
        t_dispatch=t0, t_launched=t_launched, t_wait=t_launched,
        t_retire=t_retire, batch_size=4, cache_hit=True, inflight_depth=1,
        op="pca", bucket=(16, 16), padded_batch=4, t_put=t_put,
        t_ready=t_ready, t_done=t_retire + unpack, stack_s=stack,
        lookup_s=lookup)


def ctx_of(flushes):
    return {"records": {"flushes": flushes, "requests": []}}


@pytest.mark.parametrize("family,expect_ms", [
    ("stack_ms", 0.5), ("put_ms", 2.0), ("fetch_ms", 4.0),
    ("unpack_ms", 1.5)])
def test_reader_means_its_stage_over_the_window(family, expect_ms):
    reader = cells.load_module("layers", family)
    flushes = [flush(10.0, 0.4e-3, 1e-5, 1.5e-3, 0.2e-3, 9e-3, 3e-3, 1e-3),
               flush(20.0, 0.6e-3, 1e-5, 2.5e-3, 0.2e-3, 9e-3, 5e-3, 2e-3)]
    assert reader.read(ctx_of(flushes)) == pytest.approx(expect_ms)


@pytest.mark.parametrize("family", FAMILIES)
def test_reader_finds_nothing_without_stamps(family):
    """An empty window, and records of a server that stamps only dispatch
    and retire (as the parent program's ``FlushRecord`` does)."""
    reader = cells.load_module("layers", family)
    assert reader.read(ctx_of([])) is None
    bare = SimpleNamespace(t_dispatch=1.0, t_launched=1.1, t_wait=1.1,
                           t_retire=1.3, dispatch_s=0.1, wait_s=0.2)
    assert reader.read(ctx_of([bare, bare])) is None


@pytest.mark.parametrize("workload,suffix,requests", [
    ("mnist-28x28.fit", "fit",
     [{"op": "pca", "rows": 512, "cols": 32, "data": "decay"}]),
    ("mnist-8x8.closed16", "rps", None),
], ids=["fit", "closed16"])
def test_traced_rehearsal_reports_the_stage_metrics(workload, suffix,
                                                    requests):
    cell = copy.deepcopy(cells.resolve(workload))
    if requests is not None:
        cell.config["requests"] = requests
    cell.traffic["trace_seconds"] = 0.2
    r = session.run_cell(cell, 2**31 + 7, 0.6, True, time.monotonic(),
                         jax.devices())
    assert r["correct"] is True
    m = {k: v["value"] for k, v in r["metrics"].items()}
    names = {f"{f}.{suffix}" for f in FAMILIES}
    assert names <= set(m)
    assert all(m[n] > 0 for n in names)
    assert m[f"stack_ms.{suffix}"] + m[f"put_ms.{suffix}"] \
        <= m[f"dispatch_ms.{suffix}"]
