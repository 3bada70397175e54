"""The ``20-newsgroups.fit`` cell: it resolves to its configuration and
metrics, a CPU rehearsal at a small svd shape is correct, the
lower-precision control and an answer at the solver's old cutoff each
fail the configuration's check at the cell's own size, and the
``fetch_waste`` reader's sums."""
import copy
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from harness import cells, control, gen, reference, session

CELL = "20-newsgroups.fit"
SMALL = [{"op": "svd", "rows": 1178, "cols": 64, "data": "decay"}]
# every layer the cell runs: the fit cell's metrics, and the fetch's waste
HOST_LAYERS = {"padding_waste.fit", "dispatch_ms.fit", "stack_ms.fit",
               "put_ms.fit", "fetch_ms.fit", "unpack_ms.fit",
               "slab_reuse.fit", "fetch_waste.news"}
LAYERS = HOST_LAYERS | {"device_idle.fit", "solver_roofline.fit"}


def small_cell():
    cell = copy.deepcopy(cells.resolve(CELL))
    cell.config["requests"] = SMALL
    cell.traffic["trace_seconds"] = 0.2
    return cell


def test_cell_resolves():
    cell = cells.resolve(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, "20-newsgroups", "fit")
    assert {m["name"] for m in cell.end_to_end} == {"fit_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == LAYERS
    assert all(m["moves"] == "fit_s" for m in cell.per_layer)
    (req,) = cell.config["requests"]
    assert (req["op"], req["rows"], req["cols"]) == ("svd", 18846, 1024)
    fit = cells.resolve("mnist-28x28.fit").config
    assert cell.config["server_spec"] == fit["server_spec"]
    assert set(cell.config["check"]["limits"]) == {"values", "vectors"}


@pytest.mark.parametrize("traced", [False, True], ids=["e2e", "traced"])
def test_rehearsal_is_correct(traced):
    cell = small_cell()
    r = session.run_cell(cell, 2**31 + 15, 0.6, traced, time.monotonic(),
                         jax.devices())
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    if not traced:
        assert set(r["metrics"]) == {"fit_s", "setup_s"}
        return
    # no TPU plane on the CPU: the device metrics stay out
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert set(m) == HOST_LAYERS
    # one svd in a batch of four: at least the three filler slots' bytes
    assert 75.0 < m["fetch_waste.news"] < 80.0


@pytest.fixture(scope="module")
def cell_size():
    """One request at the cell's own 18846x1024 and its float64
    reference, shared by the answers below."""
    cell = cells.resolve(CELL)
    stream = gen.RequestStream(cell.config["requests"], 2**31 + 15)
    req = stream.next()
    ref = reference.reference(req.op, stream.bases[req.base])
    return cell.config["check"]["limits"], req, ref


def old_cutoff(matrix):
    """The exact SVD with the U columns the solver's cutoff zeroed before
    its refinement: below sqrt(nb * eps_f32) * s_max."""
    u, s, vt = np.linalg.svd(np.asarray(matrix, np.float64),
                             full_matrices=False)
    n = matrix.shape[1]
    live = s > np.sqrt(n * np.finfo(np.float32).eps) * s[0]
    return SimpleNamespace(U=(u * live).astype(np.float32),
                           S=s.astype(np.float32),
                           Vt=vt.astype(np.float32))


@pytest.mark.parametrize("answer", [
    lambda m: control.served("svd", m), old_cutoff],
    ids=["bf16-control", "old-cutoff"])
def test_wrong_answers_fail_the_check(cell_size, answer):
    limits, req, ref = cell_size
    got = reference.numbers(ref, reference.to_base(req, answer(req.matrix)))
    failed = {k for k, lim in limits.items() if got[k] > lim}
    assert failed, got


def test_fetch_waste_reader():
    reader = cells.load_module("layers", "fetch_waste")

    def ctx(flushes):
        return {"records": {"flushes": flushes, "requests": []}}

    def rec(fetched, kept):
        return SimpleNamespace(fetched_bytes=fetched, answer_bytes=kept)

    assert reader.read(ctx([rec(400, 100), rec(400, 300)])) == \
        pytest.approx(50.0)
    assert reader.read(ctx([rec(1000, 1000)])) == pytest.approx(0.0)
    assert reader.read(ctx([])) is None
    assert reader.read(ctx([rec(0, 0)])) is None
    # a server that keeps no byte counts (the parent program's records)
    bare = SimpleNamespace(t_dispatch=1.0, t_retire=1.3)
    assert reader.read(ctx([bare])) is None
