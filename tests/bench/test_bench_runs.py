"""Whole runs on the CPU at a small size: each loop runs and labels its
device; the lower-precision control and each fault the cells can have
make ``correct`` come out false.

Every run here skips the entry point's look for a chip and drives the
rest of ``harness.session.run_cell`` with the real configurations' server
specs and limits, on request classes cut to small shapes where the
configuration's own is too large for a test run.
"""
import copy
import time

import jax
import jax.numpy as jnp
import pytest

from harness import cells, control, gen, reference, session

FIT = [{"op": "pca", "rows": 512, "cols": 32, "data": "decay"}]
# the harness's other loops, on the fit cell's server and limits
OPEN = {"loop": "open", "arrivals": "poisson", "rate": 60.0,
        "trace_seconds": 0.2}
CLOSED = {"loop": "closed", "outstanding": 8, "wait": "poll",
          "trace_seconds": 0.2}


def small_cell(workload: str, requests, traffic=None) -> cells.Cell:
    cell = cells.resolve(workload)
    cell = copy.deepcopy(cell)
    if requests is not None:
        cell.config["requests"] = requests
    if traffic is not None:
        cell.traffic = dict(traffic)
    return cell


def run(cell, traced=False, seconds=0.6, seed=2**31 + 99):
    return session.run_cell(cell, seed, seconds, traced, time.monotonic(),
                            jax.devices())


@pytest.mark.parametrize("workload,requests,traffic", [
    ("mnist-28x28.fit", FIT, None),
    ("mnist-28x28.fit", FIT, OPEN),
    ("mnist-28x28.fit", FIT, CLOSED),
    ("mnist-8x8.closed16", None, None),
], ids=["fit", "open", "closed", "closed16"])
def test_rehearsal_runs_and_labels_cpu(workload, requests, traffic):
    cell = small_cell(workload, requests, traffic)
    r = run(cell)
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    names = {m["name"] for m in cell.end_to_end}
    assert set(r["metrics"]) == names and "setup_s" in names
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == set(cell.config["check"]["limits"])


def test_traced_rehearsal_reports_host_layers():
    cell = small_cell("mnist-28x28.fit", FIT)
    cell.traffic["trace_seconds"] = 0.2
    r = run(cell, traced=True)
    assert r["correct"] is True
    # no TPU plane in a CPU trace: the device metrics stay out, never 0
    assert not {"device_idle.fit", "solver_roofline.fit"} & set(r["metrics"])
    assert {"padding_waste.fit", "dispatch_ms.fit"} <= set(r["metrics"])
    # one fit in a batch of four: three of the four slots are filler
    assert r["metrics"]["padding_waste.fit"]["value"] == pytest.approx(75.0)


def pairs_for(cell, n, answer):
    stream = gen.RequestStream(cell.config["requests"], 5)
    reqs = [stream.next() for _ in range(n)]
    return stream, [(q, answer(q)) for q in reqs]


@pytest.mark.parametrize("workload,requests,n", [
    ("mnist-28x28.fit", FIT, 12),
    ("mnist-28x28.fit", None, 1),
    ("mnist-8x8.closed16", None, 4),
], ids=["small", "cell-size", "closed16"])
def test_bf16_control_fails_the_check(workload, requests, n):
    """At a test's size and at the cell's own (70000x784)."""
    cell = small_cell(workload, requests)
    limits = cell.config["check"]["limits"]
    stream, pairs = pairs_for(
        cell, n, lambda q: control.served(q.op, q.matrix))
    checks = reference.compare(stream, pairs, limits)
    assert any(c["value"] > c["limit"] for c in checks.values()), checks


class Broken:
    """Plants a fault in the solver every executor compiles."""

    def __init__(self, monkeypatch, kind):
        from repro.serving import sharded
        orig = sharded.build_solver_fn

        def build(op, config):
            fn = orig(op, config)

            def altered(x, nr, nc):
                # the top value off by 0.1%, one entry of its vector by 1e-3
                out = fn(x, nr, nc)
                if op == "svd":
                    return out._replace(S=out.S.at[:, 0].multiply(1.001),
                                        Vt=out.Vt.at[:, 0, 0].add(1e-3))
                vec = "eigenvectors" if op == "eigh" else "components"
                return out._replace(**{
                    "eigenvalues": out.eigenvalues.at[:, 0].multiply(1.001),
                    vec: getattr(out, vec).at[:, 0, 0].add(1e-3)})

            def half_rows(x, nr, nc):
                if op == "eigh":
                    return fn(x, nr, nc)
                half = nr // 2
                keep = jnp.arange(x.shape[1])[None, :] < half[:, None]
                return fn(x * keep[:, :, None].astype(x.dtype), half, nc)
            return {"altered": altered, "half_rows": half_rows}[kind]
        monkeypatch.setattr(sharded, "build_solver_fn", build)


@pytest.mark.parametrize("kind", ["altered", "half_rows"])
@pytest.mark.parametrize("workload,requests,traffic", [
    ("mnist-28x28.fit", FIT, None),
    ("mnist-28x28.fit", FIT, CLOSED),
    ("mnist-8x8.closed16", None, None),
], ids=["fit", "closed", "closed16"])
def test_fault_makes_correct_false(monkeypatch, kind, workload, requests,
                                   traffic):
    Broken(monkeypatch, kind)
    r = run(small_cell(workload, requests, traffic))
    assert r["correct"] is False, r["checks"]
