"""Importing the benchmark -- its harness, loops, layer readers and test
modules -- initializes no JAX backend, so no test loads the TPU library
when it is collected."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

ROOT = pathlib.Path(__file__).resolve().parents[2]

BODY = """
import importlib.util, json, pathlib, sys
root = pathlib.Path({root!r})
sys.path[:0] = [str(root / "bench"), str(root / "src"), str(root / "tests" / "bench")]
from harness import cells, client, control, e2e, gen, peaks, reference, session, trace, work
loaded = []
for kind in ("loops", "layers"):
    for f in sorted((root / "bench" / kind).glob("*.py")):
        cells.load_module(kind, f.stem)
        loaded.append(f.stem)
for f in sorted((root / "tests" / "bench").glob("test_*.py")):
    spec = importlib.util.spec_from_file_location(f.stem, f)
    spec.loader.exec_module(importlib.util.module_from_spec(spec))
    loaded.append(f.stem)
from jax._src import xla_bridge
print(json.dumps({{"backends": sorted(xla_bridge._backends), "loaded": loaded}}))
"""


def test_imports_initialize_no_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(BODY.format(root=str(ROOT)))],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["backends"] == []
    assert {"open", "closed", "device_idle", "solver_roofline"} <= set(
        out["loaded"])
