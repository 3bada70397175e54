"""Shared forced-mesh subprocess harness.

Multi-device tests need a specific host-device count regardless of how the
main pytest process was launched; XLA fixes the device count at backend
init, so each case runs in a child process that sets XLA_FLAGS before
importing jax and prints its result as a final JSON line.  Used by
tests/test_distributed.py and tests/test_sharded_serving.py.
"""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_mesh_subprocess(body: str, device_count: int = 8) -> dict:
    """Run ``body`` in a child with ``device_count`` forced host devices.

    The child gets json/numpy/jax/jnp and ``make_mesh`` (Auto-axis
    meshes) pre-imported; it must print a JSON
    object as its last stdout line, which is returned parsed.
    """
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={device_count}")
        import json
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.parallel.sharding import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])
