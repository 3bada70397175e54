"""Chip benchmark of the served PCA path: one cell, one run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's ``PCAServer`` from its configuration file, makes the
requests from ``--seed``, warms up the cell's own executables, drives the
server from one client thread for ``--seconds``, checks the window's
answers against a float64 reference, and prints one JSON line last on
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics), ``device`` and, traced, ``breakdown``; then ``checks``, each
number compared with its limit.  Exits non-zero, printing no result,
when no TPU or fewer chips than the cell asks for are visible, or when
the checkout holds no system under test.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache(jax) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), keeping every
    executable however fast it compiled."""
    path = os.environ.get(CACHE_ENV) or str(ROOT / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, str(BENCH))
    from harness import cells
    cell = cells.resolve(args.workload)
    if not (ROOT / "src" / "repro" / "serving").is_dir():
        print(f"bench: no system under test at {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    cache = enable_compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX sees {devices[0].platform}); this "
              "benchmark measures the chip only", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, "
              f"{len(devices)} visible", file=sys.stderr)
        return 3
    print(f"bench: {cell.name} seed {args.seed} on {len(devices)} x "
          f"{devices[0].device_kind}, compile cache {cache}",
          file=sys.stderr, flush=True)
    from harness.session import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      T_PROCESS, devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
