"""The benchmark's harness (``bench/harness``) and the system under test
(``src``) on the import path of the benchmark's tests."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
