"""The least work a request needs, from its op and true shape alone.

Counted from what the answer requires, not from how the program computes
it: no sweeps, no bucket padding, no batch filler.

  covariance / Gram    C = X^T X of an m x n matrix: 2 m n^2 flops
  symmetric eigh       eigenvalues and eigenvectors of n x n: 9 n^3 flops
                       (Golub & Van Loan, Matrix Computations: the
                       symmetric QR algorithm with Q accumulated)
  back-projection      U = A V of the svd: 2 m n^2 flops
  bytes                every input element read once and every output
                       element written once, 4 bytes each (float32)

pca = covariance + eigh (components, eigenvalues, mean, scale, evcr,
cvcr out); svd = Gram + eigh + back-projection (U, S, Vt out); eigh =
eigh (values and vectors out).
"""
from __future__ import annotations

from typing import Sequence, Tuple

F32 = 4


def request_work(op: str, shape: Sequence[int]) -> Tuple[float, float]:
    """(flops, bytes) of one request of ``op`` at true ``shape``."""
    m, n = (float(shape[0]), float(shape[1]))
    eig = 9.0 * n ** 3
    if op == "eigh":
        return eig, F32 * (n * n + n * n + n)
    if op == "pca":
        return 2.0 * m * n * n + eig, F32 * (m * n + n * n + 5 * n)
    if op == "svd":
        return (4.0 * m * n * n + eig,
                F32 * (m * n + m * n + n * n + n))
    raise ValueError(f"unknown op {op!r}")


def roofline_seconds(flops: float, nbytes: float, peak: dict
                     ) -> Tuple[float, str]:
    """Least time at the chip's peaks, and which peak bounds it."""
    t_c = flops / peak["flops_per_s"]
    t_b = nbytes / peak["bytes_per_s"]
    return (t_c, "compute") if t_c >= t_b else (t_b, "bandwidth")
