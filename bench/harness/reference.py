"""The plain float64 reference and the comparison that decides ``correct``.

The reference is NumPy in float64 and imports nothing of the program:

  pca   standardise each column (mean 0, population std 1; a column with
        std < 1e-8 keeps scale 1), C = Xs^T Xs, ``numpy.linalg.eigh``
  svd   ``numpy.linalg.svd`` of the matrix
  eigh  ``numpy.linalg.eigh`` of the matrix

Every request is a base matrix under an exact transform (``gen``), so the
reference is computed once per base, and each served answer is mapped
back into the base's coordinates exactly (signed permutations and powers
of two) before it is compared.

Numbers, each of one answer:

  values     relative error of the spectrum: ||w - w64|| / ||w64|| (pca,
             eigh eigenvalues; svd singular values)
  trace      error of the spectrum's sum, the total variance: pca
             |sum w - sum w64| / sum w64; svd |sum s^2 - ||A||_F^2| /
             ||A||_F^2; eigh |sum w - tr A| / ||A||_F.  The sum is the
             trace of the covariance (Gram) matrix, which no rotation of a
             sound eigensolver changes and which every product of a
             lower-precision matmul biases (its dropped low-order terms
             are squares, so they do not cancel).
  vectors    the residual of the served factors against the exact matrix:
             eigh ||A V - V W||_F / ||A||_F; svd ||A - U S V^T||_F /
             ||A||_F; pca ||C V - V W||_F / ||C||_F with C the float64
             covariance of the standardised data
  subspace   pca: sine of the largest principal angle between the served
             and the reference top-k subspace (k at 95% cumulative
             variance)
  subspace_cuts
             pca: the mean of that sine over every cut 1..r, r at the
             widest relative gap (w_r - w_r+1) / w_r in the first half of
             the reference spectrum: the rank of the data's signal.  One
             cut's sine is carried by the pair of eigenvectors across it,
             one coefficient of the rounding error, and swings from seed
             to seed; the mean over the signal's cuts is steady, and it
             takes in the leak of the signal into the noise directions,
             which a lower-precision covariance widens
  moments    pca: the larger relative error of the served mean and scale

Each number is read as the worst over the answers compared, and as their
mean under ``<name>.mean``.  A configuration compares the readings it
gives a limit (``check.limits``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from .gen import Request, RequestStream

def standardized(X: np.ndarray):
    X = np.asarray(X, np.float64)
    mean = X.mean(axis=0)
    std = X.std(axis=0)
    std = np.where(std < 1e-8, 1.0, std)
    return (X - mean) / std, mean, std


def rel(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


def cvcr_k(w: np.ndarray, target: float = 0.95) -> int:
    w = np.maximum(w, 0.0)
    c = np.cumsum(w) / np.sum(w)
    return int(min(np.sum(c < target) + 1, len(w)))


def signal_rank(w: np.ndarray) -> int:
    """The cut r (1 <= r <= n/2) at the widest relative gap of the
    descending spectrum ``w``."""
    w = np.maximum(w, 0.0)
    half = max(len(w) // 2, 1)
    rel_gap = (w[:half] - w[1:half + 1]) / np.maximum(w[:half], 1e-300)
    return int(np.argmax(rel_gap)) + 1


def sin_theta(Q1: np.ndarray, Q2: np.ndarray) -> float:
    """||(I - Q2 Q2^T) Q1||_2 for orthonormal columns."""
    return float(np.linalg.norm(Q1 - Q2 @ (Q2.T @ Q1), 2))


def cut_sines(V_ref: np.ndarray, V: np.ndarray, k: int) -> np.ndarray:
    """sin_theta(V[:, :c], V_ref[:, :c]) for every cut c in 1..k short of
    the whole space, with ``V_ref`` a full orthonormal basis."""
    M = V_ref.T @ V
    cuts = range(1, min(k, len(M) - 1) + 1)
    return np.array([np.linalg.norm(M[c:, :c], 2) for c in cuts] or [0.0])


@dataclasses.dataclass
class Ref:
    """float64 reference of one base matrix."""
    op: str
    a: np.ndarray                       # the base, float64
    w: np.ndarray                       # eigenvalues / singular values, desc
    V: Optional[np.ndarray] = None      # pca eigenvectors, desc
    C: Optional[np.ndarray] = None      # pca covariance of the standardised
    mean: Optional[np.ndarray] = None
    std: Optional[np.ndarray] = None


def reference(op: str, base: np.ndarray) -> Ref:
    a = np.asarray(base, np.float64)
    if op == "pca":
        xs, mean, std = standardized(a)
        C = xs.T @ xs
        w, V = np.linalg.eigh(C)
        return Ref(op, a, w[::-1].copy(), V[:, ::-1].copy(), C, mean, std)
    if op == "svd":
        return Ref(op, a, np.linalg.svd(a, compute_uv=False))
    if op == "eigh":
        return Ref(op, a, np.linalg.eigvalsh(a)[::-1].copy())
    raise ValueError(f"unknown op {op!r}")


def to_base(req: Request, served) -> Dict[str, np.ndarray]:
    """The served answer in the base matrix's coordinates (exact)."""
    t = req.transform
    cols, sign = t.cols, t.sign
    if req.op == "pca":
        d = len(cols)
        V = np.empty((d, d))
        V[cols] = sign[:, None] * np.asarray(served.components, np.float64)
        mean = np.empty(d)
        mean[cols] = (np.asarray(served.mean, np.float64)
                      / (sign * t.scale))
        scale = np.empty(d)
        scale[cols] = np.asarray(served.scale, np.float64) / t.scale
        return {"w": np.asarray(served.eigenvalues, np.float64), "V": V,
                "mean": mean, "scale": scale}
    c = float(t.scale[0])
    if req.op == "eigh":
        n = len(cols)
        V = np.empty((n, n))
        V[cols] = sign[:, None] * np.asarray(served.eigenvectors, np.float64)
        return {"w": np.asarray(served.eigenvalues, np.float64) / c, "V": V}
    U = np.empty(np.shape(served.U))
    U[t.rows] = np.asarray(served.U, np.float64)
    Vt = np.empty(np.shape(served.Vt))
    Vt[:, cols] = np.asarray(served.Vt, np.float64) * sign[None, :]
    return {"s": np.asarray(served.S, np.float64) / c, "U": U, "Vt": Vt}


def numbers(ref: Ref, got: Dict[str, np.ndarray]) -> Dict[str, float]:
    """The comparison numbers of one answer (base coordinates)."""
    a = ref.a
    if ref.op == "pca":
        w, V = got["w"], got["V"]
        k = cvcr_k(ref.w)
        r = signal_rank(ref.w)
        return {
            "values": rel(w, ref.w),
            "trace": abs(w.sum() - ref.w.sum()) / ref.w.sum(),
            "vectors": float(np.linalg.norm(ref.C @ V - V * w[None, :])
                             / np.linalg.norm(ref.C)),
            "subspace": sin_theta(V[:, :k], ref.V[:, :k]),
            "subspace_cuts": float(cut_sines(ref.V, V, r).mean()),
            "moments": max(rel(got["mean"], ref.mean),
                           rel(got["scale"], ref.std)),
        }
    fro2 = float(np.sum(a * a))
    if ref.op == "svd":
        s, U, Vt = got["s"], got["U"], got["Vt"]
        return {
            "values": rel(s, ref.w),
            "trace": abs(float(np.sum(s * s)) - fro2) / fro2,
            "vectors": float(np.linalg.norm(a - (U * s[None, :]) @ Vt)
                             / np.sqrt(fro2)),
        }
    w, V = got["w"], got["V"]
    return {
        "values": rel(w, ref.w),
        "trace": abs(w.sum() - np.trace(a)) / np.sqrt(fro2),
        "vectors": float(np.linalg.norm(a @ V - V * w[None, :])
                         / np.sqrt(fro2)),
    }


def readings(stream: RequestStream, pairs) -> Dict[str, float]:
    """Every number over ``pairs`` of (request, served answer): its worst
    under its name and its mean under ``<name>.mean``; a number that is
    not finite reads as infinite."""
    refs: Dict[int, Ref] = {}
    got: Dict[str, list] = {}
    for req, served in pairs:
        if req.base not in refs:
            refs[req.base] = reference(req.op, stream.bases[req.base])
        for name, v in numbers(refs[req.base], to_base(req, served)).items():
            got.setdefault(name, []).append(
                float(v) if np.isfinite(v) else float("inf"))
    out = {name: max(vs) for name, vs in got.items()}
    out.update({f"{name}.mean": float(np.mean(vs))
                for name, vs in got.items()})
    return out


def compare(stream: RequestStream, pairs, limits: Dict[str, float]
            ) -> Dict[str, Dict[str, float]]:
    """{name: {"value", "limit"}} for the readings ``limits`` names."""
    got = readings(stream, pairs)
    return {name: {"value": got.get(name, 0.0), "limit": float(lim)}
            for name, lim in limits.items()}
