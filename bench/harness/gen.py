"""Request and arrival generators, driven by a configuration's request
classes, a traffic file's parameters and the run's seed.

Everything a run sends is made here from ``--seed``: the data, the order
of the request shapes, the exact transform that makes each request's
bytes its own, and the arrival times.  The generators are copies, kept
with the benchmark so that a change to the program cannot move them:

* ``decay_dataset`` -- the paper's Table IV stand-in of
  ``benchmarks/common.py:synthetic_dataset`` (seeded rank-32 signal with
  a geometric spectrum plus 5% noise), mended so that every seed draws
  the same spectrum in another basis (see the function).
* ``small_matrix`` -- ``serving/autotune.py:synthesize``: a symmetric
  Gaussian matrix for eigh, a Gaussian data matrix for svd and pca.
* ``arrival_gaps`` -- the poisson and bursty (on/off) processes of
  ``serving/frontend.py:arrival_times``, drawn at stratified quantiles so
  that every seed offers the same set of gaps in another order.

Every seed gets the same multiset of request shapes: a round holds each
(class, dim) of the configuration ``weight`` times, in a seeded order.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

OPS = ("eigh", "svd", "pca")


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    """An independent stream per (seed, tags); any integer seed works."""
    return np.random.default_rng([int(seed) % (1 << 63), *tags])


# stream tags: one per use, so adding a use never shifts another's draws
DATA, ORDER, TRANSFORM, ARRIVALS, SAMPLE = range(5)


def decay_dataset(m: int, n: int, rng: np.random.Generator) -> np.ndarray:
    """``benchmarks.common.synthetic_dataset(spectrum="decay")``: a rank-32
    signal with geometric scales 1 to 0.05 plus 5% Gaussian noise.

    The copy draws the signal's directions so that every seed gives one
    problem of one difficulty.  The original mixes the 32 components with
    a Gaussian matrix; its column norms, and then standardisation, move
    the eigenvalues by some 20% from seed to seed, so the gap at the
    95%-variance cut (what the top-k subspace's accuracy rests on) ranged
    from 6% to 30% of the eigenvalue.  Here the mix is the eigenbasis of a
    random correlation matrix with the 32 scales' spectrum (unit diagonal,
    so every column carries the same variance and standardising leaves the
    spectrum as it is): every seed has the same eigenvalues, up to the
    sampling noise of ``m`` rows, in a random basis.
    """
    from scipy.stats import random_correlation
    k = min(n, 32)
    scales = np.geomspace(1, 0.05, k)
    eigs = np.zeros(n)
    eigs[:k] = n * scales ** 2 / np.sum(scales ** 2)
    corr = random_correlation.rvs(eigs, random_state=rng, tol=1e-8)
    _, V = np.linalg.eigh(corr)
    mix = V[:, ::-1][:, :k].T * np.sqrt(n / k)
    base = rng.standard_normal((m, k)) * scales
    x = base @ mix + 0.05 * rng.standard_normal((m, n))
    return x.astype(np.float32)


def small_matrix(op: str, shape: Sequence[int],
                 rng: np.random.Generator) -> np.ndarray:
    """Copy of ``serving.autotune.synthesize``."""
    if op == "eigh":
        n = int(shape[-1])
        a = rng.standard_normal((n, n)).astype(np.float32)
        return (a + a.T) / 2
    m, n = int(shape[0]), int(shape[1])
    return rng.standard_normal((m, n)).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class Shape:
    """One (class, dim) of a configuration: an op at one request shape."""
    op: str
    shape: Tuple[int, ...]
    data: str          # "decay" | "gaussian" | "symmetric"


def expand_classes(classes: Sequence[Dict]) -> List[Tuple[Shape, int]]:
    """(shape, weight) for every (class, dim) of the configuration.

    A class gives either ``rows``/``cols`` (one fixed shape) or ``dims``
    [lo, hi] with ``rows_per_dim`` (shapes (rows_per_dim*d, d) for every d
    in lo..hi; an eigh class is square).
    """
    out = []
    for c in classes:
        op = c["op"]
        if op not in OPS:
            raise ValueError(f"unknown op {op!r} in a request class")
        weight = int(c.get("weight", 1))
        if "dims" in c:
            lo, hi = c["dims"]
            rpd = 1 if op == "eigh" else int(c["rows_per_dim"])
            for d in range(int(lo), int(hi) + 1):
                out.append((Shape(op, (rpd * d, d), c["data"]), weight))
        else:
            out.append((Shape(op, (int(c["rows"]), int(c["cols"])),
                              c["data"]), weight))
    return out


def make_base(s: Shape, rng: np.random.Generator) -> np.ndarray:
    if s.data == "decay":
        return decay_dataset(*s.shape, rng)
    if s.data == "symmetric":
        return small_matrix("eigh", s.shape, rng)
    if s.data == "gaussian":
        return small_matrix(s.op, s.shape, rng)
    raise ValueError(f"unknown data kind {s.data!r}")


@dataclasses.dataclass
class Transform:
    """An exact change of a base matrix's bytes whose effect on the
    answer is known exactly: signed permutations, and scales that are
    powers of two (no rounding in float32 or float64).

    eigh:  A' = c * Q A Q^T,  Q = diag(sign) P_col
    svd:   A' = c * R A Q,    R a row permutation
    pca:   X' = R X Q S,      S per-column powers of two (standardising
                              removes them)
    """
    rows: np.ndarray            # row permutation (pca, svd)
    cols: np.ndarray            # column permutation
    sign: np.ndarray            # +-1 per column
    scale: np.ndarray           # per-column power of two (pca) or one
                                # global one, broadcast (eigh, svd)

    def apply(self, op: str, a: np.ndarray) -> np.ndarray:
        f = (self.sign * self.scale).astype(np.float32)
        if op == "eigh":
            b = a[np.ix_(self.cols, self.cols)]
            return (b * self.sign[:, None] * self.sign[None, :]
                    * np.float32(self.scale[0]))
        return a[np.ix_(self.rows, self.cols)] * f[None, :]


def draw_transform(op: str, shape: Tuple[int, ...],
                   rng: np.random.Generator) -> Transform:
    m, n = shape
    sign = rng.choice(np.array([-1.0, 1.0]), size=n)
    if op == "pca":
        scale = np.exp2(rng.integers(-2, 3, size=n)).astype(np.float64)
    else:
        scale = np.full(n, float(np.exp2(rng.integers(-2, 3))))
    rows = rng.permutation(m) if op != "eigh" else np.arange(m)
    return Transform(rows=rows, cols=rng.permutation(n), sign=sign,
                     scale=scale)


@dataclasses.dataclass
class Request:
    rid: int
    op: str
    base: int                   # index into the stream's bases
    transform: Transform
    matrix: np.ndarray


class RequestStream:
    """The configuration's requests for one seed.

    The bases (one matrix per (class, dim)) are made at set-up.  Each
    request is a base under a fresh exact transform, so no two requests
    carry the same bytes, while a float64 reference per base serves every
    request made from it.
    """

    def __init__(self, classes: Sequence[Dict], seed: int):
        self.shapes: List[Shape] = []
        weights: List[int] = []
        for s, w in expand_classes(classes):
            self.shapes.append(s)
            weights.append(w)
        data = rng_for(seed, DATA)
        self.bases = [make_base(s, data) for s in self.shapes]
        self._round = np.repeat(np.arange(len(self.shapes)), weights)
        self._order = rng_for(seed, ORDER)
        self._xf = rng_for(seed, TRANSFORM)
        self._queue: List[int] = []
        self._next_rid = 0

    def next_base(self) -> int:
        if not self._queue:
            self._queue = list(self._order.permutation(self._round))
        return int(self._queue.pop())

    def next(self) -> Request:
        b = self.next_base()
        s = self.shapes[b]
        t = draw_transform(s.op, s.shape, self._xf)
        rid = self._next_rid
        self._next_rid += 1
        return Request(rid, s.op, b, t, t.apply(s.op, self.bases[b]))


def arrival_gaps(params: Dict, n: int,
                 rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps (seconds) of the traffic's process.

    poisson  exponential gaps at mean 1/rate, drawn at the n stratified
             quantiles (i + 1/2)/n and shuffled: every seed offers the same
             gaps, so the same load, in another order.
    bursty   on/off (``on_s``, ``off_s`` mean exponential dwell, on-rate
             ``burst_factor`` x rate, off-rate keeping the mean at rate),
             as ``serving.frontend.arrival_times``; within each stretch the
             arrivals are uniform (a Poisson process given its count), and
             the count is the stretch's length times its rate, rounded.
    """
    kind = params["arrivals"]
    rate = float(params["rate"])
    q = (np.arange(n) + 0.5) / n
    if kind == "poisson":
        return rng.permutation(-np.log1p(-q) / rate)
    if kind == "bursty":
        on_s, off_s = float(params["on_s"]), float(params["off_s"])
        rate_on = float(params["burst_factor"]) * rate
        rate_off = max((rate * (on_s + off_s) - rate_on * on_s) / off_s, 0.0)
        times, t, on = [], 0.0, True
        dwell = lambda mean: float(-mean * math.log1p(-rng.random()))
        while len(times) < n:
            end = t + dwell(on_s if on else off_s)
            r = rate_on if on else rate_off
            if r > 0:
                k = max(int(round((end - t) * r)), 0)
                if k:
                    times.extend(np.sort(rng.uniform(t, end, size=k)))
            t, on = end, not on
        times = np.asarray(times[:n])
        return np.diff(times, prepend=0.0)
    raise ValueError(f"unknown arrival process {kind!r}")


def schedule(params: Dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of every arrival due
    in a window of ``seconds``."""
    n = int(math.ceil(float(params["rate"]) * seconds))
    due = np.cumsum(arrival_gaps(params, n, rng_for(seed, ARRIVALS)))
    return due[due < seconds]
