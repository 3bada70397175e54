"""Generators: deterministic per seed, the same work for every seed, and
transforms whose effect on an answer the reference undoes exactly."""
from types import SimpleNamespace

import numpy as np
import pytest

from harness import gen, reference

CLASSES = [
    {"op": "eigh", "dims": [8, 12], "data": "symmetric"},
    {"op": "svd", "dims": [8, 12], "rows_per_dim": 4, "data": "gaussian"},
    {"op": "pca", "dims": [8, 12], "rows_per_dim": 4, "data": "gaussian"},
]
BIG_SEED = 2**31 + 12345


def take(stream, n):
    return [stream.next() for _ in range(n)]


def test_same_seed_same_requests():
    a = take(gen.RequestStream(CLASSES, BIG_SEED), 40)
    b = take(gen.RequestStream(CLASSES, BIG_SEED), 40)
    for x, y in zip(a, b):
        assert x.op == y.op and x.base == y.base
        np.testing.assert_array_equal(x.matrix, y.matrix)


def test_other_seed_other_bytes_same_shapes():
    s1 = gen.RequestStream(CLASSES, 1)
    s2 = gen.RequestStream(CLASSES, 2)
    n = len(s1.shapes)
    r1, r2 = take(s1, 3 * n), take(s2, 3 * n)
    key = lambda r: (r.op, r.matrix.shape)
    assert sorted(map(key, r1)) == sorted(map(key, r2))
    assert [key(r) for r in r1] != [key(r) for r in r2]
    assert not np.array_equal(s1.bases[0], s2.bases[0])


def test_no_two_requests_share_bytes():
    reqs = take(gen.RequestStream(CLASSES, 7), 60)
    seen = {r.matrix.tobytes() for r in reqs}
    assert len(seen) == len(reqs)


def test_round_holds_every_shape_once():
    s = gen.RequestStream(CLASSES, 3)
    bases = [s.next_base() for _ in range(len(s.shapes))]
    assert sorted(bases) == list(range(len(s.shapes)))
    assert len(s.shapes) == 3 * 5


def test_seed_accepts_large_and_negative():
    for seed in (0, 2**31 - 1, 2**31 + 7, 2**40, -5):
        gen.rng_for(seed, gen.DATA).random()


def test_stratified_arrivals_same_set_each_seed():
    p = {"arrivals": "poisson", "rate": 50.0}
    a = gen.arrival_gaps(p, 500, gen.rng_for(1, gen.ARRIVALS))
    b = gen.arrival_gaps(p, 500, gen.rng_for(2, gen.ARRIVALS))
    np.testing.assert_allclose(np.sort(a), np.sort(b))
    assert not np.array_equal(a, b)
    assert abs(a.mean() - 1 / 50.0) < 0.05 / 50.0


def test_schedule_counts_rate_times_window():
    p = {"arrivals": "poisson", "rate": 40.0}
    for seed in (5, BIG_SEED):
        due = gen.schedule(p, 10.0, seed)
        assert len(due) == 400
        assert np.all(np.diff(due) >= 0) and due[-1] < 10.0


def test_bursty_keeps_the_mean_rate():
    p = {"arrivals": "bursty", "rate": 100.0, "on_s": 1.0, "off_s": 3.0,
         "burst_factor": 4.0}
    gaps = gen.arrival_gaps(p, 4000, gen.rng_for(9, gen.ARRIVALS))
    assert len(gaps) == 4000 and np.all(gaps >= 0)
    assert 0.5 < 1.0 / gaps.mean() / 100.0 < 2.0


def test_decay_dataset_shape_and_spectrum():
    x = gen.decay_dataset(2000, 64, gen.rng_for(4, gen.DATA))
    assert x.shape == (2000, 64) and x.dtype == np.float32
    w = np.linalg.eigvalsh(np.cov(x.T))[::-1]
    assert w[0] > 20 * w[40]          # rank-32 signal over a noise floor


def test_decay_dataset_draws_one_spectrum_for_every_seed():
    """Every seed gives the same standardised spectrum in another basis,
    so the gap at the 95%-variance cut (what the subspace check rests on)
    does not change with the seed."""
    spectra, cuts = [], []
    for seed in (3, 2**31 + 5, 1412697434):
        x = gen.decay_dataset(20000, 128, gen.rng_for(seed, gen.DATA))
        assert np.allclose(x.var(0), x.var(0).mean(), rtol=0.05)
        xs, _, _ = reference.standardized(x)
        w = np.linalg.eigvalsh(xs.T @ xs)[::-1]
        spectra.append(w[:33] / w.sum())
        cuts.append(reference.cvcr_k(w))
    assert len(set(cuts)) == 1
    k = cuts[0]
    gaps = [(s[k - 1] - s[k]) / s[k - 1] for s in spectra]
    assert max(gaps) < 1.15 * min(gaps) and min(gaps) > 0.1, gaps
    for s in spectra[1:]:
        assert np.allclose(s, spectra[0], rtol=0.05)


def float64_answer(op, a):
    """An exact (float64) answer for the transformed matrix itself."""
    a = np.asarray(a, np.float64)
    if op == "eigh":
        w, V = np.linalg.eigh(a)
        return SimpleNamespace(eigenvalues=w[::-1], eigenvectors=V[:, ::-1])
    if op == "svd":
        U, s, Vt = np.linalg.svd(a, full_matrices=False)
        return SimpleNamespace(U=U, S=s, Vt=Vt)
    xs, mean, std = reference.standardized(a)
    w, V = np.linalg.eigh(xs.T @ xs)
    return SimpleNamespace(eigenvalues=w[::-1], components=V[:, ::-1],
                           mean=mean, scale=std)


@pytest.mark.parametrize("op", ["eigh", "svd", "pca"])
def test_transform_is_undone_exactly(op):
    classes = [c for c in CLASSES if c["op"] == op]
    stream = gen.RequestStream(classes, 11)
    for req in take(stream, 6):
        ref = reference.reference(op, stream.bases[req.base])
        got = reference.numbers(ref, reference.to_base(
            req, float64_answer(op, req.matrix)))
        assert max(got.values()) < 1e-10, got


def test_signal_rank_is_the_widest_relative_gap():
    w = np.concatenate([np.geomspace(100.0, 10.0, 5), np.full(7, 0.1)])
    assert reference.signal_rank(w) == 5
    x = gen.decay_dataset(4000, 96, gen.rng_for(8, gen.DATA))
    xs, _, _ = reference.standardized(x)
    assert reference.signal_rank(np.linalg.eigvalsh(xs.T @ xs)[::-1]) == 32
