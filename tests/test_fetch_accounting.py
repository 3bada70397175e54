"""What a flush fetches and what its tickets keep: ``FlushRecord``'s
``fetched_bytes`` counts the whole result tree gathered home, batch filler
included, and ``answer_bytes`` only the true-shape slices the tickets
keep; with obs attached an svd flush counts the columns its cutoff
zeroed in ``serve_svd_dead_columns_total``."""
import numpy as np
import pytest

from repro.core import PCAConfig
from repro.obs import Observability
from repro.serving import BucketPolicy, PCAServer

F32, I32 = 4, 4


def _server(obs=None):
    return PCAServer(PCAConfig(T=8, S=4, sweeps=6), policy=BucketPolicy(T=8),
                     max_delay_s=10.0, obs=obs)


def _fetched(op, b, mb, nb):
    """Bytes of the padded result tree of one flush of ``b`` slots."""
    if op == "svd":       # U, S, Vt, n_rows, n_cols, n_dead
        return b * (F32 * (mb * nb + nb + nb * nb) + 3 * I32)
    # components, eigenvalues, mean, scale, evcr, cvcr, off_norm, n_rows,
    # n_cols
    return b * (F32 * (nb * nb + 5 * nb + 1) + 2 * I32)


def _kept(op, m, n):
    if op == "svd":       # U, S, Vt
        return F32 * (m * n + n + n * n)
    return F32 * (n * n + 5 * n)   # components and five vectors


@pytest.mark.parametrize("op", ["svd", "pca"])
@pytest.mark.parametrize("shapes", [[(20, 6)], [(20, 6), (21, 5)]],
                         ids=["one", "two"])
def test_flush_counts_fetched_and_kept_bytes(op, shapes):
    """One or two requests in a (24, 8) bucket padded to a batch of 4:
    every slot of the result tree is fetched, only the true shapes are
    kept."""
    rng = np.random.default_rng(1)
    srv = _server()
    for shape in shapes:
        srv.submit(rng.standard_normal(shape).astype(np.float32), op=op)
    srv.drain()
    (fr,) = srv.stats.flush_records
    assert (fr.batch_size, fr.padded_batch, fr.bucket) == (
        len(shapes), 4, (24, 8))
    assert fr.fetched_bytes == _fetched(op, 4, 24, 8)
    assert fr.answer_bytes == sum(_kept(op, *s) for s in shapes)
    assert fr.answer_bytes < fr.fetched_bytes


def test_dead_column_counter_on_rank_deficient_input():
    """A rank-2 16x16 matrix served twice: 14 of its 16 columns fall
    below the cutoff each time, and a full-rank one adds none."""
    rng = np.random.default_rng(3)
    low = (rng.standard_normal((16, 2))
           @ rng.standard_normal((2, 16))).astype(np.float32)
    full = rng.standard_normal((16, 16)).astype(np.float32)
    obs = Observability.enabled()
    srv = _server(obs)
    for a in (low, full, low):
        srv.submit(a, op="svd")
        srv.drain()
    fam = obs.metrics.counter("serve_svd_dead_columns_total",
                              labels=("bucket",))
    assert fam.labels((16, 16)).total == 28
    assert len(srv.stats.flush_records) == 3
