"""The served thin SVD against a float64 reference, at small sizes.

``jacobi_svd_batched`` runs the paper's Gram path, then refinement sweeps
on H = (A V)^T (A V) until U is orthonormal to nb * eps_f32; they resolve
the small singular values to eps * cond relative (not eps * cond^2), and
its cutoff zeroes only the columns below nb * eps_f32 * s_max.  Inputs:

  * tall, 512 x 64, singular values geomspace(1, 1e-3): condition number
    1000.  The cutoff used before the refinement, sqrt(nb * eps_f32) =
    2.76e-3, zeroes its last 10 columns;
  * wide rank-deficient, 512 x 256 of rank 200, its live singular values
    geomspace(1, 1 / cond) at cond 1e3 and 1e4: 56 dead columns, and at
    the old cutoff sqrt(256 * eps_f32) = 5.5e-3 also 50 live ones.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pca import PCAConfig
from repro.serving import (PCAServer, ServerSpec, build_solver_fn,
                           jacobi_eigh_batched, jacobi_svd_batched)

EPS = float(np.finfo(np.float32).eps)
M, N, COND = 512, 64, 1e3
OLD_RCOND = float(np.sqrt(N * EPS))
SWEEPS = ServerSpec().execution.sweeps
SEEDS = (0, 1, 2, 3)

# Bounds, each with room over the largest reading of the served answer on
# the CPU at these seeds (and at seeds 4-11), and far below the reading at
# the old cutoff:
C_VALUES = 1.0        # |s - s64| / s64 <= C_VALUES * eps * cond; read <= 0.06
ORTH = 1e-5           # max |U^T U - I|; read <= 6e-7; old cutoff: 1.0
RESIDUAL = 5e-5       # ||A - U S V^T|| / ||A||; read <= 7e-6; old: 2.5e-3


def tall(seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    u, _ = np.linalg.qr(rng.standard_normal((M, N)))
    v, _ = np.linalg.qr(rng.standard_normal((N, N)))
    return ((u * np.geomspace(1.0, 1.0 / COND, N)) @ v.T).astype(np.float32)


def measure(a, U, S, Vt):
    a = np.asarray(a, np.float64)
    U, S, Vt = (np.asarray(x, np.float64) for x in (U, S, Vt))
    s64 = np.linalg.svd(a, compute_uv=False)
    return {
        "values": float(np.max(np.abs(S - s64) / s64)) / (EPS * COND),
        "orth": float(np.abs(U.T @ U - np.eye(U.shape[1])).max()),
        "residual": float(np.linalg.norm(a - (U * S) @ Vt)
                          / np.linalg.norm(a)),
    }


@pytest.fixture(scope="module")
def served():
    """Each seed's matrix served through ``PCAServer.submit(op="svd")``
    at ``ServerSpec`` defaults, and its answer."""
    srv = PCAServer.from_spec(ServerSpec())
    mats = {seed: tall(seed) for seed in SEEDS}
    tickets = {seed: srv.submit(a, op="svd") for seed, a in mats.items()}
    srv.drain()
    return {seed: (mats[seed], tickets[seed].result()) for seed in SEEDS}


@pytest.mark.parametrize("seed", SEEDS)
def test_served_svd_matches_float64(served, seed):
    a, ans = served[seed]
    got = measure(a, ans.U, ans.S, ans.Vt)
    assert got["values"] <= C_VALUES, got
    assert got["orth"] <= ORTH, got
    assert got["residual"] <= RESIDUAL, got
    assert np.all(ans.S > 0) and np.all(np.diff(ans.S) <= 0)


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_old_cutoff_fails_the_bounds(seed):
    """The cutoff the solver had before the refinement zeroes the 10
    columns below 2.76e-3 * s_max, so U is no longer orthonormal and A is
    not reconstructed; and the Gram path's own factors (the square roots of
    its eigenvalues, which the solver served before, and U = A V / s)
    miss the eps * cond bound by orders of magnitude and leave U far from
    orthonormal."""
    a = tall(seed)
    res = jacobi_svd_batched(a[None], sweeps=SWEEPS, rcond=OLD_RCOND)
    assert int(res.n_dead[0]) == 10
    got = measure(a, res.U[0], res.S[0], res.Vt[0])
    assert got["orth"] > ORTH and got["residual"] > RESIDUAL, got
    hi = jax.lax.Precision.HIGHEST
    gram = jnp.matmul(a.T, a, precision=hi)[None]
    eig = jacobi_eigh_batched(gram, sweeps=SWEEPS)
    s_gram = np.sqrt(np.maximum(np.asarray(eig.eigenvalues[0], np.float64),
                                0.0))
    v_gram = np.asarray(eig.eigenvectors[0])
    u_gram = np.asarray(jnp.matmul(a, v_gram, precision=hi)) / s_gram
    got = measure(a, u_gram, s_gram, v_gram.T)
    assert got["values"] > C_VALUES and got["orth"] > ORTH, got


def test_rank_deficient_counts_dead_columns():
    rng = np.random.default_rng(3)
    n, rank = 16, 2
    a = (rng.standard_normal((n, rank))
         @ rng.standard_normal((rank, n))).astype(np.float32)
    res = jacobi_svd_batched(a[None], sweeps=SWEEPS)
    assert int(res.n_dead[0]) == n - rank
    S = np.asarray(res.S[0])
    assert np.all(S[rank:] == 0.0) and np.all(S[:rank] > 0.0)


@pytest.mark.parametrize("tol", [1e-6, 1e-4])
@pytest.mark.parametrize("case", ["cond1000", "rank_deficient"])
def test_tol_leaves_the_refinement_whole(case, tol):
    """``PCAConfig.tol`` stops the Gram path's Jacobi early; the
    refinement keeps sweeping until U is orthonormal, so the answer holds
    the same bounds and the dead columns are still zeroed."""
    fn = jax.jit(build_solver_fn("svd", PCAConfig(T=16, tol=tol)))
    if case == "cond1000":
        a = tall(0)
    else:
        rng = np.random.default_rng(3)
        a = (rng.standard_normal((16, 2))
             @ rng.standard_normal((2, 16))).astype(np.float32)
    m, n = a.shape
    res = fn(a[None], jnp.array([m]), jnp.array([n]))
    S = np.asarray(res.S[0])
    if case == "rank_deficient":
        assert int(res.n_dead[0]) == 14
        assert np.all(S[2:] == 0.0) and np.all(S[:2] > 0.0)
        return
    got = measure(a, res.U[0], S, res.Vt[0])
    assert got["values"] <= C_VALUES, got
    assert got["orth"] <= ORTH and got["residual"] <= RESIDUAL, got


WIDE_M, WIDE_N, WIDE_RANK = 512, 256, 200
WIDE = [(seed, 1e3) for seed in SEEDS] + [(seed, 1e4) for seed in SEEDS[:2]]
# Bounds at the wide shape: the refinement stops once U is orthonormal to
# WIDE_N * eps_f32 (read <= 1.4e-5 at seeds 0-5 of both conds); the live
# values read <= 0.35 * eps * cond; the residual reads <= 2.9e-5, and 5.4e-3
# at the old cutoff.
WIDE_ORTH = WIDE_N * EPS
WIDE_RESIDUAL = 1e-4


def wide(seed: int, cond: float) -> np.ndarray:
    rng = np.random.default_rng(seed)
    u, _ = np.linalg.qr(rng.standard_normal((WIDE_M, WIDE_RANK)))
    v, _ = np.linalg.qr(rng.standard_normal((WIDE_N, WIDE_RANK)))
    return ((u * np.geomspace(1.0, 1.0 / cond, WIDE_RANK)) @ v.T
            ).astype(np.float32)


def measure_live(a, U, S, Vt, cond):
    """``measure`` of the kept columns against the float64 rank-200
    factorisation, and how many columns were served as dead."""
    a = np.asarray(a, np.float64)
    U, S, Vt = (np.asarray(x, np.float64) for x in (U, S, Vt))
    s64 = np.linalg.svd(a, compute_uv=False)[:WIDE_RANK]
    kept = S > 0
    Uk = U[:, kept]
    return {
        "dead": int(np.sum(~kept)),
        "values": float(np.max(np.abs(S[:WIDE_RANK] - s64) / s64))
        / (EPS * cond),
        "orth": float(np.abs(Uk.T @ Uk - np.eye(Uk.shape[1])).max()),
        "residual": float(np.linalg.norm(a - (U * S) @ Vt)
                          / np.linalg.norm(a)),
    }


@pytest.fixture(scope="module")
def served_wide():
    srv = PCAServer.from_spec(ServerSpec())
    mats = {key: wide(*key) for key in WIDE}
    tickets = {key: srv.submit(a, op="svd") for key, a in mats.items()}
    srv.drain()
    return {key: (mats[key], tickets[key].result()) for key in WIDE}


@pytest.mark.parametrize("seed,cond", WIDE)
def test_wide_rank_deficient_served(served_wide, seed, cond):
    """At a 256-wide bucket through ``PCAServer.submit`` at ``ServerSpec``
    defaults: exactly the 56 dead columns are zeroed, no live one, and
    the kept columns hold the bounds."""
    a, ans = served_wide[(seed, cond)]
    got = measure_live(a, ans.U, ans.S, ans.Vt, cond)
    assert got["dead"] == WIDE_N - WIDE_RANK, got
    assert got["values"] <= C_VALUES, got
    assert got["orth"] <= WIDE_ORTH, got
    assert got["residual"] <= WIDE_RESIDUAL, got


def test_wide_rank_deficient_fails_at_the_old_cutoff():
    """sqrt(nb * eps_f32) = 5.5e-3 at nb = 256 zeroes 50 live columns
    besides the 56 dead ones, and A is no longer reconstructed."""
    a = wide(0, 1e3)
    res = jacobi_svd_batched(a[None], sweeps=SWEEPS,
                             rcond=float(np.sqrt(WIDE_N * EPS)))
    got = measure_live(a, res.U[0], res.S[0], res.Vt[0], 1e3)
    assert got["dead"] == 106 and got["residual"] > WIDE_RESIDUAL, got


def test_filler_and_padding_stay_exact():
    """A zero filler slot and a problem padded into a wider bucket: the
    padded columns of U, S and V stay exactly zero or exact basis
    vectors, and nothing there counts as dead."""
    a = tall(0)[:, :40]
    batch = np.zeros((2, M, N), np.float32)
    batch[0, :, :40] = a
    res = jacobi_svd_batched(batch, n_rows=np.array([M, 0]),
                             n_cols=np.array([40, 0]), sweeps=SWEEPS)
    U, S, Vt = (np.asarray(x) for x in (res.U, res.S, res.Vt))
    assert np.all(U[0, :, 40:] == 0) and np.all(S[0, 40:] == 0)
    assert np.all(Vt[0, 40:, :40] == 0) and np.all(Vt[0, :40, 40:] == 0)
    assert np.all(U[1] == 0) and np.all(S[1] == 0)
    assert res.n_dead.tolist() == [0, 0]
    got = measure(a, U[0, :, :40], S[0, :40], Vt[0, :40, :40])
    assert got["orth"] <= ORTH and got["residual"] <= RESIDUAL, got


@pytest.mark.parametrize("op,scopes", [
    ("svd", {"covariance", "jacobi_sweep", "back_projection",
             "svd_refine"}),
    ("pca", {"covariance", "jacobi_sweep"}),
])
def test_name_scopes_of_each_executable(op, scopes):
    """The svd executable marks its back-projection and refinement; the
    pca executable runs none of that code."""
    fn = build_solver_fn(op, PCAConfig(T=16, sweeps=2))
    x = jax.ShapeDtypeStruct((2, 48, 16), jnp.float32)
    i = jax.ShapeDtypeStruct((2,), jnp.int32)
    hlo = jax.jit(fn).lower(x, i, i).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    found = {scope for scope in ("covariance", "jacobi_sweep",
                                 "back_projection", "svd_refine")
             if any(f"/{scope}/" in name for name in names)}
    assert found == scopes
