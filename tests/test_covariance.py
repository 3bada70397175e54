"""Block-streamed covariance (``core.covariance``): the fp32 error of the
panelled accumulation against float64, exact handling of a sample count
that does not fill its last panel, the ``normalize`` divisor, ``vmap``,
and the host-side standardisation."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import precision as prec
from repro.core.covariance import blocked_covariance, standardize


def _ints(m, n, seed=0):
    """Small integer-valued fp32 data: every product and partial sum of
    X^T X is an exact fp32 integer, so any panelling or summation order
    gives the same bits and a mismatch is a real bug."""
    rng = np.random.default_rng(seed)
    return rng.integers(-8, 9, (m, n)).astype(np.float32)


@pytest.mark.parametrize("m,n", [(64, 12), (96, 8), (128, 16)])
def test_blocked_covariance_within_fp32_budget(m, n):
    rng = np.random.default_rng(m + n)
    x = rng.standard_normal((m, n)).astype(np.float32)
    got = np.asarray(jax.jit(lambda a: blocked_covariance(a, block_m=32))(x))
    want = x.astype(np.float64).T @ x.astype(np.float64)
    err = prec.rel_frobenius(got, want)
    assert err < prec.ERROR_BUDGETS["fp32"]["covariance"]


@pytest.mark.parametrize("m,n", [(5, 3), (33, 7), (1, 4)])
def test_blocked_covariance_partial_last_panel_is_exact(m, n):
    """m not a multiple of ``block_m`` (and m < ``block_m``): the zero rows
    that fill the last panel add nothing."""
    x = _ints(m, n, seed=m)
    got = np.asarray(blocked_covariance(jnp.asarray(x), block_m=16))
    np.testing.assert_array_equal(got, x.T @ x)


@pytest.mark.parametrize("m", [6, 1])
def test_blocked_covariance_normalize_divides_by_m_minus_1(m):
    """``normalize`` divides by m - 1; a single sample divides by 1."""
    x = _ints(m, 5, seed=m)
    got = np.asarray(blocked_covariance(jnp.asarray(x), block_m=4,
                                        normalize=True))
    want = (x.T @ x) / np.float32(max(m - 1, 1))
    np.testing.assert_array_equal(got, want)


def test_blocked_covariance_vmaps_per_problem():
    xs = np.stack([_ints(40, 6, seed=s) for s in range(3)])
    fn = lambda a: blocked_covariance(a, block_m=16)
    got = np.asarray(jax.vmap(fn)(jnp.asarray(xs)))
    for b, x in enumerate(xs):
        np.testing.assert_array_equal(got[b], np.asarray(fn(jnp.asarray(x))))


def test_standardize_keeps_constant_column_scale_at_one():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((20, 4)).astype(np.float32)
    x[:, 2] = 7.0
    xs, mean, std = standardize(jnp.asarray(x))
    assert float(std[2]) == 1.0
    assert float(mean[2]) == 7.0
    assert not np.any(np.asarray(xs)[:, 2])
    np.testing.assert_allclose(np.asarray(jnp.std(xs, axis=0))[[0, 1, 3]],
                               1.0, rtol=1e-5)
