"""Mixed-precision policy: bf16-streamed / fp32 paths, the reference Gram
and the served solvers, vs the fp64 subprocess oracle, held to the
documented ``ERROR_BUDGETS``.

The oracle runs ``JAX_ENABLE_X64=1`` in a child process (the x64 switch
is global and import-time, so this process never flips it); one oracle
run per op is shared across tests via module-scoped fixtures.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import PCAConfig, precision as prec
from repro.core.jacobi import jacobi_eigh
from repro.kernels import ref as kref
from repro.serving.solver import build_solver_fn, jacobi_svd_batched

M, N, SWEEPS = 256, 12, 20


@pytest.fixture(scope="module")
def X():
    rng = np.random.default_rng(42)
    # mild conditioning spread so precision differences are visible but
    # the Jacobi solve still converges well inside SWEEPS
    base = rng.standard_normal((M, N))
    return (base * np.logspace(0, -2, N)[None, :]).astype(np.float32)


@pytest.fixture(scope="module")
def oracle_cov(X):
    return prec.run_fp64_oracle(X, "covariance")


@pytest.fixture(scope="module")
def oracle_eigh(X):
    return prec.run_fp64_oracle(X, "eigh", sweeps=SWEEPS)


@pytest.fixture(scope="module")
def oracle_svd(X):
    return prec.run_fp64_oracle(X, "svd", sweeps=SWEEPS)


# ---------------------------------------------------------------------------
# policy plumbing
# ---------------------------------------------------------------------------

def test_policy_dtypes():
    assert prec.operand_dtype("fp32") == jnp.float32
    assert prec.operand_dtype("bf16_fp32acc") == jnp.bfloat16
    assert prec.acc_dtype("bf16_fp32acc") == jnp.float32
    with pytest.raises(ValueError):
        prec.validate("fp16")


def test_serving_process_is_not_x64():
    """The whole point of the subprocess oracle: this process is fp32."""
    assert not prec.supports_x64()


# ---------------------------------------------------------------------------
# budgets vs the fp64 oracle
# ---------------------------------------------------------------------------

def _gram(X, precision):
    """The policy's Gram: operands cast to its streaming dtype, summed in
    its accumulator dtype (bf16 operands, fp32 accumulation for
    ``bf16_fp32acc``)."""
    return kref.covariance_gram(
        jnp.asarray(X).astype(prec.operand_dtype(precision)),
        acc_dtype=prec.acc_dtype(precision))


@pytest.mark.parametrize("precision", ["fp32", "bf16_fp32acc"])
def test_covariance_budget(X, oracle_cov, precision):
    C = _gram(X, precision)
    err = prec.rel_frobenius(np.asarray(C), oracle_cov["C"])
    budget = prec.ERROR_BUDGETS[precision]["covariance"]
    assert err < budget, f"{precision} covariance err {err} >= {budget}"


@pytest.mark.parametrize("precision", ["fp32", "bf16_fp32acc"])
def test_eigh_budget(X, oracle_eigh, precision):
    C = _gram(X, precision)
    res = jacobi_eigh(np.asarray(C), sweeps=SWEEPS)
    err = prec.rel_frobenius(np.asarray(res.eigenvalues),
                             oracle_eigh["eigenvalues"])
    budget = prec.ERROR_BUDGETS[precision]["eigh"]
    assert err < budget, f"{precision} eigh err {err} >= {budget}"


@pytest.mark.parametrize("precision", ["fp32", "bf16_fp32acc"])
def test_svd_budget(X, oracle_svd, precision):
    res = jacobi_svd_batched(X[None], sweeps=SWEEPS, precision=precision)
    err = prec.rel_frobenius(np.asarray(res.S[0]), oracle_svd["S"])
    budget = prec.ERROR_BUDGETS[precision]["svd"]
    assert err < budget, f"{precision} svd err {err} >= {budget}"


@pytest.mark.parametrize("precision", ["fp32", "bf16_fp32acc"])
def test_served_pca_budget(X, oracle_eigh, precision):
    """The served ``pca`` executable, unstandardized so its covariance is
    the oracle's X^T X, keeps the policy's eigenvalue budget."""
    fn = build_solver_fn("pca", PCAConfig(sweeps=SWEEPS, standardize=False,
                                          precision=precision))
    res = jax.jit(fn)(X[None], jnp.asarray([M], jnp.int32),
                      jnp.asarray([N], jnp.int32))
    err = prec.rel_frobenius(np.asarray(res.eigenvalues[0]),
                             oracle_eigh["eigenvalues"])
    budget = prec.ERROR_BUDGETS[precision]["eigh"]
    assert err < budget, f"{precision} served pca err {err} >= {budget}"


def test_bf16_halves_streamed_bytes():
    """The policy's entire value: the operand panels stream at 2 bytes."""
    assert jnp.dtype(prec.operand_dtype("bf16_fp32acc")).itemsize == 2
    assert jnp.dtype(prec.acc_dtype("bf16_fp32acc")).itemsize == 4


@pytest.mark.parametrize("op", ["pca", "svd"])
@pytest.mark.parametrize("policy,highest", [("fp32", True),
                                            ("bf16_fp32acc", False)])
def test_fp32_policy_matmuls_run_at_highest_precision(op, policy, highest):
    """A TPU runs an fp32 dot at DEFAULT precision as one bf16 pass, which
    misses the fp32 budgets; the fp32 policy's plain matmuls therefore
    carry HIGHEST into the lowered program, and the bf16 lane does not."""
    fn = build_solver_fn(op, PCAConfig(T=8, S=2, sweeps=2,
                                       precision=policy))
    structs = (jax.ShapeDtypeStruct((2, 32, 8), jnp.float32),
               jax.ShapeDtypeStruct((2,), jnp.int32),
               jax.ShapeDtypeStruct((2,), jnp.int32))
    text = jax.jit(fn).lower(*structs).as_text()
    assert ("HIGHEST" in text) == highest
    assert prec.matmul_precision(policy) == (
        jax.lax.Precision.HIGHEST if highest else None)
