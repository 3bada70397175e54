"""Compiles for a described TPU v5e: the main path's programs and kernels
at real widths, checked by the chip's own compiler with no chip attached.

Nothing here runs: a compile that passes says the program lowers, fits
the device and contains the kernels it should -- not that it is fast or
right (``chip_smoke.py`` runs it on the chip).  The topology is described
inside a module-scoped fixture, never at import, so every test worker
collects the same tests and only the worker given this file loads the TPU
compiler.

The MM-Engine needs a matmul block T that is a multiple of 128: the
compiler refuses T=16 blocks, so ``backend="pallas"`` serves with T=128.
"""
import os

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import PCAConfig
from repro.kernels import ops as kops
from repro.serving.solver import build_solver_fn

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, *structs):
    return jax.jit(fn).lower(*structs).compile()


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _solver_structs(sharding, bucket):
    return (_f32(sharding, 1, *bucket),
            *(jax.ShapeDtypeStruct((1,), jnp.int32, sharding=sharding)
              for _ in bucket))


def test_default_pca_solver_fits_one_v5e(one_chip):
    """The served default: XLA datapath, T=16 bucket, ExecutionSpec's 12
    sweeps, at mnist-28x28's full 70000x784."""
    fn = build_solver_fn("pca", PCAConfig(T=16, S=1, sweeps=12))
    compiled = _compile(fn, *_solver_structs(one_chip, (70000, 784)))
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 70000 * 784 * 4 <= used < V5E_HBM_BYTES, used


def test_default_svd_solver_fits_one_v5e(one_chip):
    """The served svd at 20-newsgroups' full 18846x1024 (bucket 18848):
    the Gram path, the back-projection and the refinement in one
    executable within one chip's memory."""
    fn = build_solver_fn("svd", PCAConfig(T=16, S=1, sweeps=12))
    compiled = _compile(fn, *_solver_structs(one_chip, (18848, 1024)))
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 2 * 18848 * 1024 * 4 <= used < V5E_HBM_BYTES, used


def test_pallas_eigh_runs_the_mm_engine(one_chip):
    """eigh on the kernel backend with the unified MM-Engine rotation
    datapath: the Jacobi rotations lower to the Pallas matmul."""
    fn = build_solver_fn("eigh", PCAConfig(T=128, S=1, sweeps=12,
                                           backend="pallas",
                                           rotation="matmul"))
    compiled = _compile(fn, *_solver_structs(one_chip, (784, 784)))
    assert "tpu_custom_call" in compiled.as_text()


KERNELS = {
    "mm_engine_matmul": (
        lambda a, b: kops.mm_engine_matmul(a, b, block=128,
                                           backend="pallas"),
        ((784, 70000), (70000, 784))),
    "dle_find_pivot": (
        lambda c: kops.dle_find_pivot(c, backend="pallas"), ((784, 784),)),
    "cordic_rotate_256": (
        lambda a, b, c: kops.cordic_rotate(a, b, c, backend="pallas"),
        ((256,), (256,), (256,))),
    "cordic_rotate_1024": (
        lambda a, b, c: kops.cordic_rotate(a, b, c, backend="pallas"),
        ((1024,), (1024,), (1024,))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, *(_f32(one_chip, *s) for s in shapes))
    assert "tpu_custom_call" in compiled.as_text()
