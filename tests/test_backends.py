"""Backend-dispatch registry: golden interpret-vs-ref parity for every
registered op, resolution-order semantics, and per-bucket backend routing in
``PCAServer`` (distinct backend-qualified cache entries, identical results)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import backends
from repro.core import PCAConfig
from repro.kernels import ops
from repro.serving import BucketPolicy, PCAServer, threshold_router

# what the registry's auto rule resolves to on THIS host (pallas on TPU,
# interpret elsewhere) -- keeps these tests green on both host kinds
_AUTO = "pallas" if jax.default_backend() == "tpu" else "interpret"


# ---------------------------------------------------------------------------
# registry mechanics
# ---------------------------------------------------------------------------

def test_registry_lists_every_op_and_backend():
    assert set(backends.registered_ops()) == {
        "mm_engine_matmul", "dle_find_pivot", "cordic_rotate",
        "flash_attention", "mamba_scan"}
    for op in backends.registered_ops():
        assert backends.backends_for(op) == ("pallas", "interpret", "ref")


def test_registry_rejects_unknown_names():
    with pytest.raises(KeyError):
        backends.resolve("no_such_op")
    with pytest.raises(ValueError):
        backends.resolve("mm_engine_matmul", "hls")
    with pytest.raises(ValueError):
        backends.set_default_backend("hls")


def test_default_backend_resolution_order(monkeypatch):
    # auto: per host
    monkeypatch.delenv(backends.ENV_VAR, raising=False)
    assert backends.default_backend() == _AUTO
    # env escape hatch
    monkeypatch.setenv(backends.ENV_VAR, "ref")
    assert backends.default_backend() == "ref"
    # process default beats env
    backends.set_default_backend("interpret")
    try:
        assert backends.default_backend() == "interpret"
        # scoped override beats process default
        with backends.use_backend("ref"):
            assert backends.default_backend() == "ref"
        assert backends.default_backend() == "interpret"
    finally:
        backends.set_default_backend(None)
    assert backends.default_backend() == "ref"


def test_use_backend_reroutes_ops(monkeypatch):
    """The scoped override must change what ops.* actually run."""
    calls = []
    real = backends.resolve("mm_engine_matmul", "ref")

    def spy(a, b, **kw):
        calls.append("ref")
        return real(a, b, **kw)

    monkeypatch.setitem(
        backends.registry._REGISTRY["mm_engine_matmul"], "ref", spy)
    # distinctive shape/block so the jit trace (where resolve() runs) is
    # fresh and the spy is actually reached
    a = jnp.ones((3, 5), jnp.float32)
    b = jnp.ones((5, 4), jnp.float32)
    with backends.use_backend("ref"):
        ops.mm_engine_matmul(a, b, block=8)
    assert calls  # the spy ran -> dispatch honoured the context


# ---------------------------------------------------------------------------
# golden parity: interpret vs ref for every registered op
# ---------------------------------------------------------------------------

def _mm_inputs():
    rng = np.random.default_rng(42)
    a = jnp.asarray(rng.standard_normal((37, 21)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((21, 19)), jnp.float32)
    return (a, b), dict(block=16)


def _dle_inputs():
    rng = np.random.default_rng(43)
    c = rng.standard_normal((26, 26)).astype(np.float32)
    c = c + c.T
    return (jnp.asarray(c),), dict(tile=16)


def _cordic_inputs():
    rng = np.random.default_rng(44)
    k = 33
    return (jnp.asarray(rng.uniform(-3, 3, k), jnp.float32),
            jnp.asarray(rng.uniform(-3, 3, k), jnp.float32),
            jnp.asarray(rng.uniform(-3, 3, k), jnp.float32)), dict(block=16)


def _fa_inputs():
    rng = np.random.default_rng(45)
    q = jnp.asarray(rng.standard_normal((2, 32, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 32, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 32, 16)), jnp.float32)
    return (q, k, v), dict(causal=True, block_q=16, block_k=16)


def _ms_inputs():
    rng = np.random.default_rng(46)
    b, l, d, n = 2, 24, 8, 4
    return (jnp.asarray(rng.standard_normal((b, l, d)), jnp.float32),
            jnp.asarray(rng.uniform(0.01, 0.2, (b, l, d)), jnp.float32),
            jnp.asarray(-rng.uniform(0.5, 2, (d, n)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, l, n)), jnp.float32),
            jnp.asarray(rng.standard_normal((b, l, n)), jnp.float32),
            jnp.asarray(rng.standard_normal((d,)), jnp.float32)), \
        dict(chunk=8)


# per-op (wrapper, inputs, tolerance): the CORDIC tolerance covers its
# Q2.29 fixed-point angle quantisation vs the float-exact reference
_PARITY_CASES = {
    "mm_engine_matmul": (ops.mm_engine_matmul, _mm_inputs, 1e-5),
    "dle_find_pivot": (ops.dle_find_pivot, _dle_inputs, 0.0),
    "cordic_rotate": (ops.cordic_rotation_params, _cordic_inputs, 3e-7),
    "flash_attention": (ops.flash_attention, _fa_inputs, 2e-5),
    "mamba_scan": (ops.mamba_scan, _ms_inputs, 1e-4),
}


def test_every_registered_op_has_a_parity_case():
    assert set(_PARITY_CASES) == set(backends.registered_ops())


@pytest.mark.parametrize("op", sorted(_PARITY_CASES))
def test_interpret_matches_ref(op):
    fn, make_inputs, tol = _PARITY_CASES[op]
    args, kw = make_inputs()
    got = fn(*args, backend="interpret", **kw)
    want = fn(*args, backend="ref", **kw)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# PCAConfig backend plumbing
# ---------------------------------------------------------------------------

def test_pca_config_backend_names_matmul_fn():
    assert PCAConfig().matmul_fn() is None
    assert not PCAConfig().use_pallas
    cfg = PCAConfig(T=16, backend="interpret")
    mm = cfg.matmul_fn()
    rng = np.random.default_rng(47)
    a = jnp.asarray(rng.standard_normal((9, 7)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((7, 5)), jnp.float32)
    np.testing.assert_allclose(np.asarray(mm(a, b)),
                               np.asarray(a) @ np.asarray(b),
                               rtol=2e-4, atol=2e-4)
    assert PCAConfig(backend="pallas").use_pallas


# ---------------------------------------------------------------------------
# per-bucket backend routing in PCAServer
# ---------------------------------------------------------------------------

def _sym(n, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    return (a + a.T) / 2


def _routed_server():
    # small bucket (8, 8) -> plain XLA; large bucket (24, 24) -> the Pallas
    # MM-Engine under the interpreter (the CPU-runnable stand-in for the
    # compiled "pallas" backend)
    return PCAServer(PCAConfig(T=8, S=2, sweeps=14),
                     policy=BucketPolicy(T=8), max_delay_s=1e9,
                     backend_router=threshold_router(
                         16, large="interpret", small=None))


def test_threshold_router_boundaries():
    route = threshold_router(16, large="pallas", small="ref")
    assert route("eigh", (8, 8)) == "ref"
    assert route("eigh", (16, 16)) == "pallas"
    assert route("svd", (24, 8)) == "pallas"
    # default large="auto" resolves per host, so threshold_router(n) is
    # safe on any machine
    assert threshold_router(16)("eigh", (16, 16)) == _AUTO
    assert threshold_router(16)("eigh", (8, 8)) is None
    assert set(backends.available()) >= {"interpret", "ref"}
    assert ("pallas" in backends.available()) == (_AUTO == "pallas")


def test_threshold_router_resolves_auto_once_at_construction(monkeypatch):
    """The "auto" sentinel is resolved when the router is built, not on
    every route call -- and telemetry therefore only ever sees the
    concrete backend name."""
    calls = []
    orig = backends.default_backend

    def counting_default():
        calls.append(1)
        return orig()

    monkeypatch.setattr(backends, "default_backend", counting_default)
    route = threshold_router(16)            # large="auto"
    assert calls == [1]                     # resolved exactly once, eagerly
    for n in (8, 16, 24, 32):
        assert route("eigh", (n, n)) != "auto"
    assert calls == [1]                     # ...and never again per route

    srv = PCAServer(PCAConfig(T=8, S=2, sweeps=14), policy=BucketPolicy(T=8),
                    max_delay_s=1e9,
                    backend_router=threshold_router(16, large="auto",
                                                    small="ref"))
    srv.solve_many([_sym(20, seed=5), _sym(20, seed=6)], op="eigh")
    recorded = {r.backend for r in srv.stats.records}
    assert recorded == {_AUTO}              # the concrete name, no sentinel


def test_server_routes_buckets_to_different_backends():
    srv = _routed_server()
    mats = [_sym(6, seed=1), _sym(6, seed=2), _sym(20, seed=3),
            _sym(20, seed=4)]
    results = srv.solve_many(mats, op="eigh")
    for m, r in zip(mats, results):
        ref = np.linalg.eigh(m)[0][::-1]
        np.testing.assert_allclose(r.eigenvalues, ref, rtol=1e-3, atol=1e-3)
    routed = {(r.bucket, r.backend) for r in srv.stats.records}
    assert routed == {((8, 8), None), ((24, 24), "interpret")}
    # distinct backend-qualified cache entries, one per bucket and rung
    assert sorted((k[1], k[2]) for k in srv._cache) == [
        ((8, 8), 1), ((8, 8), 2), ((24, 24), 1), ((24, 24), 2)]
    assert {k[3].backend for k in srv._cache} == {None, "interpret"}


def test_routed_backends_agree_with_unrouted_server():
    """Backend choice must not change results: the routed server and an
    all-XLA server agree bitwise-tightly on the same traffic."""
    mats = [_sym(20, seed=7), _sym(20, seed=8)]
    routed = _routed_server().solve_many(mats, op="eigh")
    plain = PCAServer(PCAConfig(T=8, S=2, sweeps=14),
                      policy=BucketPolicy(T=8),
                      max_delay_s=1e9).solve_many(mats, op="eigh")
    for a, b in zip(routed, plain):
        np.testing.assert_allclose(a.eigenvalues, b.eigenvalues,
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.abs(a.eigenvectors),
                                   np.abs(b.eigenvectors), atol=1e-4)


def test_same_bucket_two_backends_two_cache_entries():
    """Flipping the router between runs must MISS the cache (the key is
    backend-qualified), not silently reuse the other backend's executable."""
    srv = _routed_server()
    srv.solve_many([_sym(20, seed=1), _sym(20, seed=2)], op="eigh")
    assert srv.stats.cache_misses == 1
    srv.backend_router = threshold_router(16, large=None, small=None)
    srv.solve_many([_sym(20, seed=3), _sym(20, seed=4)], op="eigh")
    # two backends x rungs 1 and 2
    assert srv.stats.cache_misses == 2 and len(srv._cache) == 4
