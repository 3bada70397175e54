"""Jacobi eigensolver: agreement with numpy.linalg.eigh across pivot /
rotation / angle modes + hypothesis property tests on the invariants."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare env: deterministic fallback sampler
    from _hypothesis_fallback import given, settings, st

from repro.core import (jacobi_eigh, jacobi_svd, offdiag_frobenius,
                        relative_offdiag, round_robin_rounds)


def _sym(n, seed=0, cond=None):
    rng = np.random.default_rng(seed)
    if cond is None:
        a = rng.standard_normal((n, n)).astype(np.float32)
        return (a + a.T) / 2
    eigs = np.geomspace(1.0, 1.0 / cond, n)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    return (q * eigs) @ q.T


@pytest.mark.parametrize("pivot", ["parallel", "cyclic", "paper"])
@pytest.mark.parametrize("rotation", ["rowcol", "matmul"])
def test_matches_numpy(pivot, rotation):
    n = 24
    c = jnp.asarray(_sym(n, 1))
    sweeps = 30 if pivot == "paper" else 12
    res = jacobi_eigh(c, sweeps=sweeps, pivot=pivot, rotation=rotation)
    ref = np.linalg.eigh(np.asarray(c))
    np.testing.assert_allclose(np.asarray(res.eigenvalues),
                               ref[0][::-1], rtol=1e-4, atol=1e-4)
    # eigenvector correctness up to sign: C v = lambda v
    v = np.asarray(res.eigenvectors)
    lhs = np.asarray(c) @ v
    rhs = v * np.asarray(res.eigenvalues)[None, :]
    np.testing.assert_allclose(lhs, rhs, atol=5e-4)


@pytest.mark.parametrize("pivot", ["parallel", "cyclic", "paper"])
@pytest.mark.parametrize("angle", ["atan2", "rutishauser", "cordic"])
def test_angle_modes(angle, pivot):
    c = jnp.asarray(_sym(16, 2))
    res = jacobi_eigh(c, sweeps=30 if pivot == "paper" else 10,
                      angle=angle, pivot=pivot)
    ref = np.linalg.eigh(np.asarray(c))[0][::-1]
    np.testing.assert_allclose(np.asarray(res.eigenvalues), ref,
                               rtol=1e-3, atol=1e-3)


def test_odd_dimension_padding():
    c = jnp.asarray(_sym(17, 3))
    res = jacobi_eigh(c, sweeps=12, pivot="parallel")
    ref = np.linalg.eigh(np.asarray(c))[0][::-1]
    np.testing.assert_allclose(np.asarray(res.eigenvalues), ref,
                               rtol=1e-4, atol=1e-4)
    assert res.eigenvectors.shape == (17, 17)


def test_fixed_50_sweep_schedule_ill_conditioned():
    """Paper Sec. VII-D: the 50-sweep factor of safety covers clustered
    spectra; well-conditioned data converges in 10-15."""
    c = jnp.asarray(_sym(32, 4, cond=1e6).astype(np.float32))
    res = jacobi_eigh(c, sweeps=50, track_history=True)
    hist = np.asarray(res.history)
    assert hist[-1] < 1e-6
    # noise floor reached well before the safety bound
    assert (hist < 1e-6).argmax() <= 15


def test_early_exit_tolerance():
    c = jnp.asarray(_sym(20, 5))
    res = jacobi_eigh(c, sweeps=50, tol=1e-5)
    assert float(res.off_norm) <= 1e-5


def test_round_robin_covers_all_pairs():
    for n in (4, 8, 14):
        rounds = round_robin_rounds(n)
        assert rounds.shape == (n - 1, n // 2, 2)
        seen = set()
        for rnd in rounds:
            cols = set()
            for p, q in rnd:
                assert p != q
                cols.update((int(p), int(q)))
                seen.add((int(p), int(q)))
            assert len(cols) == n  # disjoint within a round
        assert len(seen) == n * (n - 1) // 2


@pytest.mark.parametrize("pivot", ["parallel", "cyclic", "paper"])
def test_jacobi_svd(pivot):
    rng = np.random.default_rng(9)
    a = jnp.asarray(rng.standard_normal((40, 12)), jnp.float32)
    u, s, vt = jacobi_svd(a, sweeps=30 if pivot == "paper" else 12,
                          pivot=pivot)
    ref = np.linalg.svd(np.asarray(a), compute_uv=False)
    np.testing.assert_allclose(np.asarray(s), ref, rtol=1e-4, atol=1e-4)
    recon = np.asarray(u) * np.asarray(s)[None, :] @ np.asarray(vt)
    np.testing.assert_allclose(recon, np.asarray(a), atol=1e-3)


@pytest.mark.parametrize("pivot,rotation", [
    ("cyclic", "rowcol"), ("cyclic", "matmul"), ("paper", "rowcol"),
    ("paper", "matmul"), ("parallel", "matmul")])
def test_indexed_paths_keep_bucket_padding_exact(pivot, rotation):
    """The null-pivot guard on the pair-indexed rounds: a 7x7 problem
    zero-padded into a 12 bucket keeps its padded eigenvalues exactly 0 and
    its padded eigenvectors exactly the basis vectors e_j, with nothing
    mixed into or out of the live block."""
    n, nb = 7, 12
    c = np.zeros((nb, nb), np.float32)
    c[:n, :n] = _sym(n, 11)
    res = jacobi_eigh(jnp.asarray(c), sweeps=6, pivot=pivot,
                      rotation=rotation, sort=False)
    w, V = np.asarray(res.eigenvalues), np.asarray(res.eigenvectors)
    assert not np.any(w[n:])
    np.testing.assert_array_equal(V[n:, n:], np.eye(nb - n))
    assert not np.any(V[n:, :n]) and not np.any(V[:n, n:])
    ref = np.linalg.eigh(c[:n, :n])[0]
    np.testing.assert_allclose(np.sort(w[:n]), ref, rtol=1e-4, atol=1e-4)


def _cordic_case(engine):
    """(got, want) for one of the fixed-point CORDIC engines of
    ``core.cordic`` against its float formula, over inputs that reach
    every quadrant fold."""
    from repro.core import cordic
    rng = np.random.default_rng(13)
    if engine == "atan2":
        y = rng.uniform(-4, 4, 64).astype(np.float32)
        x = rng.uniform(-4, 4, 64).astype(np.float32)
        x[:4], y[:4] = (-1.0, -1.0, 2.0, -3.0), (1e-3, -1e-3, 0.0, 0.0)
        return cordic.cordic_atan2(y, x), np.arctan2(y, x)
    if engine == "sincos":
        th = rng.uniform(-np.pi, np.pi, 64).astype(np.float32)
        th[:3] = (np.pi, -np.pi / 2 - 0.1, np.pi / 2 + 0.1)
        return jnp.stack(cordic.cordic_sincos(th)), np.stack(
            [np.sin(th), np.cos(th)])
    apq, app, aqq = (rng.uniform(-3, 3, 64).astype(np.float32)
                     for _ in range(3))
    return (jnp.stack(cordic.rotation_params_cordic(apq, app, aqq)),
            np.stack(cordic.rotation_params(apq, app, aqq)))


@pytest.mark.parametrize("engine", ["atan2", "sincos", "rotation_params"])
def test_core_cordic_matches_float(engine):
    """The Q2.29 CORDIC engines behind ``angle="cordic"`` agree with the
    float formulas to the fixed-point granularity, in every quadrant."""
    got, want = _cordic_case(engine)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# hypothesis property tests
# ---------------------------------------------------------------------------

def _invariants_case(n, seed):
    c = jnp.asarray(_sym(n, seed))
    res = jacobi_eigh(c, sweeps=14)
    v = np.asarray(res.eigenvectors)
    w = np.asarray(res.eigenvalues)
    # V orthogonal
    np.testing.assert_allclose(v.T @ v, np.eye(n), atol=5e-4)
    # reconstruction C = V diag(w) V^T
    np.testing.assert_allclose(v @ np.diag(w) @ v.T, np.asarray(c),
                               atol=5e-3)
    # eigenvalues sorted descending
    assert np.all(np.diff(w) <= 1e-5)
    # trace preserved by similarity transforms
    np.testing.assert_allclose(w.sum(), np.trace(np.asarray(c)), rtol=1e-4,
                               atol=1e-3)


@settings(max_examples=4, deadline=None)
@given(n=st.integers(3, 20), seed=st.integers(0, 2 ** 16))
def test_property_invariants_fast(n, seed):
    _invariants_case(n, seed)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(n=st.integers(3, 20), seed=st.integers(0, 2 ** 16))
def test_property_invariants(n, seed):
    _invariants_case(n, seed)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(4, 16), seed=st.integers(0, 2 ** 16))
def test_property_offdiag_monotone_to_floor(n, seed):
    """Off-diagonal energy decreases (weak monotonicity modulo the
    numerical floor) and ends at the floor."""
    c = jnp.asarray(_sym(n, seed))
    res = jacobi_eigh(c, sweeps=12, track_history=True)
    hist = np.asarray(res.history)
    assert hist[-1] < 1e-5
    # each sweep reduces off-norm until the floor (allow tiny noise)
    above = hist > 1e-6
    deltas = np.diff(hist)
    assert np.all(deltas[above[:-1]] < 1e-3)


# ---------------------------------------------------------------------------
# tournament layout: the parallel row/column round without gathers
# ---------------------------------------------------------------------------

ANGLES = ("rutishauser", "atan2", "cordic")


def _tournament_sweep_fn(angle):
    from repro.core.cordic import ANGLE_MODES
    from repro.core.jacobi import (_enter_tournament, _leave_tournament,
                                   _tournament_sweep)

    def sweep(C, V):
        state = _tournament_sweep(*_enter_tournament(C, V), ANGLE_MODES[angle])
        return _leave_tournament(*state)
    return sweep


def _indexed_sweep_fn(angle):
    """The indexed chain: ``kref.jacobi_sweep_step`` over every round."""
    from repro.kernels import ref as kref

    def sweep(C, V):
        rounds = jnp.asarray(round_robin_rounds(C.shape[0]))
        step = lambda cv, pairs: (kref.jacobi_sweep_step(*cv, pairs,
                                                         angle=angle), None)
        return jax.lax.scan(step, (C, V), rounds)[0]
    return sweep


def _pair_sets():
    """A first and a last parallel round at n = 10, and one cyclic pair."""
    from repro.core.jacobi import cyclic_pairs
    rounds = round_robin_rounds(10)
    return {"parallel_first": rounds[0], "parallel_last": rounds[-1],
            "cyclic_pair": cyclic_pairs(10)[7]}


@pytest.mark.parametrize("pairs", ["parallel_first", "parallel_last",
                                   "cyclic_pair"])
@pytest.mark.parametrize("angle", ANGLES)
def test_indexed_round_equals_reference_step(angle, pairs):
    """One round of ``_sweep_scan`` (rowcol) is ``kref.jacobi_sweep_step``
    bit for bit: the reference the tournament tests hold the served round
    to is the round ``jacobi_eigh`` runs on its indexed paths."""
    from repro.core.cordic import ANGLE_MODES
    from repro.core.jacobi import _sweep_scan
    from repro.kernels import ref as kref
    C, V = _padded_problem(10, seed=5)
    P = jnp.asarray(_pair_sets()[pairs], jnp.int32)
    got = jax.jit(lambda C, V: _sweep_scan(C, V, P[None], ANGLE_MODES[angle],
                                           "rowcol", None))(C, V)
    want = jax.jit(lambda C, V: kref.jacobi_sweep_step(C, V, P,
                                                       angle=angle))(C, V)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert np.any(np.asarray(got[1]) != np.eye(10, dtype=np.float32))


def _padded_problem(n, batch=None, seed=0):
    """Symmetric C zero-padded to even size (as ``jacobi_eigh`` pads odd n)
    and V = I; with ``batch``, a stack of independent problems."""
    m = n + n % 2
    rng = np.random.default_rng(seed + n)
    shape = (n, n) if batch is None else (batch, n, n)
    a = rng.standard_normal(shape).astype(np.float32)
    c = (a + np.swapaxes(a, -1, -2)) / 2
    pad = [(0, 0)] * (c.ndim - 2) + [(0, m - n), (0, m - n)]
    v = np.broadcast_to(np.eye(m, dtype=np.float32), c.shape[:-2] + (m, m))
    return jnp.asarray(np.pad(c, pad)), jnp.asarray(v)


@pytest.mark.parametrize("n", [2, 4, 8, 10, 64, 784])
def test_tournament_slots_and_shift_follow_the_circle_method(n):
    """Slot i of the top row meets slot i of the bottom row: round by round
    the pairs of ``round_robin_rounds``; ``_shift`` moves the players from
    one round's slots to the next and, after n-1 moves, back to the start."""
    from repro.core.jacobi import _shift, tournament_slots
    slots = tournament_slots(n)
    assert slots.shape == (n - 1, 2, n // 2)
    pairs = np.sort(np.swapaxes(slots, 1, 2), axis=-1)
    np.testing.assert_array_equal(pairs, round_robin_rounds(n))
    top, bot = jnp.asarray(slots[0, 0]), jnp.asarray(slots[0, 1])
    for r in range(1, n):
        top, bot = _shift(top, bot, 0)
        expect = slots[r % (n - 1)]
        np.testing.assert_array_equal(np.asarray(top), expect[0])
        np.testing.assert_array_equal(np.asarray(bot), expect[1])


@pytest.mark.parametrize("batched", [False, True], ids=["one", "vmap"])
@pytest.mark.parametrize("angle", ANGLES)
@pytest.mark.parametrize("n", [2, 8, 9, 10, 64])
def test_tournament_sweep_equals_indexed_chain(n, angle, batched):
    """A full sweep on the tournament layout computes every entry of C and V
    as the indexed round does: bit for bit at fp32 on the CPU.

    Under ``vmap`` (a batch of four, as served) one exception: the CPU
    compiler fuses ``tau * tau`` into ``1 + tau * tau`` (an FMA) in the
    vectorised part of a loop and not in its remainder, and which lanes
    fall where follows the batch's memory order, which differs between the
    two programs; the indexed chain compiled with and without ``vmap``
    differs in the same way.  So the Rutishauser angle's vmapped sweep is
    held to rounding, 8 ulps of the largest entry; the other angle modes
    stay bitwise there too.
    """
    new = _tournament_sweep_fn(angle)
    ref = _indexed_sweep_fn(angle)
    if batched:
        new, ref = jax.vmap(new), jax.vmap(ref)
    C, V = _padded_problem(n, batch=4 if batched else None)
    got = jax.jit(new)(C, V)
    want = jax.jit(ref)(C, V)
    for g, w in zip(got, want):
        if batched and angle == "rutishauser":
            ulp = float(np.finfo(np.float32).eps) * float(jnp.max(jnp.abs(w)))
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                       atol=8 * ulp)
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("angle", ANGLES)
def test_tournament_sweep_keeps_bucket_padding_exact(angle):
    """A 7x7 problem zero-padded into a 12 bucket: through three sweeps the
    padded rows and columns of C stay exactly 0, and V's padded block
    exactly I with nothing mixed into the live block."""
    n, nb = 7, 12
    c, _ = _padded_problem(n)
    C = jnp.zeros((nb, nb), jnp.float32).at[:8, :8].set(c)
    V = jnp.eye(nb, dtype=jnp.float32)
    sweep = _tournament_sweep_fn(angle)
    C, V = jax.jit(lambda C, V: sweep(*sweep(*sweep(C, V))))(C, V)
    C, V = np.asarray(C), np.asarray(V)
    assert not np.any(C[n:, :]) and not np.any(C[:, n:])
    np.testing.assert_array_equal(V[n:, n:], np.eye(nb - n))
    assert not np.any(V[n:, :n]) and not np.any(V[:n, n:])
    assert np.any(C[:n, :n] != np.diag(np.diagonal(C[:n, :n])))  # live rotated


def test_served_round_holds_no_gather_or_scatter():
    """The default served ``pca_solve`` executable rotates on the tournament
    layout: no ``gather`` or ``scatter`` in the Jacobi loops' bodies (the
    masked sort after them may gather), and the ``jacobi_shift`` scope in
    their operations' metadata."""
    import re
    from repro.core import PCAConfig
    from repro.serving.solver import build_solver_fn
    fn = build_solver_fn("pca", PCAConfig(sweeps=2))
    x = jax.ShapeDtypeStruct((4, 96, 64), jnp.float32)
    i = jax.ShapeDtypeStruct((4,), jnp.int32)
    hlo = jax.jit(fn).lower(x, i, i).compile().as_text()
    comps = dict(re.findall(r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)\n\}",
                            hlo, re.S | re.M))
    called = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
    todo = re.findall(r"\bwhile\([^\n]*body=%?([\w.\-]+)", hlo)
    assert todo, "no while loop in the solver"
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        todo += called.findall(comps[name])
    loops = "\n".join(comps[name] for name in seen)
    assert not re.search(r"\b(gather|scatter)\(", loops)
    assert "/jacobi_shift/" in loops
