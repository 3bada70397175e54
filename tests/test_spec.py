"""Declarative server construction: ``ServerSpec`` validation, the
JSON / CLI-args / kwargs round trips, flag-conflict rejection, the
13-kwarg compatibility shim's deprecation contract, and the parity
claim -- a spec-built server serves bitwise-identically to the
kwarg-built server it replaces."""
import dataclasses
import json
import pathlib
import types
import warnings

import numpy as np
import pytest

from repro.core import PCAConfig
from repro.serving import (BucketPolicy, CacheSpec, ControllerSpec,
                           ExecutionSpec, ObsSpec, PCAServer,
                           SchedulingSpec, ServerSpec, SpecConflictError,
                           build_server, resolve_spec, validate_args)

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _sym(n, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    return (a + a.T) / 2


# the serve_pca parser defaults for the flags validate_args inspects
DEFAULTS = {"tile": 16, "bucket_policy": "tile", "max_batch": 4,
            "timeout_ms": 10.0, "inflight": 1, "mesh": "none",
            "sweeps": 12, "cache_dir": None, "warmup": None,
            "slo_ms": None, "trace_out": None, "metrics_out": None,
            "jax_profile": None, "controller": "off",
            "profile_window": 5.0, "reprofile_every": 1.0,
            "hysteresis": 0.15, "min_dwell": 2.0, "spec": None,
            "autotune": "off", "arrivals": None, "profile_in": None,
            "degrade_frac": 0.5, "admission": "shed",
            "measure_top_k": 3}


def _ns(**kw):
    ns = types.SimpleNamespace(**DEFAULTS)
    for k, v in kw.items():
        setattr(ns, k, v)
    return ns


# ---------------------------------------------------------------------------
# the spec itself
# ---------------------------------------------------------------------------

def test_spec_is_frozen_and_validates():
    spec = ServerSpec()
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.scheduling = SchedulingSpec(T=8)
    with pytest.raises(ValueError, match="unknown bucket mode"):
        ServerSpec(scheduling=SchedulingSpec(mode="fib")).validate()
    with pytest.raises(ValueError, match="must be >= 1"):
        ServerSpec(scheduling=SchedulingSpec(T=0)).validate()
    with pytest.raises(ValueError, match="hysteresis"):
        ServerSpec(controller=ControllerSpec(
            enabled=True, hysteresis=1.5)).validate()
    # controller guards only apply when the controller is on
    ServerSpec(controller=ControllerSpec(hysteresis=1.5)).validate()


def test_spec_derives_config():
    spec = ServerSpec(scheduling=SchedulingSpec(T=8, max_batch=2),
                      execution=ExecutionSpec(sweeps=7, precision="fp32"))
    cfg = spec.config()
    assert cfg.T == 8 and cfg.S == 2 and cfg.sweeps == 7
    pol = spec.scheduling.policy()
    assert isinstance(pol, BucketPolicy) and pol.T == 8


def test_spec_json_round_trip(tmp_path):
    spec = ServerSpec(
        scheduling=SchedulingSpec(mode="pow2", T=8, pow2_cap=32,
                                  max_batch=2, max_delay_s=0.5,
                                  max_inflight=3),
        execution=ExecutionSpec(mesh="auto", sweeps=9),
        cache=CacheSpec(cache_dir=str(tmp_path / "cache")),
        obs=ObsSpec(slo_ms=250.0, trace_out="trace.json"),
        controller=ControllerSpec(enabled=True, window_s=2.0,
                                  hysteresis=0.05,
                                  meshes=("none", "auto")))
    assert ServerSpec.from_json(spec.to_json()) == spec
    doc = json.loads(spec.to_json())           # valid JSON with a format tag
    assert doc["server_spec"] == 1
    path = tmp_path / "server.json"
    spec.save(path)
    assert ServerSpec.load(path) == spec
    # partial documents fill defaults, unknown sub-keys are ignored
    partial = ServerSpec.from_json('{"scheduling": {"T": 8}}')
    assert partial.scheduling.T == 8
    assert partial.execution == ExecutionSpec()


@pytest.mark.parametrize("fused", [False, True])
def test_spec_from_json_retired_fused_key(fused):
    """``execution.fused`` named the removed fused kernel path.  ``false``
    (which the benchmark configs still carry) loads as if the key were
    absent; ``true`` is refused rather than served by the unfused path."""
    doc = json.loads(ServerSpec().to_json())
    doc["execution"]["fused"] = fused
    if fused:
        with pytest.raises(ValueError, match="fused"):
            ServerSpec.from_json(json.dumps(doc))
        return
    assert ServerSpec.from_json(json.dumps(doc)) == ServerSpec()
    configs = sorted(REPO_ROOT.glob("bench/configs/*.json"))
    assert configs
    for path in configs:
        doc = json.loads(path.read_text())["server_spec"]
        loaded = ServerSpec.from_json(json.dumps(doc))
        doc.get("execution", {}).pop("fused", None)
        assert loaded == ServerSpec.from_json(json.dumps(doc))


def test_spec_from_args_and_cli_round_trip():
    ns = _ns(tile=8, bucket_policy="pow2", max_batch=2, timeout_ms=20.0,
             inflight=2, sweeps=9, controller="on", profile_window=2.0,
             reprofile_every=0.5, hysteresis=0.1, min_dwell=1.0,
             slo_ms=100.0)
    spec = ServerSpec.from_args(ns)
    assert spec.scheduling == SchedulingSpec(mode="pow2", T=8, max_batch=2,
                                             max_delay_s=0.02,
                                             max_inflight=2)
    assert spec.execution.sweeps == 9
    assert spec.obs.slo_ms == 100.0 and spec.obs.armed
    assert spec.controller == ControllerSpec(
        enabled=True, window_s=2.0, reprofile_every_s=0.5, hysteresis=0.1,
        min_dwell_s=1.0)
    # args -> spec -> JSON -> spec is lossless
    assert ServerSpec.from_json(spec.to_json()) == spec
    # a bare namespace resolves to the defaults
    assert ServerSpec.from_args(types.SimpleNamespace()) == ServerSpec()


def test_spec_from_args_grows_mesh_axis():
    assert ServerSpec.from_args(_ns()).controller.meshes == ("none",)
    spec = ServerSpec.from_args(_ns(mesh="auto"))
    assert spec.execution.mesh == "auto"
    assert spec.controller.meshes == ("none", "auto")


# ---------------------------------------------------------------------------
# flag-conflict validation
# ---------------------------------------------------------------------------

def test_spec_file_conflicts_with_explicit_flags(tmp_path):
    path = tmp_path / "server.json"
    ServerSpec().save(path)
    with pytest.raises(SpecConflictError, match="--tile.*scheduling.T"):
        validate_args(_ns(spec=str(path), tile=8), DEFAULTS)
    # a flag at its parser default is not "explicitly set"
    validate_args(_ns(spec=str(path)), DEFAULTS)
    # and resolve_spec prefers the file when given
    assert resolve_spec(_ns(spec=str(path)), DEFAULTS) == ServerSpec()


def test_controller_flag_conflicts():
    with pytest.raises(SpecConflictError, match="--autotune"):
        validate_args(_ns(controller="on", autotune="analytic"), DEFAULTS)
    with pytest.raises(SpecConflictError, match="--hysteresis"):
        validate_args(_ns(hysteresis=0.05), DEFAULTS)
    with pytest.raises(SpecConflictError, match="--min-dwell"):
        validate_args(_ns(min_dwell=1.0), DEFAULTS)
    # the same knobs are fine once the controller is on
    validate_args(_ns(controller="on", hysteresis=0.05, min_dwell=1.0),
                  DEFAULTS)


def test_open_loop_and_mode_scoped_conflicts():
    with pytest.raises(SpecConflictError, match="--warmup.*--arrivals"):
        validate_args(_ns(arrivals="poisson", warmup="p.json"), DEFAULTS)
    with pytest.raises(SpecConflictError, match="--autotune.*--arrivals"):
        validate_args(_ns(arrivals="poisson", autotune="analytic"),
                      DEFAULTS)
    with pytest.raises(SpecConflictError, match="--degrade-frac"):
        validate_args(_ns(degrade_frac=0.25), DEFAULTS)
    validate_args(_ns(degrade_frac=0.25, admission="degrade"), DEFAULTS)
    with pytest.raises(SpecConflictError, match="--measure-top-k"):
        validate_args(_ns(measure_top_k=5), DEFAULTS)
    validate_args(_ns(measure_top_k=5, autotune="measured"), DEFAULTS)


# ---------------------------------------------------------------------------
# the deprecation shim
# ---------------------------------------------------------------------------

def test_kwarg_soup_warns_and_points_at_the_spec():
    cfg = PCAConfig(T=8, S=2, sweeps=6)
    with pytest.warns(DeprecationWarning, match="PCAServer.from_spec"):
        PCAServer(cfg, policy=BucketPolicy(T=8), max_batch=2,
                  max_delay_s=10.0)
    # one or two kwargs is a tweak, not a configuration: no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        PCAServer(cfg, max_delay_s=10.0, max_batch=2)
    # the spec path builds with the same kwargs internally, silently
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        build_server(ServerSpec(
            scheduling=SchedulingSpec(T=8, max_batch=2, max_delay_s=10.0)))


# ---------------------------------------------------------------------------
# construction parity
# ---------------------------------------------------------------------------

def _burst():
    return [_sym(n, seed=n) for n in (5, 9, 12, 7)]


def test_spec_built_server_matches_kwarg_built_bitwise():
    spec = ServerSpec(
        scheduling=SchedulingSpec(mode="tile", T=8, max_batch=2,
                                  max_delay_s=10.0),
        execution=ExecutionSpec(sweeps=8))
    a = build_server(spec)
    assert a.spec == spec
    with pytest.warns(DeprecationWarning):
        b = PCAServer(PCAConfig(T=8, S=2, sweeps=8),
                      policy=BucketPolicy(T=8), max_batch=2,
                      max_delay_s=10.0)
    for ra, rb in zip(a.solve_many(_burst()), b.solve_many(_burst())):
        np.testing.assert_array_equal(ra.eigenvalues, rb.eigenvalues)
        np.testing.assert_array_equal(ra.eigenvectors, rb.eigenvectors)
    assert a.describe_plan() == b.describe_plan()


def test_from_spec_classmethod_is_build_server():
    spec = ServerSpec(scheduling=SchedulingSpec(T=8, max_delay_s=10.0))
    srv = PCAServer.from_spec(spec)
    assert srv.spec == spec and srv.policy.T == 8
    assert srv.max_delay_s == 10.0


def test_build_server_arms_obs_and_controller_only_when_asked():
    plain = build_server(ServerSpec())
    assert plain.obs is None
    assert plain.controller is None
    armed = build_server(ServerSpec(obs=ObsSpec(slo_ms=100.0)))
    assert armed.obs is not None and armed.obs.slo is not None
    steered = build_server(ServerSpec(
        controller=ControllerSpec(enabled=True, window_s=1.0)))
    assert steered.controller.server is steered
    assert steered.controller.window_s == 1.0


def test_jax_profile_alone_does_not_arm_obs():
    """A profiled serve runs the uninstrumented path users run; only the
    SLO, trace and metrics outputs arm the bundle."""
    obs = ObsSpec(jax_profile="/profile/dir")
    assert not obs.armed
    assert build_server(ServerSpec(obs=obs)).obs is None
    assert ObsSpec(jax_profile="/profile/dir", trace_out="t.json").armed


def test_build_server_injects_shared_clock():
    t = [7.0]
    srv = build_server(ServerSpec(obs=ObsSpec(slo_ms=100.0)),
                       clock=lambda: t[0])
    assert srv.clock() == 7.0
    assert srv.obs.clock() == 7.0               # obs rides the same clock
