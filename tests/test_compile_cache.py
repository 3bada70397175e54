"""The compile-cache placement rule: ``$JAX_COMPILATION_CACHE_DIR`` when
set (and nothing set in code), else the fixed ``<checkout>/.jax_cache``."""
import pathlib

import jax

from repro.launch import compile_cache

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_is_honoured_and_nothing_is_set(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cc"))
    calls = _record_updates(monkeypatch)
    assert compile_cache.enable_compile_cache() == tmp_path / "cc"
    assert calls == []


def test_default_is_fixed_under_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    calls = _record_updates(monkeypatch)
    path = compile_cache.enable_compile_cache()
    assert path == REPO_ROOT / ".jax_cache"
    assert compile_cache.compile_cache_dir({}) == path   # same every call
    assert calls == [("jax_compilation_cache_dir", str(path))]


def test_empty_env_var_means_unset():
    assert (compile_cache.compile_cache_dir({compile_cache.ENV_VAR: ""})
            == REPO_ROOT / ".jax_cache")
