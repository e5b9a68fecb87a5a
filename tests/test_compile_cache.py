"""The one compile-cache placement rule
(``cruise_control_tpu.enable_persistent_compile_cache``):
$JAX_COMPILATION_CACHE_DIR, else ``solver.compile.cache.dir``, else
``<checkout>/.jax_cache``."""

import os

import jax
import pytest

import cruise_control_tpu
from cruise_control_tpu import warmstart
from cruise_control_tpu.config.cruise_control_config import CruiseControlConfig


@pytest.fixture()
def updates(monkeypatch):
    """jax.config.update calls, recorded instead of applied (the session's
    own cache placement must not move under the other tests)."""
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.__setitem__(key, value))
    return calls


def test_env_var_set_means_no_directory_set_in_code(monkeypatch, updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/by/deployment")
    cfg = CruiseControlConfig({"solver.compile.cache.dir": "/from/config"})
    assert warmstart.configure_compile_cache(cfg) \
        == jax.config.jax_compilation_cache_dir
    assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 1.0


def test_config_key_places_the_cache_when_env_unset(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cfg = CruiseControlConfig({"solver.compile.cache.dir": "/from/config"})
    assert warmstart.configure_compile_cache(cfg) == "/from/config"
    assert updates["jax_compilation_cache_dir"] == "/from/config"


def test_default_is_dot_jax_cache_in_the_checkout(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(cruise_control_tpu.__file__)))
    expected = os.path.join(checkout, ".jax_cache")
    assert warmstart.configure_compile_cache(CruiseControlConfig()) \
        == expected
    assert updates["jax_compilation_cache_dir"] == expected
    # The prewarm shape registry follows the directory.
    cfg = CruiseControlConfig({"solver.prewarm.enabled": True})
    from cruise_control_tpu.analyzer.optimizer import GoalOptimizer
    mgr = warmstart.ensure_prewarm(GoalOptimizer(cfg), cfg, start=False)
    assert mgr.registry.path == os.path.join(expected, "solver_shapes.json")
