"""The persistent compile cache goes where the caller can find it again."""
from pathlib import Path

import jax
import pytest

from repro.utils import compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_env_dir_wins_and_is_not_overridden(monkeypatch, tmp_path,
                                            restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jax.config.jax_enable_compilation_cache


def test_default_dir_is_fixed_under_the_checkout(monkeypatch,
                                                 restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    checkout = Path(__file__).resolve().parents[1]
    path = compile_cache.enable_compile_cache()
    assert path == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert path == compile_cache.enable_compile_cache()  # stable across calls


def test_importing_benchmarks_leaves_the_cache_alone(restore_cache_config):
    """Only entry points turn the cache on: a test that imports a benchmark
    module must not start writing its CPU programs into the checkout."""
    import importlib

    import benchmarks.common

    jax.config.update("jax_enable_compilation_cache", False)
    importlib.reload(benchmarks.common)
    assert not jax.config.jax_enable_compilation_cache
