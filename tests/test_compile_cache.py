"""Where the launchers keep JAX's persistent compile cache: the directory
``JAX_COMPILATION_CACHE_DIR`` names, else one fixed path in the repo."""
import os

import jax
import pytest

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_setting():
    """Put JAX's cache-dir setting back, so no later test caches."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_default_dir_is_fixed_in_repo(monkeypatch, cache_dir_setting):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    # the same path on every call: the directory is part of the cache key
    assert compile_cache.enable_compile_cache() == got


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, cache_dir_setting):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was  # nothing set in code
