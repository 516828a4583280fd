"""``repro.compile_cache``: the entry points' persistent compile cache
defers to ``JAX_COMPILATION_CACHE_DIR`` and otherwise uses one fixed
directory inside the checkout."""

from pathlib import Path

import jax
import pytest

from repro.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = Path(__file__).resolve().parents[1]
    assert DEFAULT_CACHE_DIR == repo / ".jax_cache"
    assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
    assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
