"""Persistent compilation cache placement (``util.compile_cache``)."""
import os

import jax
import pytest

from repro.util import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/some/where"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_dir_in_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(compile_cache.CHECKOUT, ".jax_cache")
    assert os.path.isfile(os.path.join(compile_cache.CHECKOUT,
                                       "pyproject.toml"))
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path    # stable
