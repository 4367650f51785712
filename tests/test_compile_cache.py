"""Where the chip entry points keep JAX's persistent compilation cache."""

import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    """Restore the process-wide cache directory after each case."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_defaults_to_fixed_dir_in_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.enable_compile_cache() == os.path.join(
        root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
    # the same directory on every call: a later process must find it
    assert compile_cache.enable_compile_cache() == compile_cache.CACHE_DIR


def test_cache_env_dir_wins_and_nothing_else_is_set(monkeypatch,
                                                     cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
