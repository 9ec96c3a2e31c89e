"""`repro.compile_cache`: the persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to one fixed in-checkout path."""
import pathlib

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_default_cache_dir_is_fixed_in_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    compile_cache.use_compile_cache()
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
    assert compile_cache.CACHE_DIR == repo / ".jax_cache"


def test_env_cache_dir_wins(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    jax.config.update("jax_compilation_cache_dir", "/elsewhere")
    compile_cache.use_compile_cache()
    assert jax.config.jax_compilation_cache_dir == "/elsewhere"
