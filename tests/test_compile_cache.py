"""The persistent compilation cache goes where the environment says,
or to a fixed directory in the checkout — never anywhere else."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_wins_and_code_sets_none(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir is None


def test_default_dir_is_fixed_in_the_checkout(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = Path(__file__).resolve().parents[1]
    got = compile_cache.use_compile_cache()
    assert got == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.use_compile_cache() == got      # stable
