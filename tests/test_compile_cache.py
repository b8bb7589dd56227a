"""The entry points' compile-cache rule: follow ``JAX_COMPILATION_CACHE_DIR``
when it is set, else one fixed directory inside the checkout."""

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_env_dir_wins_and_nothing_is_set(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_in_checkout_dir_without_env(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.CACHE_DIR.parent / "src" / "repro").is_dir()
    # the same path on every call: nothing run-specific in it
    assert compile_cache.enable_compile_cache() == got
