"""The launchers' compilation-cache directory: the environment decides,
else a fixed directory inside the checkout."""
import os

import jax
import pytest

from repro.launch import cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_decides_and_code_sets_nothing(monkeypatch, restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(cache.ENV, "/elsewhere/jax-cache")
    assert cache.enable_compile_cache() == "/elsewhere/jax-cache"
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_fixed_inside_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cache.ENV, raising=False)
    first = cache.enable_compile_cache()
    assert first == cache.enable_compile_cache() == \
        jax.config.jax_compilation_cache_dir
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == os.path.join(root, ".jax_cache")
