"""Placement of JAX's persistent compilation cache by the entry points."""
import os

import jax
import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_goes_to_fixed_checkout_path(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == CHECKOUT_CACHE_DIR
    assert CHECKOUT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE_DIR
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_dir_from_environment_is_left_to_jax(monkeypatch,
                                                    cache_dir_restored):
    placed = os.path.join(REPO, "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == placed
    assert jax.config.jax_compilation_cache_dir == before
