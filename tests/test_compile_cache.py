"""The persistent compilation cache helper: placed from outside through
``JAX_COMPILATION_CACHE_DIR``, else at one fixed path in the checkout."""
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    """Put JAX's cache settings back as they were, so no other test of
    this process writes a persistent cache."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_cache_dir_from_environment_is_the_only_one_written(
        tmp_path, monkeypatch, restore_cache_config):
    def checkout_entries():
        cache = compile_cache.CHECKOUT_CACHE
        return set(cache.iterdir()) if cache.exists() else set()

    target = tmp_path / "outside"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(target))
    before = checkout_entries()
    assert compile_cache.enable_compile_cache() == target
    assert jax.config.jax_compilation_cache_dir == str(target)
    # cache even a tiny program, so the write can be seen
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
    assert any(target.iterdir())
    assert checkout_entries() == before


def test_cache_dir_without_environment_is_fixed_in_checkout(
        monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    assert first == second == REPO / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == str(first)
