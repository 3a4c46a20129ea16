"""Where the persistent compilation cache goes."""

from __future__ import annotations

from pathlib import Path

import jax
import pytest

from repro.compile_cache import DEFAULT_CACHE_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_default_dir_is_fixed_at_checkout_root():
    root = Path(__file__).resolve().parents[1]
    assert DEFAULT_CACHE_DIR == root / ".jax_cache"


def test_unset_env_places_cache_at_checkout(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", raising=False)
    assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_env_min_compile_time_is_respected(monkeypatch, restore_cache_config):
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2.5")
    before = jax.config.jax_persistent_cache_min_compile_time_secs
    enable_compile_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == before
