"""Where the entry points keep JAX's persistent compilation cache."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[1])


def test_env_dir_is_left_to_jax(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_default_dir_is_fixed_inside_checkout(cache_config, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    path = compile_cache.use_compile_cache()
    repo = Path(__file__).resolve().parents[1]
    assert path == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.use_compile_cache() == path
