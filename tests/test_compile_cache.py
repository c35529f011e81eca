"""``launch.cache.enable_compile_cache``: where the persistent cache lives."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import cache

CONFIG_NAMES = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_enable_compilation_cache")


@pytest.fixture
def saved_cache_config():
    saved = {n: getattr(jax.config, n) for n in CONFIG_NAMES}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_env_dir_is_used_and_cache_files_land_there(tmp_path, monkeypatch,
                                                     saved_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # JAX reads the variable when it starts; this process started without
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    compilation_cache.reset_cache()
    jax.block_until_ready(jax.jit(lambda x: x * 3.0 + 0.25)(jnp.ones(7)))
    assert os.listdir(tmp_path)


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                  saved_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = cache.enable_compile_cache()
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    assert path == os.path.join(root, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
