"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache."""
import os

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_dir", [None, "/some/shared/cache"])
def test_enable_compile_cache_dir(monkeypatch, cache_config, env_dir):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.enable_compile_cache()
    if env_dir is None:
        # the checkout's .jax_cache, next to src/
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    else:
        # JAX reads the variable itself; nothing is set in code
        assert got == env_dir
        assert jax.config.jax_compilation_cache_dir == before
