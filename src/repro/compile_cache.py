"""JAX's persistent compilation cache for the repo's entry points.

A cold process spends much of its first minute compiling.  The cache
lets the next process on the same machine load those executables
instead, so the directory is fixed: a temp, pid- or time-derived path
would start empty in every process.
"""
from __future__ import annotations

import os

#: ``<checkout>/.jax_cache`` (listed in .gitignore)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile
    and return its directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
    JAX already reads it and nothing is set here; otherwise the cache
    lives in the checkout's ``.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
