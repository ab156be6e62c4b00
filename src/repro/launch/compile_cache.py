"""JAX's persistent compilation cache, at one fixed place.

The cache key includes the cache path, so a directory that moves never
hits.  Entry points call :func:`enable_compile_cache` before their first
compile.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache")


def enable_compile_cache() -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
    and this sets nothing; otherwise the cache goes to ``.jax_cache/`` at
    the checkout root.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
