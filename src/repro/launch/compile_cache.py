"""JAX's persistent compilation cache, placed from outside or at a fixed
path in the checkout.

The cache key includes the directory, so the path must not move between
runs: it is never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

__all__ = ["use_compile_cache"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other directory; otherwise the cache is
    ``<checkout>/.jax_cache``.  Entry points call this before their first
    compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
