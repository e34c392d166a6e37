"""Where JAX keeps its persistent compile cache.

``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and wins;
otherwise the cache sits at the fixed ``<repo>/.jax_cache``.  The directory
is part of the cache key, so it is never built from a temp name, a pid or
the time.  Call :func:`enable_compile_cache` before the first compile.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
