"""JAX's persistent compilation cache, at one fixed place per checkout.

Call :func:`use_compile_cache` before the first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and this
sets nothing; otherwise the cache lives in ``<checkout>/.jax_cache``.  The
path is part of the cache's key, so it is never built from a temporary
name, a pid or the time: a directory that moved would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
