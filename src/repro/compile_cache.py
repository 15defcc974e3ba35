"""JAX's persistent compilation cache for this checkout's entry points."""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, already configures the
    cache, and nothing is changed.  Otherwise the cache lives in
    ``<checkout>/.jax_cache``, a path derived from this package's
    location: the path is part of the cache key, so every process of
    the checkout finds the entries the others wrote.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
