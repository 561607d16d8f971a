"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so a cache that moves never hits:
the path is either placed from outside through
``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself) or fixed inside
the checkout (``<repo>/.jax_cache``, listed in ``.gitignore``).  Entry
points call :func:`enable_compile_cache` at start-up, before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  A
    directory placed through ``JAX_COMPILATION_CACHE_DIR`` is left as it
    is, and nothing else is set."""
    placed = os.environ.get(ENV)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
