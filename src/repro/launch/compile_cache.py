"""JAX's persistent compilation cache, placed for the entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, the cache goes there and this
module names no other directory. Otherwise it goes to one fixed directory
inside the checkout, ``.jax_compile_cache/`` (git-ignored). The path never
depends on a temporary name, a pid or the time: a cache whose directory
moves is never found again.

Call ``enable_compile_cache()`` before the process compiles anything.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = (pathlib.Path(__file__).resolve().parents[3]
                      / ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
