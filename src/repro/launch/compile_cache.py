"""JAX's persistent compilation cache, placed from outside.

The persistent cache lets a later process of the same checkout skip
the compiles of an earlier one.  Entries are found only in the
directory they were written to, so the path must not move between
runs: it is never derived from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["ENV_CACHE_DIR", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache: src/repro/launch/compile_cache.py -> parents[3]
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here.  Otherwise the cache lives at the
    one fixed path `DEFAULT_CACHE_DIR` inside the checkout.
    """
    import jax
    path = os.environ.get(ENV_CACHE_DIR)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
