"""Where JAX keeps its persistent compilation cache for this checkout."""
from __future__ import annotations

import os
import pathlib

import jax

# the checkout root: src/repro/compile_cache.py → parents[2]
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads the
    variable itself) and nothing else is configured. Otherwise the cache
    lives in ``.jax_cache/`` at the checkout root — a fixed path, since
    the path is part of the cache key. Entry points call this before
    their first compile; a process that has already compiled keeps the
    cache setting it started with.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
