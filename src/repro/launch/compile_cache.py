"""Persistent XLA compilation cache for the entry points.

Every launcher and benchmark calls :func:`enable` before its first
compile; importing ``repro`` never does, so library users and the tests
compile without a cache.  ``JAX_COMPILATION_CACHE_DIR``, where set, is
the cache (JAX reads it itself).  Otherwise the cache lives at the fixed
path ``<repo>/.jax_cache``: the path is part of the cache key, so it
never depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every executable: a serving engine compiles one per kernel
    # shape, many of them in well under JAX's default one-second floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
