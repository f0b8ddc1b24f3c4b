"""Persistent XLA compilation cache for launchers.

Call :func:`enable_compile_cache` from a launcher's ``main`` (never at
import time). Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
uses that directory and nothing else is set here. Otherwise the cache
lives at a fixed ``<checkout>/.jax_cache``: the path is part of the
cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
