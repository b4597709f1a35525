"""Persistent compilation cache placement for the entry points.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this sets
nothing. Otherwise the cache lives at the fixed `<checkout>/.jax_cache`:
the directory is part of the cache key, so a path built from a temporary
name, a process id or the clock would never hit. Tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
