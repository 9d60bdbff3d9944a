"""JAX's persistent compilation cache for the launchers, benchmarks and the
chip smoke check.

``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and this
helper sets nothing.  Otherwise the cache goes to ``.jax_cache/`` at the root
of the checkout -- a fixed path, because the directory is part of the
cache's key and one that moved would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
