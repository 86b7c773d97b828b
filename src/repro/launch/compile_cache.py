"""Where JAX keeps its persistent compilation cache.

The cache directory is part of every entry's key, so it must not move
between runs: a path built from a temporary name, a pid or the time never
hits.  ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
itself, so nothing is set here); otherwise the cache lives in ``.jax_cache``
at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
