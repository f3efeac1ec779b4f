"""JAX's persistent compilation cache, kept at one fixed place.

Entry points call ``setup_compile_cache()`` once, before their first
compile; importing the library never turns the cache on.  The cache key
includes its directory, so the directory is fixed per checkout and never
made from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["setup_compile_cache", "DEFAULT_CACHE_DIR"]

# <repo>/.jax_cache: this file is <repo>/src/repro/launch/compile_cache.py.
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def setup_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    that directory stands.  Otherwise the cache goes to ``DEFAULT_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
