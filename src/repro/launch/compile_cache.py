"""JAX's persistent compilation cache for the repo's entry points.

A relaunched TonY attempt builds a fresh ``jax.jit`` of the same step; with
the cache on, its compile is a disk read instead of a full compile. Scripts
call ``enable_compile_cache()`` once at start-up, before their first compile;
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads it
    itself. Otherwise the cache goes to ``<repo root>/.jax_cache``: a fixed
    path, because the path is part of what a later run must find again."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
