"""Where JAX keeps its persistent compilation cache.

The cache directory is part of each entry's key, so it must not move between
runs: a directory named after a temp dir, a pid or the time never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

# src/repro/launch/compile_cache.py -> the checkout's root
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins (JAX reads it itself);
    otherwise the cache lives at ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
