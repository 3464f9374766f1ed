"""Where JAX keeps its persistent compilation cache.

The cache directory is part of the cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else the
fixed ``<checkout>/.jax_cache`` (git-ignored).  Entry points call
``use_compile_cache()`` first thing in ``main``; importing this module
changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
