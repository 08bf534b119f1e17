"""JAX's persistent compilation cache at a fixed path.

The cache key includes the directory, so a path that moves never hits.
Entry points call ``enable_compile_cache`` before compiling anything;
importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and nothing else is set.  Otherwise the cache goes to
    ``<checkout>/.cache/jax``, which ``.gitignore`` lists."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".cache" / "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
