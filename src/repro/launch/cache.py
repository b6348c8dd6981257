"""JAX's persistent compilation cache, at a place every entry point finds
again.

The cache key includes the cache directory, so a directory that moves
between runs never hits. Entry points call ``enable_compile_cache()``
first thing in ``main()``; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed here. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
