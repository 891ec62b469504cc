"""JAX's persistent compilation cache for the entry points.

A cold start compiles every step program; with the cache on, a second run
of the same programs loads them from disk instead.
"""

from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py → the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``, so that every run of this checkout finds
    what an earlier one wrote.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
