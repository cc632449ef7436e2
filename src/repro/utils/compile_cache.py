"""Where JAX keeps its persistent compilation cache.

A cold start on the chip recompiles every program, and a whole train step of
a 48-layer model takes minutes to compile. The cache directory is part of
the cache key, so it must not move between runs: it is either placed from
outside through ``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself) or
fixed at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout root: src/repro/utils/compile_cache.py -> three levels up.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Sets nothing when ``JAX_COMPILATION_CACHE_DIR`` is set; otherwise points
    JAX at ``CHECKOUT_CACHE_DIR``. Call before the first compile.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
