"""JAX's persistent compilation cache, turned on by the entry points.

``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py`` and the
``benchmarks/`` mains call :func:`enable_compile_cache` first thing in
``main``; importing this module changes nothing.  A TPU machine keeps no
process between runs, so without the cache every run recompiles every
eager op shape of every tenant.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout: the directory is part of the cache key, so a
# path built from a temporary name, a pid or the time would never hit
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is the directory (JAX reads
    the variable itself) and no other is set.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``.  The minimum compile time to be cached
    drops from JAX's default 1 s to 0: the plan executor's eager ops each
    compile in well under a second, and at the default none of them would
    ever be written."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
