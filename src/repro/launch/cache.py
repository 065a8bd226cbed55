"""JAX's persistent compilation cache, turned on by entry points.

Called by the scripts a user runs (`chip_smoke.py`, `benchmarks/run.py`,
the examples), never when the library is imported. When
`JAX_COMPILATION_CACHE_DIR` is set, JAX already reads the cache from
there and nothing is set here. Otherwise the cache is one fixed directory
inside the checkout, `.jax_cache/`: the directory is part of the cache
key, so a path that moved from run to run would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
