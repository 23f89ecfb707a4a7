"""JAX's persistent compilation cache, kept in one fixed place.

The entry points (``repro.launch.solve``, ``repro.launch.serve`` and the
repository's ``chip_smoke.py``) call :func:`enable_compile_cache` before
they compile anything, so a second run of the same program on the same
machine reads its executables back instead of compiling them again.
"""
from __future__ import annotations

import os
import pathlib

import jax

# The checkout root (this file is <root>/src/repro/launch/compile_cache.py).
# The path is part of the cache key, so it must not move between runs.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the
    cache there and nothing is set here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
