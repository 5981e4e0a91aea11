"""JAX's persistent compilation cache, set up in one place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this module sets no other directory.  Where it is not, the cache goes
to :data:`CACHE_DIR`, a fixed directory inside the checkout (listed in
``.gitignore``): the path is part of the cache key, so it never
depends on a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The directory the cache uses: ``$JAX_COMPILATION_CACHE_DIR`` or
    :data:`CACHE_DIR`."""
    return os.environ.get(ENV) or str(CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_enable_compilation_cache", True)
    return cache_dir()
