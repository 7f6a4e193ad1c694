"""JAX's persistent compilation cache, placed at a fixed directory.

Call ``use_compile_cache()`` at the start of an entry point, after parsing
its arguments and before the first compile; never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/src/repro/launch/cache.py -> <checkout>/.jax_cache.  The path
# is part of the cache key, so it must not move between runs.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the cache directory in use, setting it if nobody has.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is changed here.  Otherwise the cache goes to ``DEFAULT_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
