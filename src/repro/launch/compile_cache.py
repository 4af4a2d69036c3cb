"""Where JAX keeps its persistent compilation cache.

The entry points call :func:`enable_compile_cache` once, before their
first compile, so a second process on the same machine loads compiled
programs instead of recompiling them.  Importing this module changes
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

# Fixed, inside the checkout: the cache directory is part of each entry's
# key, so it must not move between runs (and is git-ignored).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    wins; otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
