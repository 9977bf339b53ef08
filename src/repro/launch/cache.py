"""Where JAX keeps its persistent compilation cache.

A chip run compiles every program it has not seen, which can be most of a
cold run. The cache lets a later process (or a later call on a machine that
keeps the directory) load those programs instead. The directory's path is
part of what a cached entry is found by, so it is fixed: never a temporary
directory, a pid or a time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is not set
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left alone (JAX reads it
    itself); otherwise the cache goes to ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
