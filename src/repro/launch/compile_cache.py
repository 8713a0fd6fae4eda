"""JAX's persistent compilation cache for the entry points.

``enable_compile_cache()`` is called from an entry point's ``main()``
and never at import time: tests that compile for a described chip must
not write to the cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# one fixed directory inside the checkout: the path is part of the
# cache key, so a directory that moves between runs never hits
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already
    and nothing is set here. Otherwise the cache goes to ``CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
