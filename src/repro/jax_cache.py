"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, before compiling; the
library never sets the cache on import.  ``JAX_COMPILATION_CACHE_DIR``, when
set, wins (JAX reads it itself, so nothing is set here); otherwise the cache
lives at one fixed, gitignored directory of the checkout.  The path is part
of the cache key, so it never depends on a temp name, a pid or a time.
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
