"""JAX's persistent compilation cache for the entry points.

Only entry points call :func:`enable_compile_cache`; importing a library
module never configures the cache.
"""
from __future__ import annotations

import os

#: the checkout's own cache directory (listed in .gitignore).  The path is
#: part of the cache key, so it is fixed: a per-process or temporary path
#: would never hit.
CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache"))


def enable_compile_cache() -> str:
    """Keep compiled programs across processes and return the directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is changed here.  Otherwise the cache goes to ``.jax_cache/``
    at the root of the checkout."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
