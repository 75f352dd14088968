"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` at the top of ``main()``,
never at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here. Otherwise the cache goes to the fixed
directory ``<checkout>/.jax_cache`` (listed in ``.gitignore``): the path
is part of the cache key, so a temporary or per-process directory would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
