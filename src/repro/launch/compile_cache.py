"""JAX's persistent compilation cache, in one fixed place.

Call :func:`enable_compile_cache` before the first compilation.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this sets nothing.
Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored): the
directory is part of each entry's key, so a path derived from a temporary
name, a pid or the time would never hit again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
