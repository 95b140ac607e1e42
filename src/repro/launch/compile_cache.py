"""JAX's persistent compilation cache for the entry points.

Every entry point calls :func:`enable_compile_cache` before its first
compile, so a second process on the same machine loads compiled executables
instead of recompiling them.  ``JAX_COMPILATION_CACHE_DIR``, where set, is
left to JAX; otherwise the cache lives at the fixed ``<checkout>/.jax_cache``
(the directory is part of the cache key, so it must not move between runs).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


__all__ = ["enable_compile_cache", "DEFAULT_DIR"]
