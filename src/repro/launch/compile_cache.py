"""JAX persistent compilation cache, placeable from outside.

``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing else is
set here.  Unset: the cache lives at a fixed ``<checkout>/.jax_cache``
(git-ignored).  The path is part of a cached program's key, so it never
carries a temp dir, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
