"""JAX's persistent compilation cache, placed from outside or at a fixed
path. Entry points call ``enable_compile_cache()`` from ``main()``; no
module turns it on at import."""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here. Otherwise the cache is ``<checkout>/.jax_cache``
    — a fixed path, since the path is part of what a cached entry is found
    by, so a temporary or per-process directory would never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
