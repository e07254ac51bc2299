"""JAX's persistent compilation cache, switched on by entry points.

Library modules never touch the cache; a script that drives the chip
calls :func:`enable_compile_cache` first.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads that directory
and nothing is set here.  Otherwise the cache lives at a fixed path in
the checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``): the path
is part of the cache key, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

#: The checkout-local default: src/repro/launch/ -> the repository root.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    REPO_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
