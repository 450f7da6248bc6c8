"""JAX persistent compilation cache at one fixed place.

A compile on the TPU takes seconds to minutes, and a cache entry is only
found again under the same directory.  ``JAX_COMPILATION_CACHE_DIR``,
when set, wins: JAX reads it itself and nothing here overrides it.
Otherwise the cache lives at ``<checkout>/.jax_cache`` (git-ignored),
so every run from the same checkout shares it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory
    and return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
