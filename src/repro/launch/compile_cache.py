"""JAX's persistent compilation cache, kept at one fixed place.

The cache path is part of what a cached entry is found by, so it never
comes from a temp name, a pid or the time. Entry points call
`enable_compile_cache()` before their first compile; library code and
tests never do."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# `.jax_cache/` at the checkout root (listed in .gitignore).
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and no
    other directory is set here; otherwise the cache goes to
    `.jax_cache/` at the checkout root."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
