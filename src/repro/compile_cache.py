"""JAX's persistent compilation cache, kept at one fixed place."""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# a fixed path inside the checkout (listed in .gitignore): the directory
# is part of what a cached entry is found under, so it must not move
CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on the persistent compilation cache; call before compiling.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and this sets nothing.  Otherwise the cache goes to
    :data:`CACHE_DIR`, ``.jax_cache/`` at the root of the checkout.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
