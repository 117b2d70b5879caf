"""Persistent XLA compilation cache, placed from outside the program."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed path: the cache directory is part of what a compile is keyed on,
# so a location that moved between runs would never hit
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is changed.  Otherwise the cache goes to
    ``.jax_cache/`` at the checkout root.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
