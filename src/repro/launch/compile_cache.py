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
    there and its place is not changed.  Otherwise the cache goes to
    ``.jax_cache/`` at the checkout root.  Either way a program's metadata
    (its ``named_scope`` op names) is part of its key: without it, two
    programs that differ only in their scopes share one entry, and the one
    loaded second reports the other's op names to the profiler and in its
    HLO text.  Call before the first compile."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
