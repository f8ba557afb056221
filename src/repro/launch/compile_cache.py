"""JAX's persistent compilation cache for the entry points that run on a
chip: a DiT-XL/2 serve step takes tens of seconds to compile, and a later
process in the same checkout reads it back instead.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory
and nothing here overrides it.  Otherwise the cache lives at a fixed
``.jax_cache/`` in the checkout (git ignores it).  The path is never built
from a temp name, a pid or the time, so a later process in the same
checkout finds what an earlier one wrote.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
