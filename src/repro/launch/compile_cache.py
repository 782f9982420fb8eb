"""JAX's persistent compilation cache, kept where every entry point finds it.

`enable_compile_cache` is the one place the cache directory is chosen:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself, so nothing is set in code), otherwise ``.jax_cache`` at
the root of the checkout.  The path is fixed — never built from a temp
name, a pid or the time — because it is part of what a later process
looks the cache up by.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
