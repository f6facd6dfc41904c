"""JAX's persistent compilation cache, kept at one fixed place.

Call ``enable_compile_cache()`` from a program's ``main()``: never at import
time and never from the tests, which compile for a described chip that can
write the cache but not read it back. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX already uses it and nothing else is set. Otherwise the cache lives
in ``.jax_cache`` at the root of the checkout: the directory is part of each
entry's key, so a path that moves (a temporary name, a pid, a time) would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile; returns its path."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache kernels and small steps too, not only compiles over a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
