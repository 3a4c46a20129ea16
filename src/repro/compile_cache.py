"""JAX's persistent compilation cache, at a path that can be placed from outside.

Every entry point that compiles (``chip_smoke.py``, ``repro.launch.serve``,
``repro.eval.run`` on a grid with engine cells) calls
:func:`enable_compile_cache` before its first compile, so a second run of the
same programs reads them back instead of compiling them again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed, never built from a temporary name, a process id or the time: the
# directory is where later runs look, so one that moves never hits.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the
    directory is left alone; otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout.  Programs are cached however quickly they
    compiled: the engine compiles one program per (batch, bucket) shape, and
    most of them compile in under JAX's default one-second threshold."""
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if cache_dir:
        return cache_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
