"""JAX's persistent compilation cache, placed from outside the program.

Every entry point that compiles at scale (``chip_smoke.py``, the train
and serve launchers, the benchmarks) calls :func:`enable_compile_cache`
once, before its first compile.  The cache directory is

* ``JAX_COMPILATION_CACHE_DIR`` when that is set — JAX reads the
  variable itself, and this module sets no other directory;
* otherwise ``<checkout>/.jax_cache``, a fixed path (the path is part of
  the cache key, so a directory that moves between runs never hits).

``PSP_NO_COMPILE_CACHE=1`` turns the cache off, e.g. when measuring
cold-compile cost itself.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

from repro.core import env

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: the fixed default: ``.jax_cache`` at the root of this checkout
CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> Optional[str]:
    """Switch the persistent compilation cache on; returns its directory
    (None when ``PSP_NO_COMPILE_CACHE`` opts out)."""
    if env.flag("PSP_NO_COMPILE_CACHE"):
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # small sweep chunks compile in well under the default 1 s threshold:
    # cache every executable, whatever its size or compile time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir
