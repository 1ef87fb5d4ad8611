"""JAX's persistent compilation cache, at a directory that stays put.

Entry points (``chip_smoke.py``, the benchmarks) call
:func:`enable_compile_cache` before their first compile; importing the
library or running the tests never turns the cache on.  The directory is
part of every cache key, so it is either the one ``JAX_COMPILATION_CACHE_DIR``
names or a fixed path inside the checkout, never a temporary one.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn on the persistent compilation cache and return its directory
    (None when JAX is not installed: the numpy engines compile nothing).

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and this
    sets no other directory; otherwise the cache lives in ``.jax_cache`` at
    the root of the checkout.  Every executable is cached, however quick
    its compile: the simulator's scan kernels compile in a few seconds
    each, near JAX's default one-second floor.
    """
    try:
        import jax
    except ImportError:
        return None

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
