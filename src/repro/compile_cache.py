"""JAX's persistent compilation cache, switched on by entry points.

Rule: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
that directory is the cache; nothing else is set here.  Otherwise the
cache is the fixed ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
The directory is part of each cache entry's identity, so it is never a
temporary, per-process or time-stamped path.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` at start-up; importing the library never does.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
