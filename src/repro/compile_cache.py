"""JAX's persistent compilation cache for the program's entry points.

The entry points (``launch/live_train.py``, ``chip_smoke.py`` and the
worker and coordinator processes of ``runtime/net.py``) call
``enable_compile_cache`` before their first compile; importing the
package turns nothing on, so tests keep JAX's own default.

The directory is part of the cache key, so it is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names, which JAX reads by itself, or
``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing else is set here."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
