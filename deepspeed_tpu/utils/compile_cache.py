"""Where the persistent XLA compile cache lives — decided in one place.

``chip_smoke.py``, ``benchmark/run.py`` and the examples call
:func:`enable_compile_cache` before their first compile.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing; otherwise the cache goes to one fixed directory inside the
checkout.  The path is part of the cache key, so it is never a temp dir,
a pid or a timestamp.
"""
from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in
    use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
