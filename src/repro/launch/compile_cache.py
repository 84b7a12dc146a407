"""JAX's persistent compile cache, turned on by the entry points.

Only the launchers, ``chip_smoke.py`` and ``benchmarks/run.py`` call
:func:`enable_compile_cache`; importing the package turns nothing on, so
tests compile as they always did.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout that holds ``src/repro``
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here.  Otherwise the cache is the fixed
    ``<checkout>/.jax_cache`` (git ignores it): a directory that moved
    between runs would never hit."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
