"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points that run on the chip (``chip_smoke.py``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once, before compiling anything, so their
processes share compiled programs.  The test suite does not call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout root: src/repro/compile_cache.py -> <checkout>.
CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left to it.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a
    fixed path, because a cache whose directory moves from run to run is
    never hit."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
