"""Where entry points keep JAX's persistent compilation cache.

Entry points (``chip_smoke.py``, ``examples/*.py``, the ``launch/*.py``
mains, ``benchmarks/run.py``) call :func:`use_compile_cache` once before
their first compile; importing ``repro`` never does. When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing. Otherwise the cache lives at ``<checkout>/.jax_cache``: a fixed
path, because the directory is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
