"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``train.py``/``serve.py`` ``main``, ``chip_smoke.py``) call
:func:`enable_compile_cache` once; importing a module never does. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is set. Otherwise the cache lives at a fixed path inside the checkout
(``<repo>/.jax_cache``, gitignored): the path is part of the cache key, so
a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
