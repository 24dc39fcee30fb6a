"""JAX's persistent compilation cache for this repository's entry points.

Called from ``main`` of an entry point (``chip_smoke.py``,
``repro.launch.serve``), never at import, so tests and library users
keep JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: a fixed path, so every run from this checkout
# finds the kernels compiled by the one before (the path is part of the
# cache key)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX
    reads it and no other directory is set; otherwise the cache lives
    at ``<checkout>/.jax_cache``. Every compile is cached, including
    kernels that compile in about a second."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
