"""Persistent XLA compile cache for the entry-point scripts.

``chip_smoke.py`` and the benchmark CLIs call :func:`enable_compile_cache`
before their first compile; library imports and tests never do.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
changed here.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, since the path is part of what a later run must find again
(never a temp name, a pid or a time).  The directory is git-ignored.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
