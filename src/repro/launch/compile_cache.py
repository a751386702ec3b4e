"""Where JAX keeps its persistent compilation cache for this repository.

Scripts that drive the chip call :func:`enable_compile_cache` once, before
their first compile; the library never does it at import. The directory is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise the fixed
``<repo>/.jax_cache`` (listed in ``.gitignore``): the path is part of the
cache key, so it holds no temp directory, pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory used."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
