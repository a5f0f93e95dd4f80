"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move
between runs. ``use_compile_cache`` is called at the start of each
entry point (``train.main``, ``serve.main``, ``chip_smoke.py``), never
at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache (listed in .gitignore)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the cache directory in use. ``JAX_COMPILATION_CACHE_DIR``,
    when set, is left as JAX read it; otherwise the cache goes to the
    fixed ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
