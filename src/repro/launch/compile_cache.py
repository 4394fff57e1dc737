"""JAX persistent compilation cache placement, called by entry points only.

Importing ``repro`` sets no cache: scripts call :func:`use_compile_cache`
first thing in their ``main``. The cache directory is part of what makes
an entry hit, so it is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when that is
set (JAX reads the variable itself and nothing is set here), else
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

_REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
