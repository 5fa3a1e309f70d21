"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, before they compile.
``JAX_COMPILATION_CACHE_DIR``, where it is set, names the directory and
nothing else is chosen.  Otherwise the cache lives at one fixed path inside
the checkout (``.jax_cache``, listed in ``.gitignore``): a directory that
moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; return it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
