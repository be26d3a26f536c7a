"""Where JAX keeps its persistent compilation cache for this repo's entry points.

Entry points call :func:`use_compile_cache` first thing, never at import.
``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
nothing is set here.  Otherwise the cache lives at a fixed ``.jax_cache``
directory in the repository root.  The path is part of what the cache is
keyed on, so it is derived from this file's location only, never from a
temp name, pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
