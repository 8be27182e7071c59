"""Persistent XLA compilation cache for the entry points.

A run on a fresh machine compiles everything; with the cache, a second
process (or a second run on the same checkout) loads the compiled programs
instead.  The directory is part of every entry's key, so it never moves.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at a fixed directory; returns it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing changes.  Otherwise the cache is ``<repo>/.jax_cache``.  Call
    at the start of an entry point, before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
