"""Persistent XLA compilation cache for the entry points.

A run on a fresh machine compiles everything; with the cache, a second
process (or a second run on the same checkout) loads the compiled programs
instead.  The directory is part of every entry's key, so it never moves.

The key also holds each program's metadata, its operations' names with
the models' ``jax.named_scope`` layers.  JAX leaves that out by default,
and then loads a program compiled from code that differs only there, so
that a profiler trace names the other code's operations.  The names carry
no Python frames (``jax_traceback_in_locations_limit`` 0): with them the
key would hold files and lines, and a checkout at another path, or an
edit that moves a line of any caller, would compile everything again.
That holds for the whole process, so its HLO metadata, its profiler op
details and XLA's error locations name no source file or line.
``jax_hlo_source_file_canonicalization_regex`` would keep the lines and
drop only the checkout's path, but an edit that moves a line would then
still compile every program traced through it again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at a fixed directory; returns it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    the directory stays.  Otherwise the cache is ``<repo>/.jax_cache``.
    Either way the key holds the programs' op names, and no source
    locations.  Call at the start of an entry point, before the first
    compile.
    """
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
