"""Persistent compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples) call
:func:`enable_compile_cache` once before their first compile; importing
``repro`` never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this sets no other directory.  Otherwise the cache lives at the
fixed ``<checkout>/.jax_cache``: the path is part of what JAX matches an
entry against, so it never depends on a temp directory, a PID or the time.

An entry's key includes the program's op metadata.  JAX leaves it out by
default, and a cached executable then keeps the metadata of whichever
program compiled it first: the ``meliso.*`` stage names a profiler trace
shows would be an older program's, or none.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: ``<checkout>/.jax_cache`` (this file is ``src/repro/launch/...``).
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
