"""JAX's persistent compilation cache, placed at a fixed path.

A cold serving run compiles one ``l2_topk`` kernel per (node size, query
bucket, k) it touches — tens of kernels for a million-vector store.  Entry
points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`use_compile_cache` before their first compile so that a later
process on the same machine reads those executables back instead of
compiling again.  Library modules never call it: importing ``repro`` sets
nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

#: In-checkout default (``<repo>/.jax_cache``, listed in ``.gitignore``).
#: A later run finds the cache only at the same path, so the path is fixed.
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here; otherwise the cache lives at
    :data:`DEFAULT_DIR`.  Every compile is kept, however short: most
    kernel compiles take under a second, below JAX's default threshold."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
