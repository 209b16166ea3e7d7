"""Persistent XLA compile cache for the command-line entry points.

Every entry point (``chip_smoke.py``, ``launch.serve``, ``launch.train``,
``launch.evalsuite``, ``benchmarks.run``) calls
:func:`enable_compile_cache` once at start, before anything compiles, so
a second run reuses the 12-layer encoder rungs and the search kernels
instead of compiling them again.  Library modules never call it, so
importing ``repro`` (the tests) leaves JAX's cache settings untouched.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise the fixed ``<checkout>/.jax_cache``.  The path is part of what
makes a cache hit possible, so it never depends on a temp name, a pid or
the time.
"""

from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``."""
    return os.environ.get(CACHE_ENV) or str(CHECKOUT / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`compile_cache_dir` and cache
    every entry, however small or quick to compile.  Returns the path."""
    import jax

    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
