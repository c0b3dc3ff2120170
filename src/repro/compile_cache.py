"""JAX's persistent compilation cache, placed where the next run finds it.

Entry points call ``enable()`` before their first compile: chip_smoke.py
and the benchmark drivers (through ``benchmarks/common.make_manager``).
Importing the package never calls it, so the tests compile without a
cache and stay silent.  JAX decides once per process, at its first
compile, whether the process uses the cache, so ``enable()`` and
``disable()`` only take effect before that compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: fixed, derived from this file's own location —
# a cache directory that moves between runs never hits
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it on its own and
    this sets no other directory.  Otherwise the cache lives in
    ``DEFAULT_DIR``.  Every executable is kept, not only those that took
    longer than JAX's one-second default: a cold run compiles everything,
    and the many small kernel compiles add up."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def disable() -> None:
    """Keep this process off the persistent cache, even where
    ``JAX_COMPILATION_CACHE_DIR`` is set: for measurements whose subject
    is the compile itself (the INSERT baseline of fig24)."""
    jax.config.update("jax_enable_compilation_cache", False)


def is_on() -> bool:
    """Whether compiles in this process may be served from disk."""
    return bool(jax.config.jax_enable_compilation_cache
                and jax.config.jax_compilation_cache_dir)
