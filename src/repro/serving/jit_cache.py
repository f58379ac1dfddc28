"""Persistent XLA compilation cache, set up the same way in every process.

The single-process runtime, every cluster worker and ``chip_smoke.py``
compile the same jit programs (prefill chunks, decode step, chunk
re-page). ``enable_jit_cache`` points this process's JAX at one on-disk
cache, so a program compiled once is loaded by the other processes and
by later runs:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads that directory itself,
    and no other is set here;
  * unset: ``<checkout>/.jax_cache`` — one fixed path (gitignored), so
    runs from the same checkout find each other's programs.

Call it before the process's first jit execution: JAX decides whether
the cache is on when it compiles its first program.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_jit_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program, however small or quick to compile: many small
    # programs per process is exactly the profile that multiplies across
    # worker processes
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
