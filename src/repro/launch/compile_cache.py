"""JAX's persistent compilation cache for the launch scripts and ``chip_smoke.py``.

A mining session builds many small executables (one per level shape and
degree bucket), and a cold process recompiles every one of them. With the
persistent cache a second process on the same chip loads them instead.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and that directory
  stands; nothing here names another.
* otherwise: ``<checkout>/.jax_cache`` — a fixed path (git-ignored), so that
  every run from the same checkout finds what the last one wrote.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Every executable is cached however quickly it compiled (JAX's default
    skips those under a second, which is most of the engine's per-bucket
    executables)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
