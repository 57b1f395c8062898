"""JAX's persistent compilation cache, placed from outside.

Entry points call ``use_compile_cache()`` under their ``__main__`` check, so
a second run of the same programs loads them instead of compiling again.
Library code and tests never call it.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, and
  this sets no other directory.
* Otherwise: a fixed directory inside the checkout, ``<repo>/.jax_cache``
  (listed in ``.gitignore``).  The path is part of the cache key, so it is
  never a temporary name, a process id or a time.

Every program is cached, however short its compile: a serving run compiles
many programs of under a second each (weight init, prefill buckets, pool
updates), which JAX's default 1 s threshold would leave out.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
