"""JAX's persistent compilation cache, placed where a deployment can find it.

Entry points call `enable_compile_cache()` before their first compile;
importing this module (or any library module) turns nothing on.

  * `JAX_COMPILATION_CACHE_DIR` set: JAX reads that directory from the
    environment itself, and this module sets no other.
  * unset: the cache goes to `<checkout>/.jax_cache`. The path is fixed —
    never a temp, pid- or time-derived one — because the directory is
    part of every entry's key: a cache that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
