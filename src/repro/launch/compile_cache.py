"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` once, before their first jit;
importing this module changes nothing. ``JAX_COMPILATION_CACHE_DIR``, when
set, wins (JAX reads it by itself). Otherwise the cache is ``.jax_cache/``
at the root of this checkout: a fixed path, since the path is part of the
cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
