"""Persistent XLA compilation cache for the repo's entry-point scripts.

Called by ``chip_smoke.py`` and ``benchmarks/run.py`` before their first
compile — never at library import, so importing ``repro`` leaves JAX's
configuration alone.
"""
from __future__ import annotations

import os
from pathlib import Path

# a fixed path: the cache directory is part of the cache key, so a
# directory that moves between runs (a temp dir, a pid) never hits
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    keeps the cache there — no other path is set. Otherwise the cache
    lives at ``<repo>/.jax_cache`` (listed in ``.gitignore``). Every
    compile is cached, however quick.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
