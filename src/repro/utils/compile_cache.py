"""Where JAX keeps its persistent compilation cache.

``use_compile_cache()`` is the first call of every entry point that compiles
for a chip (chip_smoke.py, launch/fl_train.py, benchmarks/bench_round.py).
If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache goes to ``<repo>/.jax_cache``: a fixed path,
because the path is part of the cache key — a temp name, pid or time would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory used when JAX_COMPILATION_CACHE_DIR is unset
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Place the persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
