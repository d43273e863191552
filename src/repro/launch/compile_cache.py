"""JAX's persistent compilation cache, set up the same way by every entry
point (the launcher, ``chip_smoke.py`` and the benchmarks).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there and
nothing is set in code.  Otherwise the cache lives in one fixed,
git-ignored directory of the checkout: the directory is part of what a
cached entry is found by, so it must not move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compilation of the process.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
