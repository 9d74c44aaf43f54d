"""Where JAX keeps compiled programs between runs of this repository.

A cold start on a TPU compiles one window-step program per (slot bucket,
event rung) pair, each taking seconds at the paper's width, so entry
points that drive the chip keep JAX's persistent compilation cache.  The
cache can be placed from outside: when ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and nothing is set here.  Otherwise the cache
lives at the fixed path ``<repo root>/.jax_cache`` — fixed because the
path is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call before the first compile.  Leaves an externally set
    ``JAX_COMPILATION_CACHE_DIR`` alone; otherwise points JAX at
    :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
