"""Shared benchmark utilities."""
from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Callable

import jax
import numpy as np

REPO_ROOT = Path(__file__).resolve().parent.parent


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache lives at the fixed
    path ``<repo>/.jax_cache`` (git-ignored): the path is part of the
    cache key, so it must not move between runs.  Call it before the first
    compilation.  The test suite never calls it.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def time_us(fn: Callable, *args, reps: int = 5, warmup: int = 2) -> float:
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6


def emit(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}")
