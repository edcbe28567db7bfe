"""Shared benchmark helpers: timing + CSV emission."""

from __future__ import annotations

import json
import os
import time

OUT_DIR = os.path.join(os.path.dirname(__file__), "out")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honored as JAX reads it and
    no other directory is set here. Otherwise the cache lives at the fixed
    ``<repo>/.jax_cache`` (gitignored): the path is part of the cache key, so
    it must never come from a temporary name, a process id or the clock.
    Returns the directory in use.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def timed(fn, *args, repeat=3, **kwargs):
    """Returns (result, microseconds_per_call) — result from the last call."""
    fn(*args, **kwargs)  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        res = fn(*args, **kwargs)
    us = (time.perf_counter() - t0) / repeat * 1e6
    return res, us


def emit(rows: list[dict], name: str):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{name}.json"), "w") as f:
        json.dump(rows, f, indent=1)


def csv_line(name: str, us: float, derived: str) -> str:
    return f"{name},{us:.1f},{derived}"
