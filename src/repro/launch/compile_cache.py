"""JAX's persistent compilation cache, set once per entry point.

Each command-line entry point (`chip_smoke.py`, `python -m repro.serve_mis`)
calls `enable_compile_cache()` before its first compile, so a second run
of the same program on the same machine reads its compiled programs back
instead of compiling again.  Library code and tests never call it.

Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
helper sets nothing.  Otherwise the cache lives at the fixed `.jax_cache/`
of the checkout (gitignored): the path is part of the cache's key, so it
is never temporary, per-process or time-stamped.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns the directory used."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
