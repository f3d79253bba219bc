"""Where JAX keeps compiled programs between processes.

A cold start of a full-width model compiles every program it runs, and the
persistent compilation cache lets the next process skip that. The cache
directory is part of the entry's key, so it must not move between runs:
either the deployment names it in ``JAX_COMPILATION_CACHE_DIR`` (JAX reads
that variable itself, and nothing is set here) or it is the fixed
``.jax_cache`` directory at the root of this checkout (git ignores it).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
