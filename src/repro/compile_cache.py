"""Where JAX's persistent compilation cache lives.

Processes that compile the same programs (the chip smoke, the benchmark
runner) share compiled executables through JAX's persistent cache. Its
directory is part of each entry's key, so it must not move between
runs: it is either the one ``JAX_COMPILATION_CACHE_DIR`` names, read by
JAX itself, or a fixed ``.jax_cache/`` at the repository root.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's own reading of it stands
    and nothing is changed."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
