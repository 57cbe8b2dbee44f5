"""Persistent compilation cache for the program's entry points.

The command-line entry points (launch/serve.py, launch/train.py,
launch/rollout.py) and chip_smoke.py call enable_compile_cache() before
their first compile, so a second run of the same program on the same
machine loads its executables instead of compiling them again. Library
modules and the tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# a fixed path: the directory is part of what a later run has to find
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and this
    sets nothing. Otherwise the cache is <repo>/.jax_cache."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
