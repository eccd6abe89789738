"""JAX's persistent compilation cache, kept at one fixed place.

A cold process recompiles every program it runs; on a TPU a train step
at published widths takes tens of seconds to compile.  The launchers
and ``chip_smoke.py`` call :func:`enable_compile_cache` before their
first compile, so later processes on the same checkout load the
compiled executables instead.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in ``.gitignore``): a fixed path, because a directory that
moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
