"""JAX's persistent compilation cache for the entry points that run on a
chip (`chip_smoke.py`, `benchmarks.run`, `repro.launch.serve`).

`enable()` is called by those entry points, never at import, and tests
do not call it. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here. Otherwise the cache sits at ONE fixed
directory of the checkout (listed in `.gitignore`): the directory is
part of every entry's key, so a temp-, pid- or time-derived path would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
