"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks.run``) call
:func:`enable_compile_cache` once, before their first compile; importing
this module changes nothing.

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this helper
    sets no directory.
  * unset: the cache lives in ``.jax_cache/`` at the repository root (listed
    in ``.gitignore``).  The path is fixed on purpose: the cache directory
    is part of what a later process must match to find an entry, so a
    temp, pid or time path would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
