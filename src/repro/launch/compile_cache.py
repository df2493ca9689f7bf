"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``chip_smoke.py``, ``launch/train.py``, ``benchmarks/``)
call :func:`enable_compile_cache` once before they compile; importing
this module does nothing, so tests never pick a cache up.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else
  is set here.
* unset: the cache lives at ``<checkout>/.jax_cache`` (gitignored). The
  path is part of the cache key, so it is fixed — never a temporary
  directory, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
