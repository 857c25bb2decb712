"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the power
advisor CLI and the ``experiments/scripts``) call
:func:`enable_compile_cache` once at start-up; importing the library never
does.  A cold replay grid compiles tens of programs, and the cache lets a
second process — or a second run on the same disk — read them back.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, and no other
  directory is set in code.
* Unset: the cache lives at one fixed path inside the checkout,
  ``<repo>/.jax_cache`` (listed in ``.gitignore``).  The path is part of
  what makes a later run hit, so it never carries a temporary name, a pid
  or a timestamp.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every program, whatever its compile
    time, and return the directory it lives in."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
