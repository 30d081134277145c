"""Where JAX keeps compiled programs between processes.

Entry points (the sweep, service and analysis CLIs, ``chip_smoke.py``)
call ``enable_compile_cache()`` once at start-up; importing this module
does nothing.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads
it itself and nothing here names another directory.  Otherwise the
cache lives at one fixed, gitignored path in the checkout — the path is
part of the cache key, so it never moves between runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
