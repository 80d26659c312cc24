"""Where JAX's persistent compilation cache lives.

The entry points (``repro.launch.train``, ``repro.launch.serve``,
``chip_smoke.py``) call :func:`enable_compile_cache` before their first
compile, so a later run of the same programs can load them instead of
compiling again.  The directory is fixed, so every run looks in the same
place: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads the variable
itself, and nothing here overrides it), otherwise ``.jax_cache/`` at the
repository root.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
