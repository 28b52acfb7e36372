"""Where JAX keeps its persistent compilation cache for this repo.

Entry points (``chip_smoke.py``, ``examples/``, ``python -m repro.bench``)
call :func:`enable` once at start-up; importing any module of ``repro``
never touches the cache.  A ``JAX_COMPILATION_CACHE_DIR`` set in the
environment wins and is left alone — JAX reads it itself.  Otherwise
the cache lives at the fixed path ``<repo>/.jax_cache`` (git-ignored):
the directory is part of every cache key, so it must not move between
runs.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
