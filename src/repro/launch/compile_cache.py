"""Persistent XLA compilation cache at a fixed place.

A cold run of a whole train step recompiles every layer, and the key of
JAX's persistent cache includes the cache directory, so the directory
must not move between runs.  ``$JAX_COMPILATION_CACHE_DIR`` wins when it
is set (JAX reads it itself, and nothing is set here); otherwise the
cache lives in ``<repo>/.jax_cache`` (gitignored).  The launchers and
``chip_smoke.py`` call :func:`enable_compile_cache` before they compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory.

    Returns the directory in use: ``$JAX_COMPILATION_CACHE_DIR`` if set
    (left to JAX, no config is touched), else ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
