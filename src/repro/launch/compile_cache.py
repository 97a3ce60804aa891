"""JAX's persistent compilation cache: the one place that points it somewhere.

Entry points (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``)
call ``enable_compile_cache()`` before they compile anything.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it by itself and nothing is
set here.  Otherwise the cache lives at the fixed ``<repo>/.jax_cache``: the
directory is part of each entry's key, so a path that moved between runs
would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
