"""Persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``) call
:func:`use_repo_compile_cache` once at start-up; library modules and
tests never do.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here.  Otherwise, on a
TPU, the cache lives at the fixed ``<repo>/.jax_cache`` (git-ignored):
the path is part of a cache entry's key, so it is never derived from a
temporary name, a pid or the time.  Off the TPU nothing is set: XLA:CPU
entries are cheap to rebuild, and loading them warns about host CPU
features on every hit.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_repo_compile_cache() -> Optional[str]:
    """Point JAX's persistent compilation cache at ``<repo>/.jax_cache``
    on a TPU, unless ``JAX_COMPILATION_CACHE_DIR`` already names one;
    returns the directory in use (None: no persistent cache)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
