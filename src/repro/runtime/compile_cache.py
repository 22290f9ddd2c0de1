"""Where JAX keeps its persistent compilation cache."""

from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout (and listed in .gitignore): the cache directory
# is part of what a later run looks entries up by, so a path that moves from
# run to run (a temp dir, a pid or a time in the name) is never hit again
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here. Otherwise the cache goes to ``.jax_cache/``
    at the repository root. Entry points (``chip_smoke.py``, the benchmark
    scripts' ``main``) call this; importing a module never does.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
