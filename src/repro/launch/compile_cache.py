"""Where JAX keeps its persistent compilation cache.

Entry points call `enable()` first thing in `main`; importing this module
changes nothing.  The cache directory is part of every entry's key, so it
is fixed: `$JAX_COMPILATION_CACHE_DIR` when that is set (JAX reads the
variable itself), otherwise `<repo>/.jax_cache`.
"""

from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
