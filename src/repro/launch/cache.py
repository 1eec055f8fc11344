"""Where compiled programs are kept between processes: the one place the
repo configures JAX's persistent compilation cache.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it as its cache directory
and `enable_compile_cache` leaves it alone.  Otherwise the cache goes to the
fixed ``<repo>/.jax_cache`` (listed in ``.gitignore``).  The directory never
depends on a temp name, a process id or the time: the path is part of what
the cache is looked up by, so a moving directory would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
