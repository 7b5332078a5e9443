"""Process set-up shared by the launchers: the persistent compile cache and
the peak device memory.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at a fixed
``<repo>/.jax_cache``: the directory is part of the cache key, so a path
that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)


def peak_bytes_in_use(device=None) -> Optional[int]:
    """Peak memory of ``device`` (default: device 0), where the backend
    reports it (the CPU backend does not)."""
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")
