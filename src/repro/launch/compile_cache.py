"""JAX's persistent compilation cache for the command-line entry points.

Called from a launcher's `main()` only — never on import and never from
tests, so a library user or a test run keeps JAX's defaults.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# a fixed directory inside the checkout (gitignored): the cache key holds
# the path, so it must not move between runs
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX has already read it and
    that directory stands; otherwise the cache goes to `REPO_CACHE_DIR`.
    Every compile is cached, however short, so a second run of the same
    shapes skips Mosaic and XLA alike."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
