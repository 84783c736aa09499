"""JAX's persistent compilation cache, kept at a fixed path.

A directory that moves between runs never hits, so the default lives
inside the checkout (``<repo>/.jax_cache``, git-ignored), never under a
temporary, per-process or per-run name.  ``JAX_COMPILATION_CACHE_DIR``,
when set, is JAX's own setting: JAX reads it and this module sets no
other directory.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
