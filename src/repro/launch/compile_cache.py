"""Placement of JAX's persistent compilation cache.

Entry points call ``setup()`` at the start of ``main`` (never at import).
The cache key includes the directory, so the path is fixed: the
``JAX_COMPILATION_CACHE_DIR`` environment variable when it is set (JAX
reads it itself), else ``<repo root>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax

#: the repository checkout this package runs from (src/repro/launch/ -> root)
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def setup() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
