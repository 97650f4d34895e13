"""Placement of JAX's persistent compilation cache for the entry points.

Called at the start of each entry point's ``main`` (never on import):
importing a module must not change process-wide JAX configuration, and the
cache directory has to be set before the first compile of the process.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <repo>/.jax_cache (this file is <repo>/src/repro/launch/compile_cache.py).
# A fixed path: the directory is part of what lets a later run hit.
REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across runs and return where they go.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set in code. Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
