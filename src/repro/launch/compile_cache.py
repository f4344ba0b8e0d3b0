"""JAX's persistent compilation cache for the launchers and ``chip_smoke.py``.

A cold published-width model spends most of a short run compiling; the cache
lets the next process on the same machine skip that. The directory is part
of what makes an entry findable again, so it never moves: ``<repo>/.jax_cache``
(git-ignored), or whatever ``JAX_COMPILATION_CACHE_DIR`` names — JAX reads
that variable itself, and then nothing is set here. Tests never call this.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
