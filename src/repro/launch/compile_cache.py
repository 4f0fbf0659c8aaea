"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``) call
:func:`use_compile_cache` once, before the first compile.  Library modules
never do, so importing ``repro`` (tests included) leaves the cache alone.
"""
from __future__ import annotations

import os

import jax

__all__ = ["use_compile_cache"]

# src/repro/launch/compile_cache.py → the checkout's root
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Point the cache at ``$JAX_COMPILATION_CACHE_DIR``, else at the fixed
    ``<repo>/.jax_cache`` (a fixed path: the path is part of the cache key,
    so a directory that moves never hits).  Returns the directory."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(REPO_ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
