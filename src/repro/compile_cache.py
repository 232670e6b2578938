"""Where JAX keeps its persistent compile cache.

Called by the entry points (``chip_smoke.py``, ``examples/``,
``benchmarks/run.py``) before their first compile; no library module
calls it, so importing the package never changes JAX's configuration.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Use ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself, so nothing is set in code), else ``<repo>/.jax_cache``: a
    fixed path, since the directory is part of what a cached program is
    found by.  Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
