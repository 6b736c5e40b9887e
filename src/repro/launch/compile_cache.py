"""JAX's persistent compilation cache for the entry points that run on the
chip (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``).

Call :func:`enable_compile_cache` from an entry point's ``main``; importing
this module changes nothing.
"""
from __future__ import annotations

import os

import jax

# <repo>/.jax_cache: a fixed path, because the path is part of the cache key
# (a directory that moves between runs never hits).  Listed in .gitignore.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
