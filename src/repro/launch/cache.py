"""Where JAX keeps its persistent compilation cache.

A launcher calls :func:`enable_compile_cache` once, at start-up; importing
the library never changes the cache.  ``JAX_COMPILATION_CACHE_DIR``, when
set, decides the directory (JAX reads it itself).  Otherwise the cache goes
to a fixed directory inside the checkout, so a later run from the same
checkout finds what an earlier one compiled: the directory is part of the
cache key, so it never comes from a temporary name, a process id or the
time.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
#: The checkout's own cache directory (listed in .gitignore).
DEFAULT_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), os.pardir, os.pardir, os.pardir, ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
