"""JAX's persistent compilation cache, kept at one fixed place.

Compiling a solver executable at a real size takes minutes (one tree
executable per route, see ``core.plan``), so every entry point that runs
solves -- ``chip_smoke.py``, ``benchmarks/run.py``, ``launch/train.py``
and the examples -- calls :func:`enable_compile_cache` once at start-up.
Importing the library never does.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
there and nothing here overrides it.  Otherwise the cache lives at
``<checkout>/.jax_cache``: a fixed path, because the directory is part of
what a later process must find again (never a temporary, per-process or
time-stamped directory).
"""

from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
