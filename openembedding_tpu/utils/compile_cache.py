"""Where the persistent XLA compile cache lives.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``bench.py``'s
per-config entry, ``serving.ha.replica_main``) call
:func:`enable_compile_cache` before their first compile. The test
harness does not: tests compile cold so a stale entry can never stand
in for a program the tested code no longer builds.
"""

from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# derived from the package location: the directory is part of the cache
# key, so a path that moves between runs (tempfile, pid, time) never hits
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already taken the
    directory from it and nothing is set here; otherwise the cache is
    ``<checkout>/.jax_cache``.
    """
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
