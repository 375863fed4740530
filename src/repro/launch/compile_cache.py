"""JAX's persistent compile cache, placed by the entry points.

A full-width step takes tens of seconds to compile; with the cache, a
second launch of the same program loads it instead.  The entry points
(``repro.launch.train``, ``repro.launch.serve``, the cluster worker and
``chip_smoke.py``) call :func:`use_compile_cache` from their ``main``;
importing ``repro`` never does, so library users and the tests choose for
themselves.
"""
from __future__ import annotations

import os

import jax

#: the checkout root (``src/repro/launch/`` is three levels below it)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    into ``jax_compilation_cache_dir`` and nothing is set here.  Otherwise
    the cache is ``<checkout>/.jax_cache``: a fixed path, since the path is
    part of what a later launch must find again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
