"""JAX's persistent compilation cache for the entry points.

A cold compile of a full-width step takes tens of seconds; the cache lets a
second run of the same program skip it.  Called by ``launch/train.py``,
``launch/serve.py`` and ``chip_smoke.py`` — never on ``import repro``.
"""
from __future__ import annotations

import os

import jax

#: The one cache directory used when the environment names none: fixed and
#: inside the checkout (the path is part of what a later run must find).
REPO_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX, which reads it
    itself; otherwise the cache lives at :data:`REPO_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
