"""Placement of JAX's persistent compilation cache.

Every entry point calls :func:`setup_compile_cache` before it compiles
anything.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and that directory is the cache.  Otherwise the cache goes to
``.jax_cache`` at the root of this checkout (listed in ``.gitignore``):
a fixed path, because the path is part of what a later run must match
to find an entry.
"""

from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: The checkout's own cache directory: src/repro/launch/ -> repo root.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
