"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`configure_compile_cache` before their first
compile; importing ``repro`` sets nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# The checkout's root: src/repro/launch/compile_cache.py -> parents[3].
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def configure_compile_cache() -> str:
    """Return the compilation cache directory, placing it if need be.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``.jax_compile_cache``
    at the root of the checkout: a fixed path, since the path is part of
    each entry's key, so a later run of the same checkout finds what an
    earlier one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
