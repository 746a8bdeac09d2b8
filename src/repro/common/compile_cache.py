"""Where JAX keeps compiled programs between processes.

``JAX_COMPILATION_CACHE_DIR``, when set, places the cache from outside:
JAX reads the variable itself and no other directory is set here.
Otherwise the cache lives at a fixed ``.jax_cache`` in the checkout (listed
in ``.gitignore``), so every later run from this checkout finds it.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
