"""Where the program keeps JAX's persistent compilation cache.

Called by the entry points (``python -m repro.launch.serve``,
``chip_smoke.py``), never on import: a library must not pick a cache for
the program that imports it.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache lives in ``.jax_cache/`` at the root of the
checkout — a fixed path, because the path is part of what the cache is
keyed on: a directory that moves between runs never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIRNAME", "enable_compile_cache"]

CACHE_DIRNAME = ".jax_cache"

# src/repro/launch/compile_cache.py -> the checkout root
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
