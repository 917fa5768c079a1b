"""Persistent compilation cache for the launchers and ``chip_smoke.py``.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing. Otherwise the cache goes to ``.jax_cache`` at the root of the
checkout: a fixed path, because the path is part of the cache key, so a
directory that moves between runs never hits. Tests do not call this.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def configure() -> str:
    """Turn the cache on; returns the directory it lives in."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
