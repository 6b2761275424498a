"""JAX's persistent compilation cache at one fixed place.

Every entry point that compiles for the chip (``chip_smoke.py``,
``launch/train.py``, ``launch/serve.py``, ``examples/``) calls
``enable_compile_cache()`` before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
else is configured here.  Otherwise the cache lives in ``.jax_cache/``
at the root of the checkout (git-ignored): a fixed path, because the
path is part of what a later run must find again.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
