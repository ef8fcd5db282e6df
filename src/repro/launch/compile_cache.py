"""JAX persistent compilation cache: one fixed place per checkout.

A whole gemma3-1b train step takes about a minute to compile; the
persistent cache lets later processes of the same checkout skip that.
The cache key includes the directory, so the directory never moves:
``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself, and
nothing else is set here), else ``<checkout>/.jax_cache`` (gitignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
