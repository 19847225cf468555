"""Persistent XLA compilation cache for the entry points.

``enable_compile_cache()`` is called by ``launch/train.py`` and
``chip_smoke.py`` at start-up, never on import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and nowhere
else; otherwise it lives at one fixed path inside the checkout
(``.jax_cache/``, gitignored).  The path is part of what a cached entry
is found by, so it never depends on a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    if set, else at ``<checkout>/.jax_cache``; returns the directory."""
    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
