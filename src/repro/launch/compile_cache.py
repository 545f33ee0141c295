"""Persistent JAX compilation cache for the entry points.

Called once at start-up by ``chip_smoke.py``, ``launch/serve.py`` and
``launch/train.py`` — never at import, so tests and library users keep
JAX's own defaults. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing is set here. Otherwise the cache lives at a
fixed directory of the checkout (``.jax_cache/``, git-ignored): the path
is part of the cache key, so it never depends on a temp name, a pid or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    CHECKOUT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
