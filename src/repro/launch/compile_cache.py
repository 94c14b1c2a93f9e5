"""JAX's persistent compilation cache, switched on by the entry points.

Each program entry point (the train, serve and cluster launchers, the
benchmark harness and ``chip_smoke.py``) calls `enable_compile_cache` once
before it compiles anything. Library imports never call it: process-wide
JAX configuration belongs to the program, not to a module it imports.

The rule: when ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and that directory stands; nothing here overrides it. Otherwise the cache
lives at the fixed, git-ignored ``<checkout>/.jax_cache``, so every run
from one checkout finds what an earlier run compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns the path."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
