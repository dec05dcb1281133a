"""Persistent XLA compilation cache for the entry points.

``chip_smoke.py`` and ``benchmarks/engine_bench.py`` call
:func:`enable_compile_cache` before their first compile, so a second run
loads the fused decode scan and the chunked-prefill step instead of
compiling them again. The library import and the tests never call it.

The cache's path is part of its key, so it is fixed: the directory named
by ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the variable
itself, and nothing is set here), else ``.jax_cache/`` at the repo root.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
