"""Where JAX keeps its persistent compilation cache for the entry points.

Every entry point that runs on the chip (``chip_smoke.py``,
``benchmarks.run``, ``repro.service.server`` and ``repro.launch.train``)
calls :func:`enable_compile_cache` before its first compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so nothing is
    set here and the cache lives wherever the environment says;
  * otherwise the cache goes to ``.jax_cache/`` at the repository root, a
    fixed path (the path is part of the cache key, so a directory that
    moved between runs would never hit).

Library code and the tests never call it, so test runs keep the cache
off.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
