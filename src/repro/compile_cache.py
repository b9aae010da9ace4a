"""Placement of JAX's persistent compilation cache.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/serving_bench.py``, ``benchmarks/run.py``) calls
``use_compile_cache()`` before its first compile.  Where the environment
sets ``JAX_COMPILATION_CACHE_DIR``, JAX already reads it and nothing else
is set.  Otherwise the cache lives at one fixed path, ``<repo>/.jax_cache``
(gitignored): the directory is part of what a later run must find again,
so it never carries a temporary name, a process id or a timestamp.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory and return
    that directory."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
