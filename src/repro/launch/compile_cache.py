"""JAX's persistent compilation cache, placed at one path per checkout.

A compile of a full round step takes minutes on a TPU, and each process
starts with no compiled code. The launchers and ``chip_smoke.py`` call
``enable_compile_cache()`` at the top of their ``main`` (never on import),
so a second run of the same program reads its executables back.

The cache key includes the directory, so the path must not move between
runs: when ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
this sets nothing; otherwise the cache lives at ``<checkout>/.jax_cache``
(listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
