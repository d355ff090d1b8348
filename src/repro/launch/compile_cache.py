"""Persistent XLA compilation cache for the programs that run on the chip.

Entry points that compile for the TPU (`chip_smoke.py`, `launch/serve.py`,
the `benchmarks/` mains) call `enable_compile_cache()` once, first thing.
Library code, tests and imports never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed path: the cache key includes it, so it must not move between runs.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and no
    directory is set here. Otherwise the cache lives at the git-ignored
    `<repo>/.jax_cache`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
