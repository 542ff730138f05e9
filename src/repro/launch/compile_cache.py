"""Where JAX's persistent compilation cache lives.

Entry points call `enable_compile_cache()` before their first compile;
tests never do. The cache directory is part of what a later run must find
again, so it is either the one the environment names or one fixed path.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already uses that
    directory and nothing else is set here. Otherwise the cache is the
    fixed `<repo>/.jax_cache`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
