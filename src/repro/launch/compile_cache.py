"""Placement of JAX's persistent compilation cache for every entry point.

A cold start of the full-width serving step compiles for about a minute;
the persistent cache lets a later process on the same machine skip that.
The cache key includes the directory, so the directory never moves between
runs: it is either the one the environment names or one fixed path inside
the checkout.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    this sets nothing.  Otherwise the cache goes to ``.jax_cache`` at the
    root of the checkout (listed in ``.gitignore``)."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
