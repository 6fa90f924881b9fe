"""Where JAX keeps its persistent compilation cache, for every entry point.

``JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache sits
at one fixed path in the checkout (``.jax_cache/``, git-ignored).  The path
is part of the cache key, so a location that moved between runs never hits.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the environment's
    directory, or at the checkout's ``.jax_cache/``; returns the path."""
    path = os.environ.get(ENV_VAR) or CHECKOUT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
