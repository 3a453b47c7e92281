"""Where JAX's persistent compilation cache lives.

A cold run of the serve path compiles decode, chunk, verify and fused
once per context bucket, seconds each at full width.  The cache keeps
them across processes, and its key includes the directory, so the
directory must not move between runs: ``$JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads it itself), otherwise ``.jax_cache`` at the
root of the checkout.  Entry points call ``use_compile_cache()`` once
in ``main``; importing a module never touches the cache.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["use_compile_cache", "DEFAULT_DIR"]

#: <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory: the one ``JAX_COMPILATION_CACHE_DIR`` names, else
    ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
