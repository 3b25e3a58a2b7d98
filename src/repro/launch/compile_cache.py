"""Where JAX's persistent compilation cache lives.

Entry points call :func:`use_compile_cache` once, before they compile
anything.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this module sets nothing.  Otherwise the cache goes to the
fixed directory ``<checkout>/.jax_cache`` (git-ignored) — never a
temporary name, a process id or a timestamp — so a second run from the
same checkout finds what the first compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["use_compile_cache", "CHECKOUT_CACHE_DIR"]

#: ``<checkout>/src/repro/launch/compile_cache.py`` -> ``<checkout>/.jax_cache``
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
