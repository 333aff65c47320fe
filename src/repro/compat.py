"""Tiny cross-layer jit utilities."""
from __future__ import annotations

import functools
import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and
    wins untouched.  Otherwise the cache lives in ``.jax_cache/`` at the
    root of the checkout: a fixed path, because the path is part of what
    a later run must find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def hashable_lru(maxsize: int = 64):
    """``lru_cache`` that degrades to an uncached call on unhashable args.

    The serving layers cache jitted programs keyed on the (frozen,
    usually hashable) pod/algorithm dataclasses so resumable loops and
    repeated pipelines don't retrace; an exotic unhashable algorithm
    must still work, just without the shared cache.
    """
    def deco(fn):
        cached = functools.lru_cache(maxsize=maxsize)(fn)

        @functools.wraps(fn)
        def wrapper(*args):
            try:
                return cached(*args)
            except TypeError:
                return fn(*args)

        return wrapper

    return deco


__all__ = ["CACHE_DIR", "hashable_lru", "use_compile_cache"]
