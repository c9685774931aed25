"""JAX's persistent compilation cache, kept in one fixed place."""
from __future__ import annotations

import os
import pathlib

import jax

# the checkout root: src/repro/utils/<this file> -> three levels up
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is set here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, so a second process on the
    same checkout finds what the first one compiled.  Call it from an
    entry point's ``main()``, never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
