"""JAX's persistent compilation cache, turned on in one place.

Entry points that compile large programs call ``enable_compile_cache()``
before their first compile, so that later processes on the same machine
read compiled programs back instead of compiling them again.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: ``<checkout>/.jax_cache`` — a fixed path: the directory is part of the
#: cache's key, so a path that moves between runs would never hit.
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and nothing is set here.  Otherwise the cache lives at
    ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
