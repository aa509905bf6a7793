"""JAX's persistent compilation cache, kept at one fixed place.

A gpt2-small train step takes minutes to compile for the TPU from cold;
with the cache, a second run of the same program on the same machine
loads it instead.  That only works if every run looks in the same
place, so the path never depends on a temporary name, a process id or
the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on before the first compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
