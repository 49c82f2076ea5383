"""JAX's persistent compilation cache, kept at one fixed directory.

A cold serve of a 36-layer model compiles several per-tick programs
(chunk prefill, decode scan, draft, verify, page copy), and every new
engine instance traces fresh jits.  The persistent cache lets later
engines and later runs reuse those executables.  Its entries are only
found again from the same directory, so the directory never moves.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing else is set.  Otherwise the cache goes to
    ``<repo>/.jax_cache``.  Entry points call this once at start-up; it is
    never run at import.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
