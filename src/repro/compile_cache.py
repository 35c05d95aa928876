"""JAX's persistent compilation cache for the entry points that drive a chip.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache lives at one fixed directory inside
the checkout, so a later process on the same checkout finds the programs
an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed in-checkout location (listed in .gitignore)
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
