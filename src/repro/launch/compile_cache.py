"""Where the entry points keep JAX's persistent compilation cache.

Compiling the served model takes most of a cold start on the chip, so
``serve``, ``train`` and ``chip_smoke.py`` keep compiled programs across
runs.  Nothing happens at import: each entry point calls
:func:`enable_compile_cache` once, before its first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed, inside the checkout: the path is part of the cache's key, so a
# directory named per run (tmp, pid, time) would never be hit again.
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left as it is; otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
