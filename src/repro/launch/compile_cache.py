"""JAX's persistent compilation cache for the launch drivers and
``chip_smoke.py``.

A cold start on the chip spends much of its time compiling: the decode
and prefill steps, the quantizer's per-block programs. With the cache
on, a second run of the same checkout reads those programs back.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path, never one built from a pid, the time or a temporary
# name: the directory is part of how a later run finds the entries.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; call before the first
    compile. Returns the directory in use.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache lives in ``.jax_cache/`` at
    the root of this checkout (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
