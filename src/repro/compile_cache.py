"""JAX's persistent compilation cache, placed from outside the program.

Entry points (scripts, ``chip_smoke.py``, ``repro.launch.train``) call
:func:`enable_compile_cache` once, before their first compile; library
modules never call it, so importing the package (as the tests do) leaves
the cache off.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets no other directory.  Otherwise the cache lives at ``.jax_cache`` in
the checkout: a fixed path, because the path is part of what a later
process must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
