"""JAX's persistent compilation cache for this checkout's entry points.

Every entry point that compiles at real widths (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks/bench_serving.py``) calls
:func:`enable_compile_cache` from its ``__main__`` path, so a second run
reads the compiled programs instead of paying the whole AOT ladder
again.  Importing this module changes nothing; tests never call it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives at a fixed
``.jax_cache/`` in the checkout (listed in ``.gitignore``): the path is
part of what a later run must find, so it is never temporary or named by
pid or time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
