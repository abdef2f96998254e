"""JAX's persistent compilation cache, placed from outside.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/serve_bench.py``) calls
:func:`enable_compile_cache` before it compiles anything, so processes
that run the same programs share compiled code.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path, never a temporary, per-process or timestamped name, so the
# next process finds what this one wrote.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    no other directory is set here.  Otherwise the cache goes to
    ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
