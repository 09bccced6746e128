"""Where JAX's persistent compilation cache lives for the command-line
entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``launch/serve.py``,
``launch/train.py``).  Importing ``repro`` never turns the cache on; each
entry point calls ``enable_compile_cache()`` once, before it compiles.

A deployment places the cache from outside with ``JAX_COMPILATION_CACHE_DIR``
(JAX reads that variable itself, and this module leaves it alone).  Without
it the cache goes to one fixed directory inside the checkout: the directory
is part of every entry's key, so a path that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
