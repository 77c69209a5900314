"""JAX's persistent compilation cache for the entry points.

A full-width model compiles for minutes from cold; with the cache on, a
second process with the same programs loads them instead.  The launchers
(``launch/serve.py``, ``launch/train.py``) and ``chip_smoke.py`` call
:func:`enable_compile_cache` first thing in ``main``; importing this
module changes nothing.

Where the cache lives:

- ``JAX_COMPILATION_CACHE_DIR``, when set.  JAX reads the variable
  itself, so no other directory is set in code.
- Otherwise :data:`CACHE_DIR`, a fixed directory inside the checkout
  (git ignores it).  The path is part of what JAX keys on, so it must not
  move between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["CACHE_DIR", "compile_cache_dir", "enable_compile_cache"]

CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_compile_cache")


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compilation: JAX fixes its cache directory when
    it first compiles.
    """
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", path)
    return path
