"""JAX's persistent compilation cache for the program entry points.

Each entry point (``chip_smoke.py``, ``repro.launch.fl_run`` /
``scenario_run`` / ``serve`` and ``repro.serve.worker``, when run as a
program) calls ``use_compile_cache()`` before its first compile;
importing the library sets nothing.  ``JAX_COMPILATION_CACHE_DIR``, when
set, is JAX's own setting and is left alone.  Otherwise the cache lives
in one fixed directory inside the checkout, ``.jax_cache`` at its root
(ignored by git): a cache whose directory moves never hits, so the path
never depends on a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
