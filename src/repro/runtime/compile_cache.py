"""JAX's persistent compilation cache at one fixed place.

A cache directory is part of what JAX keys its entries on, so it must not
move between runs: a temporary, per-process or time-stamped path never
hits. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing here overrides it; otherwise the cache is ``.jax_cache/`` at the
repository root (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: src/repro/runtime/compile_cache.py -> the repository root
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
