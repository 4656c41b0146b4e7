"""Persistent XLA compile cache, placed from outside the program.

Entry points (`launch.serve`, `launch.train`, `chip_smoke.py`) call
`enable_compile_cache()` before their first compile.  Where
`JAX_COMPILATION_CACHE_DIR` is set, the cache lives there and nowhere
else.  Otherwise it lives at one fixed directory of the checkout,
`<repo>/.jax_cache` (gitignored): the directory is part of what a later
run must find again, so it never carries a temp name, a pid or a
timestamp.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point jax's persistent compile cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
