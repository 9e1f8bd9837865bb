"""Where JAX keeps its persistent compilation cache.

One rule for every entry point that compiles for the chip: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this sets
nothing else; otherwise the cache lives at ``<repo>/.jax_cache``.  The path
is part of the cache key, so it is fixed (never a temporary, pid- or
time-named directory) and a later run of the same checkout finds it again.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the checkout's root: src/repro/launch/compile_cache.py -> parents[3]
REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
