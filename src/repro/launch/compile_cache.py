"""Persistent XLA compilation cache at a fixed, caller-placeable path.

Entry points call `use_compile_cache()` before their first compile. If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
changed. Otherwise the cache goes to ``<repo root>/.jax_cache`` (listed in
.gitignore). The directory is part of the cache key, so it is fixed: a
temporary or per-run path would never hit. Tests do not call this.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
