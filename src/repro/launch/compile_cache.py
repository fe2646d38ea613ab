"""Where the entry points keep JAX's persistent compilation cache.

A run on a fresh machine otherwise compiles every shape of every variant
again. Importing this module changes nothing; the entry points call
`use_compile_cache()` before their first compile. Tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed and git-ignored: a later process finds what an earlier one stored
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to `<repo>/.jax_cache`.
    `jax.config.jax_compilation_cache_dir` names the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
