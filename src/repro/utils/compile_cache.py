"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles calls ``use_compile_cache`` before its
first compile, so all the processes of one checkout share one cache.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Otherwise the cache is one fixed directory inside
the checkout, never a temporary, per-process or per-run name: a later
process only finds entries at the path an earlier one wrote them to.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache`` (listed in ``.gitignore``)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its
    directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
