"""JAX's persistent compilation cache for the program's entry points.

Called from each `main` (train, serve, benchmarks, chip_smoke.py) — never at
import, so tests and library users keep JAX's own default.
"""

from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache"]

# <repo>/.jax_cache: a fixed path, because the path is part of the cache's
# key — a directory that moves between runs never hits.
_REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX reads it on its own and no
    other cache is set here; otherwise the cache lives in <repo>/.jax_cache.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE))
    return str(_REPO_CACHE)
