"""Where JAX keeps its persistent compilation cache for the repo's scripts.

`chip_smoke.py` and `benchmarks/run.py` call `configure_compile_cache` once,
before their first compile; library code and tests never do.  JAX reads
`JAX_COMPILATION_CACHE_DIR` itself, so when it is set nothing is changed.
Otherwise the cache goes to `<checkout>/.jax_cache` (git-ignored).  The path
is part of every cache key, so it is fixed: never a temporary name, a
process id or a time.
"""
from __future__ import annotations

import os

__all__ = ["configure_compile_cache"]


def configure_compile_cache(checkout) -> str:
    """Point JAX's persistent compilation cache at its fixed place and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
