"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `repro.launch.serve`, `repro.launch.train`,
`benchmarks.run`) call :func:`use_compile_cache` before they compile
anything; importing `repro` never does. A later run finds what an earlier
one cached only in the same directory, so it is one fixed path: the one
`JAX_COMPILATION_CACHE_DIR` names (JAX reads that variable itself, and
nothing is set here), or else `.jax_cache` at the root of the checkout,
which `.gitignore` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout's root: src/repro/launch/compile_cache.py → three levels up
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With `JAX_COMPILATION_CACHE_DIR` set, JAX already uses it and this
    sets nothing; otherwise the cache goes to :data:`REPO_CACHE_DIR`."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
