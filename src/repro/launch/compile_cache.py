"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `benchmarks/run.py`) call
`configure_compile_cache` once, before their first compile; importing this
module changes nothing. The cache key includes the directory, so the path
is fixed: a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax


def configure_compile_cache(repo_root: str | os.PathLike) -> str:
    """Point the persistent compilation cache at its directory and return it.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and the
    cache stays there. Otherwise the cache goes to ``<repo_root>/.jax_cache``.
    Either way every compile is cached, the second-long Pallas kernel
    compiles included (JAX's default skips compiles under one second).
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(Path(repo_root).resolve() / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
