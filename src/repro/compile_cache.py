"""Where JAX keeps its persistent compilation cache.

``enable_compile_cache`` is called once at start-up by the programs that
drive the chip (``chip_smoke.py``, ``benchmarks/run.py``), never at import.
``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and wins: no
other directory is set.  Otherwise the cache goes to ``<repo>/.jax_cache``,
a fixed path (a directory that moves never hits), listed in ``.gitignore``.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir
