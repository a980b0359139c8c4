"""JAX's persistent compilation cache at one fixed place.

Entry points (``chip_smoke.py``, ``launch.serve_extract``,
``launch.serve_cluster``, ``benchmarks.run``) call ``enable`` once at
start-up, never at import. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and nothing is set in code; otherwise the cache lives
in ``<repo>/.jax_cache`` (git-ignored). The path is fixed because it is
part of what the cache is keyed on: a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
