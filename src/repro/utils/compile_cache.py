"""JAX's persistent compilation cache for the repo's entry points.

Called from an entry point's ``main`` only (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``): importing this module, or
running the tests, leaves the cache setting alone.
"""
from __future__ import annotations

import os

import jax

from repro.distributed.subproc import repo_root


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache lives at the fixed path
    ``<repo>/.jax_cache``: the path is part of the cache key, so one
    derived from a temporary name, a pid or the time would never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(repo_root(), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
