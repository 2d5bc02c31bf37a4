"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles (``chip_smoke.py``,
``examples/run_scenario.py``, ``benchmarks/run.py``, ``repro.launch.dryrun``
and ``repro.launch.perf``) calls :func:`use_compile_cache` before its first
compile, so all of them share one cache and a second run of the same
program on the same machine skips recompilation.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

#: ``<checkout>/.jax_cache`` (this file is ``<checkout>/src/repro/compile_cache.py``)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here.  Otherwise the cache lives at
    :data:`CHECKOUT_CACHE_DIR`.  The directory is always a fixed path —
    never one made from a temp name, a pid or the time — so a later run
    finds what an earlier one stored.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
