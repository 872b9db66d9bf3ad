"""The persistent XLA compilation cache of this checkout's entry points.

A fresh process skips the compiles an earlier one paid for, so cold time to
first result comes close to warm.  The cache cannot change a bit: it stores
compiled executables keyed by HLO, compile options and backend, so a hit
returns the program a recompile would produce.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compilation_cache"]

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset: a fixed
#: path beside ``src/`` (the path is part of what makes entries hit again)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on the persistent cache for this process; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and that
    directory is the cache; nothing else is set.  Otherwise the cache goes
    to :data:`CHECKOUT_CACHE_DIR`.  Every compile is cached, sub-second ones
    included: streaming ingest is the many-small-programs workload that
    jax's default thresholds skip.  Called by entry points, never on import.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
