"""The persistent XLA compile cache, placed once for every entry point.

A cold TPC-H q1 compiles for the chip in tens of seconds, so every
process that runs queries (statement server, worker, CLI,
chip_smoke.py, bench.py, the test armor in scripts/_cpu.py) calls
`setup_compile_cache()` before its first query. The directory is part
of the cache's key, so it is a fixed path: `JAX_COMPILATION_CACHE_DIR`
when the environment sets it (JAX reads that itself; nothing here sets
another), else `<checkout>/.cache/jax` next to the package.
"""

from __future__ import annotations

import os

__all__ = ["setup_compile_cache"]

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".cache", "jax")


def setup_compile_cache() -> str:
    """Idempotent. Returns the directory the cache lives in."""
    import jax
    # every program is worth keeping wherever the cache lives: the
    # engine's cost on a cold start is almost entirely compiles, small
    # ones included. These are thresholds, not a directory.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    os.makedirs(_DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
