"""Executables read from the persistent compile cache, mean per statement.

`compile_cache_reads` in QueryStats.counters: jax's
`/jax/compilation_cache/cache_hits` events on the statement's thread.
Of the `xla_compiles` of a statement these cost a file read and a
deserialization, not a compile; a statement of a warmed server reads 0.
"""

from benchmarks.harness.layers import stat


def read(run):
    seen = [stat(s["stats"], "queryStats.counters") or {}
            for s in run["statements"]
            # a program without the seam never counted: nothing to read
            if stat(s["stats"], "queryStats.stages.queue") is not None]
    if not seen:
        return None
    return sum(c.get("compile_cache_reads", 0) for c in seen) / len(seen)
