"""Programs a statement asked the compiler for, mean per statement.

`xla_compiles` in QueryStats.counters: the backend compile calls made on
the statement's thread, wherever in the statement (a planner's constant
fold and the dynamic filter's program as well as a region's first
dispatch). A call that the persistent compile cache answered is one too
(`compile_cache_reads` beside this says how many were). A statement of
a warmed server reads 0: anything else is a program traced anew in every
statement.
"""

from benchmarks.harness.layers import stat


def read(run):
    seen = [stat(s["stats"], "queryStats.counters") or {}
            for s in run["statements"]
            # a program without the seam never counted: nothing to read
            if stat(s["stats"], "queryStats.stages.queue") is not None]
    if not seen:
        return None
    return sum(c.get("xla_compiles", 0) for c in seen) / len(seen)
