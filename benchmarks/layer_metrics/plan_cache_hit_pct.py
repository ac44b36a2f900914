"""Share of the window's region programs found in the plan cache.

100 x hits / (hits + misses) over the statements of the window, from
the counters each statement carries (`plan_cache_hits`,
`plan_cache_misses` in QueryStats.counters: one of the two per call of
`plan_cache.cached_compile`). A warmed cell reads 100.
"""

from benchmarks.harness.layers import stat


def read(run):
    hits = misses = 0
    for s in run["statements"]:
        counters = stat(s["stats"], "queryStats.counters") or {}
        hits += counters.get("plan_cache_hits", 0)
        misses += counters.get("plan_cache_misses", 0)
    return 100.0 * hits / (hits + misses) if hits + misses else None
