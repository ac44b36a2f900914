"""Share of the window's scanned columns the resident tier held.

100 x hits / (hits + misses) over the statements of the window, from
the counters each statement carries (`resident_hits`: columns a
whole-table scan of a memory table took from HBM as they lay, by table
version; `resident_misses`: columns such a scan staged from the host
and offered to the tier; exec/runner._stage_resident). None where no
statement carries either: a program without the tier, or a cell whose
scans all bypass it (generated and lake tables, dynamic-filtered
scans). A warmed window over unchanged tables reads 100.
"""

from benchmarks.harness.layers import stat


def read(run):
    hits = misses = 0
    for s in run["statements"]:
        counters = stat(s["stats"], "queryStats.counters") or {}
        hits += counters.get("resident_hits", 0)
        misses += counters.get("resident_misses", 0)
    return 100.0 * hits / (hits + misses) if hits + misses else None
