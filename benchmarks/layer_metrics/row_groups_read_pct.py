"""Share of the row groups a statement's file scans met that they read.

100 x `lake_row_groups_read` / `lake_row_groups_total` over the window's
statements (QueryStats.counters): under 100 where a pushed-down range
excludes row groups by their footer statistics; 100 on a file whose
rows are in no order the range follows. A program that reads no file
has no such counters, and the metric stays out of its line.
"""

from benchmarks.harness.layers import stat


def read(run):
    read_, total = 0, 0
    for s in run["statements"]:
        counters = stat(s["stats"], "queryStats.counters") or {}
        read_ += counters.get("lake_row_groups_read", 0)
        total += counters.get("lake_row_groups_total", 0)
    return 100.0 * read_ / total if total else None
