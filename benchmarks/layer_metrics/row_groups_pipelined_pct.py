"""Share of the row groups a statement's file scans read that were staged
through the pipeline.

100 x `lake_row_groups_pipelined` / `lake_row_groups_read` over the
window's statements (QueryStats.counters): the groups whose lanes were
decoded at their narrowed dtypes and put to the device one by one while
later groups were still being read, of all the groups read. Under 100
where a scan assembled its columns on the host first: a dynamic-filtered
scan, a string column, a narrowing a group refused. A program that reads
no file, or one from before the pipeline, has not both counters, and the
metric stays out of its line.
"""

from benchmarks.harness.layers import stat


def read(run):
    pipelined, read_ = 0, 0
    for s in run["statements"]:
        counters = stat(s["stats"], "queryStats.counters") or {}
        if "lake_row_groups_pipelined" in counters:
            pipelined += counters["lake_row_groups_pipelined"]
            read_ += counters.get("lake_row_groups_read", 0)
    return 100.0 * pipelined / read_ if read_ else None
