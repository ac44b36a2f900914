"""How full the counted nodes of a statement's programs ran.

`capacity_live_rows` (what the joins and keyed aggregations of the
dispatch that answered needed: a join's output rows, an aggregation's
groups, as the program counted them on the device) over `capacity_rows`
(the static capacities that dispatch was built with), each statement's
share, the mean over the statements that carry both counters. The rest
is padding every operator above the node pays for: the ladder's one
scale gave Q3's group-by, second join and top-N the capacity only its
first join needs; a capacity per node, sized from the node's own count,
is at least half full wherever it is a power of two above a thousand
rows. A statement without a join or a keyed aggregation (Q6) carries
neither counter, and a program that reports none reads None.
"""

from benchmarks.harness.layers import stat


def read(run):
    shares = []
    for s in run["statements"]:
        live = stat(s["stats"], "queryStats.counters.capacity_live_rows")
        rows = stat(s["stats"], "queryStats.counters.capacity_rows")
        if live is not None and rows:
            shares.append(100.0 * live / rows)
    return sum(shares) / len(shares) if shares else None
