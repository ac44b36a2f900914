"""Device time of the collectives, a chip's mean a traced statement.

The entries of the traced window's `device_ops` whose XLA name starts
with `all-to-all`, `all-gather`, `all-reduce` or `collective-permute`,
summed and divided by the traced statements. `device_ops` lists the ten
ops of largest time only (harness/trace_reduce.reduce_events), each a
chip's mean: a collective that is not among the ten is not in this
number, so it is a lower bound, and 0.0 says that every collective took
less than the tenth op. The packing around a collective (ranks and
scatters) is the program's scope `_route_rows`, read by
`scripts/trace_view.py --xplane`, not this metric.
"""

COLLECTIVES = ("all-to-all", "all-gather", "all-reduce",
               "collective-permute")


def read(run):
    trace = run["trace"]
    traced = sum(1 for s in run["statements"] if s["traced"])
    if not trace or not traced:
        return None
    seconds = sum(s for name, s in trace["device_ops"]
                  if name.startswith(COLLECTIVES))
    return 1000.0 * seconds / traced
