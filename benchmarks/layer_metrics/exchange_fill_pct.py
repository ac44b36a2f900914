"""How much of what the hash and range exchanges move is rows.

`exchange_row_bytes` (bytes of the active rows a chip's hash and range
exchanges really routed, the mesh's mean, read beside the status word)
over `exchange_slot_bytes` (the send slots of those exchanges, a
constant of the program's shapes: their share of `exchange_bytes`),
summed over the window's statements. The rest is padding: slots are
sized for a full sender (parallel/exchange.slot_for) and a filter's or
a join's dropped rows never take a place in one.
"""

from benchmarks.harness.layers import stat


def read(run):
    rows = slots = 0
    for s in run["statements"]:
        routed = stat(s["stats"], "queryStats.counters.exchange_row_bytes")
        moved = stat(s["stats"], "queryStats.counters.exchange_slot_bytes")
        if routed is not None and moved:
            rows, slots = rows + routed, slots + moved
    return 100.0 * rows / slots if slots else None
