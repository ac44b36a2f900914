"""Decoded bytes over the decode hop's wall, MB/s.

`lake_decoded_bytes` in QueryStats.counters (the engine lanes and null
masks a statement's file scans produced) over `datapath.decode.wall_us`,
both summed over the window's statements that carry them: the rate a
later change to the decode has to move. A program that reads no file
has neither, and the metric stays out of its line.
"""

from benchmarks.harness.layers import stat


def read(run):
    nbytes = wall_us = 0
    for s in run["statements"]:
        b = stat(s["stats"], "queryStats.counters.lake_decoded_bytes")
        w = stat(s["stats"], "queryStats.datapath.decode.wall_us")
        if b and w:
            nbytes += b
            wall_us += w
    return nbytes / wall_us if wall_us else None  # B/us = MB/s
