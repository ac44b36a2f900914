"""The largest program of the window against the chip's memory.

`program_hbm_bytes` in QueryStats.counters: arguments, outputs and
temporaries of the largest program the statement dispatched, as XLA's
memory analysis of the executable plans them (exec/runner.
_program_hbm_bytes). Where an executable gives no analysis (one read
from a compile cache may not) the program reports the allocator's
`peak_bytes_in_use` instead, which is the process's peak so far and so
includes the load: the line's `device.memory_peak_bytes` says how high
that is. The metric is the largest over the window's statements, over
the device's published HBM size.
"""

from benchmarks.harness.layers import stat
from benchmarks.harness.peaks import peak


def read(run):
    seen = [stat(s["stats"], "queryStats.counters.program_hbm_bytes")
            for s in run["statements"]]
    seen = [b for b in seen if b]
    if not seen:
        return None
    return 100.0 * max(seen) / peak(run["device_kind"], "hbm_bytes")
