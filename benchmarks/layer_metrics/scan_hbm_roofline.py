"""The traced statements' staged bytes against the device time they took.

The least time one pass over the staged columns could take (bytes over
the chip's peak HBM bandwidth), over the seconds in which the device ran
an operation in the traced window. It is a share of the memory roofline
of the whole device side of a statement, not of one kernel: kernel times
by name wait for scopes inside the program.
"""

from benchmarks.harness.layers import stat
from benchmarks.harness.peaks import peak


def read(run):
    trace = run["trace"]
    if not trace:
        return None
    staged = [stat(s["stats"], "queryStats.stages.staging.bytes")
              for s in run["statements"] if s["traced"]]
    staged = [b for b in staged if b]
    if not staged:
        return None
    least_s = sum(staged) / peak(run["device_kind"], "hbm_bytes_per_s")
    return 100.0 * least_s / trace["busy_s"]
