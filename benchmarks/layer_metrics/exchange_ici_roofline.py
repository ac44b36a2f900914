"""The rows the exchanges routed against the chip's interconnect.

The least time one chip's `exchange_row_bytes` of the traced statements
(bytes of active rows its hash and range exchanges really routed) could
take at the chip's published interconnect rate, over the seconds in
which the device ran an operation in the traced window. Like
`scan_hbm_roofline` it is a share of the whole device side of a
statement, not of one kernel, and cannot pass 100 by construction: rows
really routed over the whole busy time, so no padding and no change of
the exchange's form inflates it. What bounds it is the interconnect.

The rate stands here with its source because `harness/peaks.py` is
edited only by a `benchmark` PR: Google Cloud documentation, "TPU v5e":
1,600 Gbit/s of chip-to-chip interconnect a chip = 200 GB/s. (A v5e 2x2
wires half of each chip's ports, so a 2x2 cannot reach it.) A device
that is not in the table is an error, never a default.
"""

from benchmarks.harness.layers import stat

ICI_BYTES_PER_S = {"TPU v5 lite": 200e9}


def read(run):
    trace = run["trace"]
    if not trace:
        return None
    routed = [stat(s["stats"], "queryStats.counters.exchange_row_bytes")
              for s in run["statements"] if s["traced"]]
    routed = [b for b in routed if b is not None]
    if not routed:
        return None
    kind = run["device_kind"]
    if kind not in ICI_BYTES_PER_S:
        raise KeyError(f"no published interconnect rate for device kind "
                       f"{kind!r}; add it here with its source")
    least_s = sum(routed) / ICI_BYTES_PER_S[kind]
    return 100.0 * least_s / trace["busy_s"]
