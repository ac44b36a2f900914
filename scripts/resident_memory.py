#!/usr/bin/env python3
"""One run of a benchmark cell that also reports the device's memory
after the warm-up, beside what the resident tier holds.

  python scripts/resident_memory.py --workload mem_sf10.join --seed 1 \\
      [any other option of benchmarks/run.py]

Runs `benchmarks/run.py` unchanged in this process, with one line more
on standard error once the warm-up is done: `bytes_in_use` and
`bytes_limit` of each device (`memory_stats()`), the tier's bytes on
its fullest chip (`exec/resident.py`) and the largest program the
process has dispatched (`program_hbm_bytes`, the tier's room is the
limit less it). A checkout without the tier reports its bytes as None.
Exits as run.py does (3 without a TPU).
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402


def _memory_line() -> dict:
    import jax
    line = {"devices": [
        {k: (d.memory_stats() or {}).get(k) for k in
         ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
        for d in jax.devices()]}
    try:
        from presto_tpu.exec.resident import tier
    except ImportError:  # a checkout without the tier
        line.update(resident_bytes=None, largest_program_bytes=None)
    else:
        line.update(resident_bytes=tier().held_bytes(),
                    largest_program_bytes=tier()._largest_program)
    return line


def main(argv=None) -> int:
    warm_up = run.Cell.warm_up

    def warm_up_then_report(self, *args, **kwargs):
        warm_up(self, *args, **kwargs)
        run._log("memory after warm-up", json.dumps(_memory_line()))

    run.Cell.warm_up = warm_up_then_report
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
