#!/usr/bin/env python
"""What one span of the statement's seam costs (exec/stats.py).

  python scripts/microbench_span_cost.py [--spans 100000] [--trace 1]

Opens and closes `stats.stage(name)` (a span that is also summed into
`QueryStats.stages`) and `stats.span(name)` (recorded, summed nowhere)
N times on an ambient collector, and the bare `TraceAnnotation` beside
them, first with no profile running and then, with `--trace 1`, under
`jax.profiler` as the benchmark's traced window sets it (host tracer
level 2, no Python tracer). Prints one JSON line: nanoseconds a span,
the median of five rounds, by form and by whether a profile ran. A host
number: it says what the machine it ran on pays, whatever device JAX
found there.
"""

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rounds(fn, n: int, rounds: int = 5) -> float:
    """Median nanoseconds a call of `fn`, over `rounds` rounds of `n`."""
    per_call = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        per_call.append((time.perf_counter() - t0) / n * 1e9)
    return round(statistics.median(per_call), 1)


def measure(n: int) -> dict:
    from jax.profiler import TraceAnnotation

    from presto_tpu.exec.stats import StatsCollector, collecting, span, stage

    def a_stage():
        with stage("scan_count", {"scans": 1}):
            pass

    def a_span():
        with span("prune"):
            pass

    def an_annotation():
        with TraceAnnotation("presto:scan_count", scans=1):
            pass

    out = {}
    for name, fn in (("stage_ns", a_stage), ("span_ns", a_span)):
        collector = StatsCollector("microbench")
        with collecting(collector):
            out[name] = _rounds(fn, n)
        assert len(collector.spans) == 5 * n
    out["annotation_ns"] = _rounds(an_annotation, n)
    # no collector ambient: a library caller outside any statement
    out["stage_no_collector_ns"] = _rounds(a_stage, n)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spans", type=int, default=100_000)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    import jax
    line = {"spans": args.spans, "platform": jax.devices()[0].platform,
            "no_profile": measure(args.spans)}
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="span_cost_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            line["under_profile"] = measure(args.spans)
        finally:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            line["stop_trace_s"] = round(time.perf_counter() - t0, 3)
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
