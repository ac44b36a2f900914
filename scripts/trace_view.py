#!/usr/bin/env python
"""Render a stitched distributed trace as an ASCII waterfall.

The reading end of the one-trace-per-query contract: point it at a
coordinator's ``GET /v1/trace/{queryId}`` (or a worker's local-slice
endpoint, or a ``RecordingTracer.export_jsonl`` file) and it prints the
span tree on the trace's time axis with critical-path attribution --
"where did q1's 1.2s go?" answered from one artifact.

  python scripts/trace_view.py http://127.0.0.1:8080/v1/trace/20260730_ab12
  python scripts/trace_view.py http://127.0.0.1:8080 --query 20260730_ab12
  python scripts/trace_view.py spans.jsonl --trace query.deadbeef
  python scripts/trace_view.py spans.jsonl            # lists trace ids
  python scripts/trace_view.py --xplane t.xplane.pb   # a profiler trace:
      device seconds by the program's scope (region/operator/ops function)
      and, per statement, device idle seconds by presto:<span>

Exit codes: 0 rendered, 1 trace not found / empty, 2 source unreadable.
"""

import argparse
import json
import os
import sys
import urllib.request

# repo root importable regardless of invocation directory
sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

from presto_tpu.traceview import device_time_by_scope, fetch_trace, \
    render_scopes, render_waterfall  # noqa: E402


def load_jsonl(path: str, trace_id: str = None):
    """JSONL span export OR a saved ``/v1/trace/{queryId}`` document ->
    one trace doc (or the available ids when the file holds several
    traces and none was picked)."""
    by_trace = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            doc = json.loads(line)
            if "spanId" in doc:
                by_trace.setdefault(doc.get("traceId", "?"),
                                    []).append(doc)
            elif isinstance(doc.get("spans"), list):
                # a curl'd GET /v1/trace/{queryId} response saved whole
                for span in doc["spans"]:
                    by_trace.setdefault(doc.get("traceId", "?"),
                                        []).append(span)
    if trace_id is not None:
        spans = by_trace.get(trace_id)
        return {"traceId": trace_id, "spans": spans} if spans else None
    if len(by_trace) == 1:
        tid, spans = next(iter(by_trace.items()))
        return {"traceId": tid, "spans": spans}
    if not by_trace:
        return None
    return {"_ids": sorted(by_trace)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="trace_view")
    ap.add_argument("source", nargs="?",
                    help="trace URL, coordinator base URL "
                         "(with --query), or spans JSONL file")
    ap.add_argument("--xplane", default=None, metavar="FILE",
                    help="a profiler trace (.xplane.pb[.gz]): device "
                         "time by scope, idle time by presto: span")
    ap.add_argument("--query", default=None,
                    help="query id: source is a coordinator/worker base "
                         "URL, fetch its /v1/trace/{query}")
    ap.add_argument("--trace", default=None,
                    help="trace id to pick out of a JSONL file")
    ap.add_argument("--width", type=int, default=72)
    args = ap.parse_args(argv)
    if (args.xplane is None) == (args.source is None):
        ap.error("give a trace source or --xplane FILE")

    try:
        if args.xplane is not None:
            print(render_scopes(device_time_by_scope(args.xplane)))
            return 0
        if args.source.startswith(("http://", "https://")):
            doc = fetch_trace(args.source, args.query)
        else:
            doc = load_jsonl(args.source, args.trace)
    except urllib.error.HTTPError as e:
        print(f"error: {e.code} from {args.source}: "
              f"{e.read().decode(errors='replace')[:200]}", file=sys.stderr)
        return 1 if e.code == 404 else 2
    except Exception as e:  # noqa: BLE001 - source unreadable is the signal
        print(f"error: cannot load {args.source}: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 2
    if doc is None:
        print("error: trace not found", file=sys.stderr)
        return 1
    if "_ids" in doc:
        print("multiple traces in file; pick one with --trace:")
        for tid in doc["_ids"]:
            print(f"  {tid}")
        return 1
    print(render_waterfall(doc, width=args.width))
    return 0


if __name__ == "__main__":
    sys.exit(main())
