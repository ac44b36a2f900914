"""Command-line SQL client: the presto-cli analog.

Reference surface: presto-cli (Console.java REPL driving the REST
protocol). Round 1 runs queries in-process against the embedded engine;
`--server` mode speaks the worker HTTP protocol instead (submit plan
JSON, pull SerializedPages) once a coordinator fronts it.

  python -m presto_tpu.cli "SELECT ... FROM lineitem ..." [--sf 0.01]
  python -m presto_tpu.cli              # REPL
"""

from __future__ import annotations

import argparse
import re
import sys
import time


def _render(v, ty):
    if v is None:
        return "NULL"
    if ty is not None and ty.is_decimal and ty.scale > 0:
        s = ty.scale
        sign = "-" if v < 0 else ""
        a = abs(int(v))
        return f"{sign}{a // 10**s}.{a % 10**s:0{s}d}"
    if ty is not None and ty.base == "date":
        import numpy as np
        return str(np.datetime64("1970-01-01") + int(v))
    return str(v)


def _format_table(names, rows, types=None, max_rows=50):
    types = types or [None] * len(names)
    rendered = [[_render(r[i], types[i]) for i in range(len(names))]
                for r in rows[:max_rows]]
    widths = [max([len(str(n))] + [len(rr[i]) for rr in rendered])
              for i, n in enumerate(names)]

    def line(vals):
        return " | ".join(v.ljust(w) for v, w in zip(vals, widths))

    out = [line([str(n) for n in names]),
           "-+-".join("-" * w for w in widths)]
    for rr in rendered:
        out.append(line(rr))
    if len(rows) > max_rows:
        out.append(f"... ({len(rows) - max_rows} more rows)")
    return "\n".join(out)


def _print_trace(doc) -> None:
    from presto_tpu.traceview import render_waterfall
    print(render_waterfall(doc))


def run_one(query: str, sf: float, explain_only: bool = False,
            stats: bool = False, trace: bool = False) -> int:
    from presto_tpu.plan import explain as explain_plan
    from presto_tpu.sql import plan_sql, sql

    import re
    m = re.match(r"\s*explain(\s+analyze)?\b", query, re.IGNORECASE)
    if m and m.group(1):
        from presto_tpu.plan import explain_analyze
        print(explain_analyze(plan_sql(query[m.end():].strip()), sf=sf))
        return 0
    if explain_only or m:
        q = query[m.end():].strip() if m else query
        print(explain_plan(plan_sql(q)))
        return 0
    t0 = time.time()
    import uuid
    kwargs = {"query_id": f"cli_{uuid.uuid4().hex[:8]}"}
    if stats:
        # --stats pays the one extra trace for FLOPs/bytes-accessed
        kwargs["session"] = {"query_cost_analysis": True}
    if trace:
        # embedded engine: make sure a tracer exists so the stage spans
        # land somewhere renderable
        from presto_tpu.server.tracing import (RecordingTracer,
                                               get_tracer, set_tracer)
        if get_tracer() is None:
            set_tracer(RecordingTracer())
    res = sql(query, sf=sf, **kwargs)
    dt = time.time() - t0
    print(_format_table(res.names, res.rows(), res.types))
    print(f"({res.row_count} rows in {dt:.2f}s)")
    if stats and res.query_stats is not None:
        print(f"stats: {res.query_stats.summary()}")
    if trace:
        from presto_tpu.server.tracing import get_tracer, trace_doc_of
        doc = trace_doc_of(get_tracer(), kwargs["query_id"])
        if doc is None:
            print("(no spans recorded for this query)")
        else:
            _print_trace(doc)
    return 0


def _watch_line(stats: dict, elapsed: float) -> str:
    """One live ticker line from a poll's enriched stats: state, stage,
    rows, percent, elapsed (the _base_doc progress enrichment)."""
    state = stats.get("state", "QUEUED")
    stage = stats.get("stage", "-")
    rows = int(stats.get("processedRows", 0))
    pct = float(stats.get("progressPercent", 0.0))
    return (f"{state:>9s} | {stage:<8s} | rows {rows:>12,} | "
            f"{pct:5.1f}% | {elapsed:6.1f}s")


def run_one_remote(query: str, server: str, user: str = "presto",
                   session=None, stats: bool = False,
                   trace: bool = False, watch: bool = False) -> int:
    """Run one statement over the client statement protocol (the
    presto-cli-to-coordinator path: POST /v1/statement + nextUri).
    `watch` renders a one-line live progress ticker from the poll
    loop's enriched stats while the statement is in flight."""
    from presto_tpu.client import QueryError, StatementClient, execute

    extra_headers = None
    if trace:
        # mint a client-side trace context: the server's query root
        # span parents under it, so the served trace is the CLIENT's
        # trace id and covers the statement end to end
        from presto_tpu.server.tracing import TRACE_HEADER, TraceContext, \
            new_span_id, new_trace_id
        ctx = TraceContext(new_trace_id(), new_span_id())
        extra_headers = {TRACE_HEADER: ctx.header()}
    t0 = time.time()
    try:
        if watch:
            client = StatementClient(server, query, user=user,
                                     session=session or {},
                                     extra_headers=extra_headers)
            try:
                while True:
                    print("\r" + _watch_line(client.stats or {},
                                             time.time() - t0),
                          end="", file=sys.stderr, flush=True)
                    if not client.advance():
                        break
            finally:
                print(file=sys.stderr)  # leave the ticker line behind
            client.drain()  # no-op advance + the error-raising contract
        else:
            client = execute(server, query, user=user,
                             session=session or {},
                             extra_headers=extra_headers)
    except QueryError as e:
        print(f"error [{e.error_name}]: {e}", file=sys.stderr)
        return 1
    dt = time.time() - t0
    names = [c["name"] for c in (client.columns or [])]
    # wire values arrive pre-rendered (decimals/dates as strings)
    rows = [tuple(r) for r in client.data]
    print(_format_table(names, rows))
    extra = f", {client.update_type}" if client.update_type else ""
    print(f"({len(rows)} rows in {dt:.2f}s via {client.query_id}{extra})")
    if stats and client.stats:
        # the server populated these from its QueryStats (statement.py)
        s = client.stats
        parts = [f"wall {s.get('elapsedTimeMillis', 0) / 1e3:.3f}s"]
        if "compileTimeMicros" in s:
            parts.append(f"compile {s['compileTimeMicros'] / 1e6:.3f}s")
        if "executeTimeMicros" in s:
            parts.append(f"execute {s['executeTimeMicros'] / 1e6:.3f}s")
        parts.append(f"rows {s.get('processedRows', len(rows))}")
        parts.append(f"bytes {s.get('processedBytes', 0)}")
        if s.get("peakMemoryBytes"):
            parts.append(f"peak mem {s['peakMemoryBytes'] >> 20}MB")
        print("stats: " + ", ".join(parts))
    if trace and client.query_id:
        # pull the stitched one-trace-per-query document back from the
        # coordinator and render the waterfall
        from presto_tpu.traceview import fetch_trace
        try:
            doc = fetch_trace(server, client.query_id)
        except Exception as e:  # noqa: BLE001 - trace absence must not
            # fail a statement that already returned its rows
            print(f"(no trace for {client.query_id} from {server}: "
                  f"{type(e).__name__}: {e} -- is a tracer installed "
                  f"on the coordinator?)", file=sys.stderr)
            return 0
        _print_trace(doc)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="presto-tpu")
    ap.add_argument("query", nargs="?", help="SQL to run (omit for a REPL)")
    ap.add_argument("--sf", type=float, default=0.01,
                    help="tpch/tpcds scale factor (default 0.01)")
    ap.add_argument("--explain", action="store_true")
    ap.add_argument("--stats", action="store_true",
                    help="print the QueryStats summary (wall/compile/"
                         "execute, rows, bytes) after each query")
    ap.add_argument("--trace", action="store_true",
                    help="render the query's distributed trace as an "
                         "ASCII waterfall with critical-path "
                         "attribution (GET /v1/trace/{queryId} in "
                         "--server mode, the in-process tracer "
                         "otherwise)")
    ap.add_argument("--server", default=None,
                    help="coordinator URL; statements ride the client "
                         "protocol instead of the embedded engine")
    ap.add_argument("--watch", action="store_true",
                    help="with --server: render a one-line live "
                         "progress ticker (state, stage, rows, "
                         "percent, elapsed) from the poll loop's "
                         "enriched stats while the statement runs")
    ap.add_argument("--user", default="presto")
    args = ap.parse_args(argv)
    if not args.server:  # the embedded engine compiles in this process
        from presto_tpu.utils.compile_cache import setup_compile_cache
        setup_compile_cache()

    if args.query:
        if args.server:
            query = args.query
            if args.explain and not re.match(r"\s*explain\b", query,
                                             re.IGNORECASE):
                query = f"EXPLAIN {query}"  # server-side EXPLAIN
            return run_one_remote(query, args.server, args.user,
                                  {"sf": str(args.sf)}, stats=args.stats,
                                  trace=args.trace, watch=args.watch)
        return run_one(args.query, args.sf, args.explain, args.stats,
                       trace=args.trace)

    print("presto-tpu> (end statements with ';', \\q to quit)")
    buf = []
    while True:
        try:
            line = input("presto-tpu> " if not buf else "          > ")
        except EOFError:
            break
        if line.strip() in ("\\q", "quit", "exit"):
            break
        buf.append(line)
        if line.rstrip().endswith(";"):
            stmt = "\n".join(buf).rstrip().rstrip(";")
            buf = []
            try:
                if args.server:
                    if args.explain and not re.match(r"\s*explain\b", stmt,
                                                     re.IGNORECASE):
                        stmt = f"EXPLAIN {stmt}"
                    run_one_remote(stmt, args.server, args.user,
                                   {"sf": str(args.sf)},
                                   stats=args.stats, trace=args.trace,
                                   watch=args.watch)
                else:
                    run_one(stmt, args.sf, args.explain, args.stats,
                            trace=args.trace)
            except Exception as e:  # noqa: BLE001 - REPL reports and continues
                print(f"error: {type(e).__name__}: {e}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
