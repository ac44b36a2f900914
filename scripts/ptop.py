#!/usr/bin/env python
"""ptop: a polling terminal dashboard over ``GET /v1/cluster``.

The live counterpart of scripts/scrape_metrics.py: point it at a
statement tier and it renders, once per interval,

  * a cluster header -- uptime, workers alive/configured, queued/
    running/blocked query counts, live tasks, aggregate rows/s, stuck
    firings;
  * one progress bar per in-flight query (state, stage, rows, percent,
    last-advance age -- the bar stalls visibly when progress does);
  * one row per worker (state, running tasks, memory occupancy,
    uptime).

  python scripts/ptop.py http://127.0.0.1:8080             # live loop
  python scripts/ptop.py URL --interval 1
  python scripts/ptop.py URL --once                        # one frame
  python scripts/ptop.py URL --once --json                 # tests/CI

``--once --json`` prints the raw cluster document (plus a ``fetchedAt``
stamp) and exits 0 -- the machine-readable mode the test suite golden-
shapes. Exit codes: 0 ok, 2 endpoint unreachable.
"""

import argparse
import json
import os
import sys
import time
import urllib.request

# repo root importable regardless of invocation directory
sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def fetch_cluster(url: str, timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(f"{url.rstrip('/')}/v1/cluster",
                                timeout=timeout) as r:
        return json.loads(r.read().decode())


def _bar(pct: float, width: int = 24) -> str:
    filled = int(round(min(max(pct, 0.0), 100.0) / 100.0 * width))
    return "[" + "#" * filled + " " * (width - filled) + "]"


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024.0
    return f"{n:.1f}TB"


def render(doc: dict) -> str:
    """One dashboard frame as text (pure function of the document, so
    tests can golden it without a terminal)."""
    lines = []
    q = doc.get("queries", {})
    fleet = ""
    if doc.get("workersDraining"):
        fleet += f" ({doc['workersDraining']} draining)"
    if doc.get("workersDead"):
        fleet += f" ({doc['workersDead']} DEAD)"
    lines.append(
        f"presto-tpu cluster  up {doc.get('uptimeSeconds', 0):.0f}s  "
        f"workers {doc.get('workersAlive', 0)}/"
        f"{doc.get('workersConfigured', 0)}{fleet}  "
        f"queries q:{q.get('queued', 0)} r:{q.get('running', 0)} "
        f"b:{q.get('blocked', 0)}  "
        f"done {q.get('finishedTotal', 0)}+{q.get('failedTotal', 0)}f  "
        f"tasks {doc.get('liveTasks', 0)}  "
        f"{doc.get('rowsPerSecond', 0):.0f} rows/s  "
        f"stuck {doc.get('stuckQueriesTotal', 0)}")
    # cluster staging rate (the data-path waterfall's device_put hop:
    # host->HBM GB/s) + the bottleneck hop when ceilings were probed
    dp = doc.get("datapath") or {}
    if dp:
        bn = dp.get("bottleneck")
        lines.append(
            f"staging {dp.get('stagingGbPerS', 0.0):.3f} GB/s"
            + (f"  bottleneck {bn}" if bn else ""))
    # estimate-accuracy roll-up (exec/accuracy.py): how many nodes were
    # scored, how many missed the band, and the worst offender so far
    acc = doc.get("accuracy") or {}
    if acc:
        worst = acc.get("worstNode")
        lines.append(
            f"accuracy {acc.get('records', 0)} records  "
            f"misest {acc.get('misestimates', 0)}  "
            f"worst q {acc.get('worstQError', 0.0):.2f}x"
            + (f" ({worst})" if worst else ""))
    lines.append("-" * 78)
    running = doc.get("runningQueries", [])
    if not running:
        lines.append("(no queries in flight)")
    for rq in running:
        prog = rq.get("progress") or {}
        pct = float(prog.get("progressPercent", 0.0))
        age = prog.get("lastAdvanceAgeMs")
        age_s = f" adv {age / 1000.0:.1f}s ago" if age is not None \
            else ""
        # straggler-mitigation provenance: speculative copies racing
        # their originals show beside the bar
        spec = prog.get("speculativeTasks", 0)
        spec_s = f" spec:{spec}" if spec else ""
        # achieved GB/s: the query's cumulative processed bytes over
        # its TOTAL elapsed wall (queue + compile included) -- a
        # processed-bytes throughput, coarser than the per-hop rates
        # /v1/datapath serves, but live per query
        gbps = float(prog.get("bytes", 0)) / \
            max(float(rq.get("elapsedMs", 0)) / 1000.0, 1e-3) / 1e9
        # worst q-error of THIS query (filled at finalize, so running
        # queries show "-" until their accuracy ledger lands)
        mq = rq.get("maxQError")
        mq_s = f"{float(mq):5.1f}x" if mq is not None else "     -"
        lines.append(
            f"{rq.get('queryId', '?'):<26} {rq.get('state', '?'):<9} "
            f"{_bar(pct)} {pct:5.1f}%  "
            f"{prog.get('stage', '-'):<8} "
            f"rows {int(prog.get('rows', 0)):>10,} "
            f"{gbps:6.3f}GB/s q{mq_s}{age_s}{spec_s}")
        lines.append(f"  {rq.get('query', '')[:74]}")
    lines.append("-" * 78)
    # resource-group rows (latency-class admission): per-group queue
    # depth beside the batching executor's dispatch amortization
    groups = doc.get("resourceGroups") or {}
    for name in sorted(groups):
        g = groups[name]
        lines.append(
            f"group {name:<20} r:{g.get('running', 0):>3}"
            f"/{g.get('hardConcurrencyLimit', 0):<3} "
            f"q:{g.get('queued', 0):>4}/{g.get('maxQueued', 0):<4} "
            f"w:{g.get('schedulingWeight', 1):<2} "
            f"prio:{g.get('priority', 0)}")
    batching = doc.get("batching") or {}
    if batching:
        lines.append(
            f"batching: {batching.get('queriesBatched', 0)} queries / "
            f"{batching.get('batchesDispatched', 0)} dispatches "
            f"(occ last {batching.get('lastBatchSize', 0)} "
            f"avg {batching.get('avgOccupancy', 0.0):.1f} "
            f"max {batching.get('maxBatchSize', 0)})  "
            f"solo {batching.get('soloDispatches', 0)}  "
            f"collapses {sum((batching.get('collapses') or {}).values())}")
        lines.append("-" * 78)
    workers = doc.get("workers", [])
    if not workers:
        lines.append("(no workers configured: embedded engine)")
    for w in workers:
        mem = w.get("memory", {})
        # the elastic fleet state machine (ACTIVE | DRAINING | DRAINED
        # | DEAD), falling back to the legacy flat state for old nodes
        state = w.get("fleetState", w.get("state", "?"))
        lines.append(
            f"{w.get('nodeId', w.get('uri', '?')):<26} "
            f"{state:<13} "
            f"tasks {w.get('runningTasks', w.get('activeTasks', 0)):>3} "
            f" mem {_fmt_bytes(mem.get('reservedBytes', 0))}/"
            f"{_fmt_bytes(mem.get('capacityBytes', 0))} "
            f"(peak {_fmt_bytes(mem.get('peakBytes', 0))})  "
            f"up {w.get('uptimeSeconds', 0):.0f}s")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="ptop")
    ap.add_argument("url", help="statement-tier base URL")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="seconds between frames (default 2)")
    ap.add_argument("--once", action="store_true",
                    help="render one frame and exit")
    ap.add_argument("--json", action="store_true",
                    help="with --once: print the raw cluster document "
                         "as JSON (the machine-readable mode)")
    args = ap.parse_args(argv)

    while True:
        try:
            doc = fetch_cluster(args.url)
        except Exception as e:  # noqa: BLE001 - endpoint down IS the news
            print(f"error: cannot fetch {args.url}/v1/cluster: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            return 2
        if args.once and args.json:
            print(json.dumps({"fetchedAt": time.time(), **doc},
                             indent=1, sort_keys=True))
            return 0
        if not args.once:
            # ANSI clear + home: a cheap full-frame repaint
            sys.stdout.write("\x1b[2J\x1b[H")
        print(render(doc))
        if args.once:
            return 0
        sys.stdout.flush()
        time.sleep(args.interval)


if __name__ == "__main__":
    sys.exit(main())
