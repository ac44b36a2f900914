#!/usr/bin/env python
"""Scrape /v1/metrics and diff counters between two scrapes.

The ops loop the metrics endpoint exists for, in script form: point it
at a coordinator or worker, and it reports counter DELTAS over the
interval (queries finished, rows/bytes produced, compile vs execute
seconds, cache hits), current gauge values, and -- for every histogram
family -- bucket-estimated p50/p95/p99 of the observations that landed
WITHIN the window, the numbers a before/after perf comparison cites.

Counter DECREASES between the two scrapes are monotonicity violations
(a restarted process, or a counter bug) and are flagged in their own
``violations`` section instead of silently diffing negative.

  python scripts/scrape_metrics.py http://127.0.0.1:8080 [--interval 5]
  python scripts/scrape_metrics.py URL --once          # one scrape, dump
  python scripts/scrape_metrics.py URL --count 3       # N diff windows

Exit codes: 0 on success, 2 when the endpoint is unreachable.
"""

import argparse
import json
import os
import re
import sys
import time
import urllib.request

# repo root importable regardless of invocation directory
sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

from presto_tpu.server.metrics import (parse_prometheus,  # noqa: E402
                                       quantile_from_buckets)


def scrape(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(f"{url.rstrip('/')}/v1/metrics",
                                timeout=timeout) as r:
        return parse_prometheus(r.read().decode())


# tracer + flight-recorder health: reported as their own diff section,
# zeros INCLUDED -- "no spans recorded" and "no dumps written" are
# answers an operator pulling a trace needs to see, not absence of news
TRACING_FAMILIES = (
    "presto_tpu_trace_spans_total",
    "presto_tpu_traces_evicted_total",
    "presto_tpu_trace_spans_dropped_total",
    "presto_tpu_flight_recorder_events_total",
    "presto_tpu_flight_recorder_dumps_total",
)

# fault-injection accounting (presto_tpu/failpoints): its own section,
# zeros included -- during a chaos run "which faults fired in this
# window" is the first question, and "none" is an answer too
FAULT_FAMILY_PREFIX = "presto_tpu_failpoint"

# query-history archive + perf sentinel (server/history.py): its own
# always-present section, zeros included -- "no regressions this
# window" is the answer a deploy watch wants stated, not implied
HISTORY_FAMILIES = (
    "presto_tpu_query_history_entries",
    "presto_tpu_query_history_records_total",
    "presto_tpu_perf_regressions_total",
)

# live-cluster introspection (exec/progress.py + server/watchdog.py):
# an always-present gauge snapshot -- in-flight tasks, alive workers,
# stuck-progress firings -- so "is anything running / wedged RIGHT
# NOW" reads off the same diff as the retrospective sections
CLUSTER_FAMILIES = (
    "presto_tpu_running_tasks",
    "presto_tpu_cluster_workers_alive",
    "presto_tpu_stuck_queries_total",
)

# elastic fleet (server/discovery.py + coordinator speculation +
# resource_manager failover): its own always-present section, zeros
# included -- during a deploy/drain "how many workers joined/left/are
# draining, did speculation fire, did a coordinator fail over" is the
# first question, and "nothing moved" is an answer too
FLEET_FAMILIES = (
    "presto_tpu_fleet_workers_joined_total",
    "presto_tpu_fleet_workers_left_total",
    "presto_tpu_fleet_workers_draining",
    "presto_tpu_announce_retries_total",
    "presto_tpu_speculation_launched_total",
    "presto_tpu_speculation_wins_total",
    "presto_tpu_speculation_losses_total",
    "presto_tpu_coordinator_failovers_total",
)


# lock-order witness (utils/locks.py): its own always-present section,
# zeros included -- "0 inversions while ARMED" is the health statement
# the concurrency audit exists to make, and "0 while disarmed" must
# read differently (nobody was watching)
LOCK_FAMILIES = (
    "presto_tpu_lock_order_violations_total",
    "presto_tpu_lock_witness_armed",
)

# data-path waterfall (exec/datapath.py): its own always-present
# section, zeros included -- per-hop byte/second deltas (their ratio
# is the window's achieved B/s per hop) plus the size histogram's
# bucket-delta p50/p99. "No bytes moved on a hop this window" is an
# answer a staging-rate investigation needs stated, not implied.
DATAPATH_FAMILY_PREFIX = "presto_tpu_datapath"

# estimate-accuracy observatory (exec/accuracy.py): its own
# always-present section, zeros included -- record/misestimate counter
# deltas, the worst-q-error gauge, and the q-error histogram's
# bucket-delta p50/p95/p99. "No misestimates this window" is an answer
# an estimate-drift investigation needs stated, not implied.
ACCURACY_FAMILY_PREFIX = "presto_tpu_accuracy"
ACCURACY_FAMILIES = (
    "presto_tpu_misestimates_total",
    "presto_tpu_worst_q_error",
)
Q_ERROR_HISTOGRAM = "presto_tpu_q_error"


# proven-safe buffer donation (exec/donation.py): its own
# always-present section, zeros included -- donated dispatches, HBM
# bytes aliased in place, and donation-path fallbacks. "Donation never
# fired this window" is an answer an HBM-headroom investigation needs
# stated, not implied.
DONATION_FAMILIES = (
    "presto_tpu_donations_total",
    "presto_tpu_donated_bytes_total",
    "presto_tpu_donation_fallbacks_total",
)


_LE_RE = re.compile(r'le="([^"]+)"')


def _histogram_window(before: dict, after: dict, fam: str) -> dict:
    """Per label-set window stats of one histogram family: delta
    counts per bucket between the scrapes -> estimated p50/p95/p99 of
    the interval's observations (quantile_from_buckets, the same
    arithmetic the server-side Histogram uses)."""
    out = {}
    groups = {}
    for key, val in after.get(fam + "_bucket", {}).items():
        m = _LE_RE.search(key)
        if not m:
            continue
        series = _LE_RE.sub("", key).replace(",,", ",").replace(
            "{,", "{").replace(",}", "}")
        le = m.group(1)
        prev = before.get(fam + "_bucket", {}).get(key, 0.0)
        groups.setdefault(series, []).append(
            (float("inf") if le == "+Inf" else float(le), val - prev))
    for series, buckets in groups.items():
        buckets.sort(key=lambda x: x[0])
        bounds = [b for b, _ in buckets if b != float("inf")]
        # cumulative deltas -> per-bucket deltas (clamped: a restarted
        # process yields negatives, reported as count_delta < 0)
        cums = [c for _, c in buckets]
        per = [cums[0]] + [cums[i] - cums[i - 1]
                           for i in range(1, len(cums))]
        count = cums[-1] if cums else 0.0
        doc = {"count_delta": round(count, 6)}
        if count > 0 and bounds:
            clamped = [max(c, 0.0) for c in per]
            for q, name in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
                doc[name] = round(
                    quantile_from_buckets(bounds, clamped, q), 6)
        out[series if series != "{}" else ""] = doc
    return out


def diff(before: dict, after: dict) -> dict:
    """Counter deltas + gauge currents between two parsed scrapes,
    histogram window quantiles, counter-monotonicity violations, plus
    the always-present tracing/flight-recorder section."""
    out = {"counters": {}, "gauges": {}, "tracing": {}, "faults": {},
           "history": {}, "cluster": {}, "fleet": {}, "locks": {},
           "datapath": {}, "accuracy": {}, "donation": {},
           "histograms": {}, "violations": {}}
    hist_bases = set()
    for fam, samples in after.items():
        if fam.endswith("_bucket"):
            hist_bases.add(fam[: -len("_bucket")])
            continue
        base = fam.rsplit("_", 1)[0]
        if fam.endswith(("_sum", "_count")) and \
                (base + "_bucket") in after:
            continue  # folded into the histogram section
        is_counter = fam.endswith("_total")
        is_fault = fam.startswith(FAULT_FAMILY_PREFIX)
        is_datapath = fam.startswith(DATAPATH_FAMILY_PREFIX)
        is_accuracy = fam.startswith(ACCURACY_FAMILY_PREFIX) \
            or fam in ACCURACY_FAMILIES
        is_history = fam in HISTORY_FAMILIES
        is_cluster = fam in CLUSTER_FAMILIES
        is_fleet = fam in FLEET_FAMILIES
        is_locks = fam in LOCK_FAMILIES
        is_donation = fam in DONATION_FAMILIES
        for key, val in samples.items():
            label = fam + key
            if is_counter:
                prev = before.get(fam, {}).get(key, 0.0)
                delta = val - prev
                if delta < 0:
                    # a counter went DOWN: that is a restart or a bug,
                    # not a negative rate -- flag it, don't diff it
                    out["violations"][label] = round(delta, 6)
                    continue
                if is_fault:
                    out["faults"][label] = round(delta, 6)
                elif is_datapath:
                    # per-hop byte/second deltas, zeros included: the
                    # window's bytes/seconds ratio is the achieved B/s
                    out["datapath"][label] = round(delta, 6)
                elif is_accuracy:
                    # record + misestimate deltas, zeros included
                    out["accuracy"][label] = round(delta, 6)
                elif is_history:
                    out["history"][label] = round(delta, 6)
                elif is_fleet:
                    # membership churn / speculation / failover deltas,
                    # zeros included
                    out["fleet"][label] = round(delta, 6)
                elif is_cluster:
                    # stuck-firing delta rides the cluster section
                    out["cluster"][label] = round(delta, 6)
                elif is_locks:
                    # inversion delta, zero included: "0 new
                    # inversions" is the statement, not silence
                    out["locks"][label] = round(delta, 6)
                elif is_donation:
                    # donated dispatches / bytes / fallback deltas,
                    # zeros included
                    out["donation"][label] = round(delta, 6)
                elif fam in TRACING_FAMILIES:
                    out["tracing"][label] = round(delta, 6)
                elif delta:
                    out["counters"][label] = round(delta, 6)
            elif is_fault:
                # the armed gauge rides the faults section too: "3
                # faults fired, 2 still armed" reads off one block
                out["faults"][label] = round(val, 6)
            elif is_accuracy:
                # the worst-q-error gauge rides beside the misestimate
                # deltas: "0 new misestimates, worst ever 47x" reads
                # off one block
                out["accuracy"][label] = round(val, 6)
            elif is_history:
                # the archive-size gauge rides the history section:
                # "N records retained, 0 regressions" reads off one block
                out["history"][label] = round(val, 6)
            elif is_fleet:
                # the draining gauge rides the fleet section: "2 left,
                # 1 still draining" reads off one block
                out["fleet"][label] = round(val, 6)
            elif is_cluster:
                # current gauge values: "what is in flight NOW" reads
                # off one block beside the stuck delta
                out["cluster"][label] = round(val, 6)
            elif is_locks:
                # the armed gauge rides beside the inversion delta so
                # the zero is qualified: watched, or unwatched
                out["locks"][label] = round(val, 6)
            else:
                out["gauges"][label] = round(val, 6)
    for base in sorted(hist_bases):
        win = _histogram_window(before, after, base)
        if not win:
            continue
        if base.startswith(DATAPATH_FAMILY_PREFIX):
            # the size histogram's bucket-delta quantiles ride the
            # datapath section beside the byte deltas (zeros included)
            out["datapath"][base] = win
        elif base == Q_ERROR_HISTOGRAM:
            # the q-error ladder's bucket-delta quantiles ride the
            # accuracy section beside the misestimate deltas
            out["accuracy"][base] = win
        else:
            out["histograms"][base] = win
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="scrape_metrics")
    ap.add_argument("url", help="coordinator or worker base URL")
    ap.add_argument("--interval", type=float, default=5.0,
                    help="seconds between the two scrapes (default 5)")
    ap.add_argument("--count", type=int, default=1,
                    help="number of diff windows to report")
    ap.add_argument("--once", action="store_true",
                    help="single scrape: dump all families, no diff")
    args = ap.parse_args(argv)

    try:
        before = scrape(args.url)
    except Exception as e:  # noqa: BLE001 - endpoint down is the signal
        print(f"error: cannot scrape {args.url}/v1/metrics: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        return 2
    if args.once:
        print(json.dumps(before, indent=1, sort_keys=True))
        return 0
    for _ in range(args.count):
        time.sleep(args.interval)
        try:
            after = scrape(args.url)
        except Exception as e:  # noqa: BLE001
            print(f"error: scrape lost: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return 2
        print(json.dumps({"intervalSeconds": args.interval,
                          **diff(before, after)}, sort_keys=True))
        before = after
    return 0


if __name__ == "__main__":
    sys.exit(main())
