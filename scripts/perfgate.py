#!/usr/bin/env python
"""perfgate: the offline perf-regression gate over committed BENCH
artifacts. Run before sending a PR (fourth gate in lint_all.sh).

The same comparator the in-engine sentinel runs per query completion
(exec/perfgate.py: median + MAD noise bands) applied to the repo's
benchmark trajectory: every ``BENCH_r*.json`` is one sample of the
engine's headline metrics, ``PERF_BASELINE.json`` is the committed
sample history, and the NEWEST artifact is the candidate under gate.
A candidate whose rows/s dropped, wall grew, or staged bytes re-widened
beyond the per-metric noise band exits 1 -- the perf trajectory is no
longer only inspected by humans.

Deterministic by construction: the comparator reads no clocks and no
env, artifacts and baseline are explicit inputs, and ``--json`` output
is sorted -- two runs over identical artifacts are byte-identical
(tests pin this). Exit contract shared with tpulint/kernaudit:

  0  candidate inside every noise band
  1  regression finding(s)
  2  internal error (unreadable artifact/baseline, no artifacts)

Typical invocations::

    python scripts/perfgate.py                    # committed artifacts
    python scripts/perfgate.py --json             # machine-readable
    python scripts/perfgate.py --all              # gate every artifact
    python scripts/perfgate.py --update-baseline  # absorb the history
    python scripts/perfgate.py BENCH_r05.json my_run.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

from presto_tpu.exec.perfgate import (BENCH_SPECS,  # noqa: E402
                                      compare_metrics)

JSON_SCHEMA_VERSION = 1
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(REPO_ROOT, "PERF_BASELINE.json")


def default_artifacts() -> List[str]:
    """The committed BENCH + LOADGEN trajectories, round order (lexical
    == round order for the zero-padded *_r0N names; loadgen artifacts
    carry the throughput-tier qps/p99_ms metrics under their own
    key)."""
    return (sorted(glob.glob(os.path.join(REPO_ROOT, "BENCH_r*.json")))
            + sorted(glob.glob(os.path.join(REPO_ROOT,
                                            "LOADGEN_r*.json"))))


def _platform(detail: dict) -> str:
    """First token of detail.platform: an annotated platform string and
    a clean 'tpu' run must not share a baseline key."""
    return str(detail.get("platform", "unknown")).split()[0] or "unknown"


def load_artifact(path: str) -> Tuple[str, Dict[str, float], dict]:
    """One BENCH artifact -> (baseline key, metric vector, meta).
    Accepts both the driver wrapper ({"parsed": {...}}) and a raw
    bench.py output line saved as JSON. Raises ValueError on documents
    that are neither (the exit-2 path)."""
    with open(path) as f:
        doc = json.load(f)
    parsed = doc.get("parsed") if isinstance(doc, dict) else None
    if not isinstance(parsed, dict) or "metric" not in parsed:
        parsed = doc if isinstance(doc, dict) and "metric" in doc else None
    if parsed is None:
        raise ValueError(f"{path}: not a BENCH artifact "
                         f"(no 'metric'/'parsed.metric' key)")
    detail = parsed.get("detail") or {}
    key = f"{parsed['metric']}|{_platform(detail)}"
    metrics: Dict[str, float] = {}
    if isinstance(parsed.get("value"), (int, float)):
        metrics["rows_per_sec"] = float(parsed["value"])
    for name in ("query_wall_s", "staged_mb", "qps", "p99_ms",
                 "staging_gb_per_s", "peak_memory_mb"):
        v = detail.get(name)
        if isinstance(v, (int, float)):
            metrics[name] = float(v)
    meta = detail.get("meta") or {}
    return key, metrics, meta


def load_baseline(path: str) -> dict:
    """PERF_BASELINE.json -> {key: {sources: [...], samples: {metric:
    [...]}}} under "entries". An absent file is an empty baseline
    (first --update-baseline creates it); a malformed one raises for
    the exit-2 path."""
    if not os.path.exists(path):
        return {"version": JSON_SCHEMA_VERSION, "entries": {}}
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or \
            doc.get("version") != JSON_SCHEMA_VERSION or \
            not isinstance(doc.get("entries"), dict) or \
            not all(isinstance(e, dict) and
                    isinstance(e.get("samples"), dict)
                    for e in doc["entries"].values()):
        raise ValueError(f"{path}: bad baseline document "
                         f"(want version {JSON_SCHEMA_VERSION} + "
                         f"entries of {{sources, samples}})")
    return doc


def build_baseline(artifacts: List[Tuple[str, str, Dict[str, float]]],
                   timestamp: Optional[str] = None) -> dict:
    """Rebuild the baseline from artifact samples, given order
    preserved per key. Each entry records which artifact contributed
    each sample PER METRIC (``sources[m]`` parallel to ``samples[m]``
    -- per metric, not per entry, because artifacts can lack a metric:
    BENCH_r01 predates staged_mb), so the gate can exclude a
    candidate's OWN sample before comparing -- a baseline that
    contains the candidate would otherwise drag the median toward a
    sustained regression and under-detect it. The timestamp is PASSED
    IN (--timestamp / the caller's clock) -- nothing in the gate reads
    one, which is what keeps same-input runs byte-identical."""
    entries: Dict[str, dict] = {}
    for name, key, metrics in artifacts:
        per = entries.setdefault(key, {"sources": {}, "samples": {}})
        for m, v in metrics.items():
            per["samples"].setdefault(m, []).append(v)
            per["sources"].setdefault(m, []).append(name)
    doc = {"version": JSON_SCHEMA_VERSION, "entries": entries}
    if timestamp:
        doc["updated"] = timestamp
    return doc


def baseline_samples_for(entry: dict, candidate: str
                         ) -> Dict[str, List[float]]:
    """The entry's per-metric samples with the candidate artifact's own
    contribution LEFT OUT (matched by name through each metric's
    parallel sources list). An artifact absent from a metric's sources
    -- the normal fresh-run case -- gets that metric's full sample
    set."""
    sources = entry.get("sources") or {}
    samples = entry.get("samples") or {}
    out: Dict[str, List[float]] = {}
    for m, vals in samples.items():
        vals = list(vals)
        names = sources.get(m) if isinstance(sources, dict) else None
        if names and candidate in names and len(names) == len(vals):
            vals.pop(names.index(candidate))
        out[m] = vals
    return out


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="perfgate",
        description="offline perf-regression gate over BENCH artifacts "
                    "(median + MAD noise bands vs PERF_BASELINE.json)")
    p.add_argument("artifacts", nargs="*",
                   help="BENCH artifact paths, oldest..newest (default: "
                        "the repo's committed BENCH_r*.json)")
    p.add_argument("--baseline", metavar="PATH", default=DEFAULT_BASELINE,
                   help="baseline file (default PERF_BASELINE.json)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="machine-readable output (schema-versioned, "
                        "byte-identical for identical inputs)")
    p.add_argument("--all", action="store_true",
                   help="gate EVERY artifact against the baseline, not "
                        "just the newest")
    p.add_argument("--update-baseline", action="store_true",
                   help="rewrite the baseline from the given artifacts "
                        "(then verify the newest against it)")
    p.add_argument("--timestamp", default=None,
                   help="stamp --update-baseline with this caller-"
                        "supplied time (the gate itself reads no clock)")
    args = p.parse_args(argv)

    # explicit paths keep the CALLER's oldest..newest order (the last
    # one is the candidate under gate); only the default glob sorts,
    # where the zero-padded BENCH_r0N names make lexical == round order
    paths = args.artifacts or default_artifacts()
    if not paths:
        print("perfgate: no BENCH artifacts found", file=sys.stderr)
        return 2
    try:
        loaded = [(os.path.basename(path), *load_artifact(path)[:2])
                  for path in paths]
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"perfgate: {type(e).__name__}: {e}", file=sys.stderr)
        return 2

    if args.update_baseline:
        doc = build_baseline(loaded, timestamp=args.timestamp)
        try:
            with open(args.baseline, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
                f.write("\n")
        except OSError as e:
            print(f"perfgate: cannot write baseline: {e}", file=sys.stderr)
            return 2
        baseline = doc
    else:
        try:
            baseline = load_baseline(args.baseline)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"perfgate: bad baseline: {e}", file=sys.stderr)
            return 2

    entries = baseline["entries"]
    if args.all:
        candidates = loaded
    elif args.artifacts:
        # explicit paths: the caller's LAST argument is the candidate
        candidates = loaded[-1:]
    else:
        # default glob: the newest artifact of EACH key gates, so the
        # BENCH trajectory and the LOADGEN throughput tier are both
        # checked in one run (one family cannot shadow the other)
        newest: Dict[str, Tuple[str, str, Dict[str, float]]] = {}
        for item in loaded:
            newest[item[1]] = item
        candidates = [item for item in loaded
                      if newest[item[1]] is item]
    findings: List[dict] = []
    unbaselined: List[str] = []
    checked = 0
    for name, key, metrics in candidates:
        entry = entries.get(key)
        if not entry:
            # a new metric/platform starts collecting history; it
            # cannot regress against nothing (reported, not failed)
            unbaselined.append(key)
            continue
        samples = baseline_samples_for(entry, name)
        checked += len([s for s in BENCH_SPECS if s.name in metrics])
        for verdict in compare_metrics(metrics, samples, BENCH_SPECS):
            findings.append({"artifact": name, "key": key, **verdict})

    if args.as_json:
        print(json.dumps({
            "version": JSON_SCHEMA_VERSION,
            "artifacts": [name for name, _, _ in loaded],
            "candidates": [name for name, _, _ in candidates],
            "baseline": os.path.basename(args.baseline),
            "metricsChecked": checked,
            "findings": findings,
            "unbaselined": sorted(unbaselined),
        }, indent=2, sort_keys=True))
    else:
        for f_ in findings:
            print(f"{f_['artifact']}: {f_['key']} {f_['metric']} "
                  f"{f_['direction']} band: {f_['value']:g} vs median "
                  f"{f_['median']:g} (band {f_['band']:g}, "
                  f"{f_['samples']} samples, ratio {f_['ratio']:g})")
        for key in sorted(unbaselined):
            print(f"note: {key} has no baseline entry "
                  f"(run --update-baseline to start its history)")
        verdict = "FAIL" if findings else "ok"
        print(f"{verdict} {len(findings)} regression(s) across "
              f"{len(candidates)} candidate artifact(s), "
              f"{checked} metric(s) checked "
              f"[{','.join(s.name for s in BENCH_SPECS)}]")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
