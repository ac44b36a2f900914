#!/usr/bin/env python3
"""On the chip: TPC-H Q3 over memory tables with its three capacities
set by hand, the ladder's one scale against what each node needs.

  python scripts/microbench_fitted_q3.py [--sf 10] [--out FILE.json]
      [--ladder 4194304] [--fitted 2097152,524288,131072] [--adaptive]

The benchmark's Q3 (BUILDING / 1995-03-15) is planned once; its two
joins and its group-by (pre-order 7, 6, 4: lineitem x orders, x
customer, the groups) get explicit capacities through
`plan.stats.with_capacities`, and each variant runs as planned
(``adaptive_capacity=false``: no feedback, no rerun, no refit): once to
compile (seconds reported), once under the profiler for device seconds
by scope (`traceview.device_time_by_scope`). The variants' rows must
agree. With --adaptive the statement then goes through the front door
three times with the ladder on: its reruns, refits and capacities as the
statement's counters give them. Exits 3 without a TPU: a CPU time is
no device number.
"""

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import presto_tpu  # noqa: E402,F401  (x64 on before any array exists)
import jax  # noqa: E402

from benchmarks.harness import trace_reduce, traffic  # noqa: E402
from presto_tpu.exec.runner import prepare_plan, run_query  # noqa: E402
from presto_tpu.plan.stats import (capacities, preorder,  # noqa: E402
                                   with_capacities)
from presto_tpu.sql import plan_sql, sql  # noqa: E402
from presto_tpu.traceview import device_time_by_scope  # noqa: E402
from presto_tpu.utils.compile_cache import setup_compile_cache  # noqa: E402

COUNTERS = ("capacity_reruns", "capacity_refits", "capacity_rows",
            "capacity_live_rows", "program_hbm_bytes", "xla_compiles",
            "join_probe_compacted", "join_expand_steps")


def log(*words):
    print("[fitted_q3]", *words, file=sys.stderr, flush=True)


def traced(fn, trace_dir):
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    found = device_time_by_scope(trace_reduce.newest_xplane(trace_dir))
    shutil.rmtree(trace_dir, ignore_errors=True)
    return out, found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=10.0)
    ap.add_argument("--ladder", type=int, default=4_194_304)
    ap.add_argument("--fitted", default="2097152,524288,131072")
    ap.add_argument("--adaptive", action="store_true")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse the path on the CPU: no number counts")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu" and not args.allow_cpu:
        log("needs a TPU; no CPU fallback")
        return 3
    setup_compile_cache()
    sf = args.sf
    config = traffic.read_json("configs", "tpch_sf1_memory")
    schema = {0.01: "tiny", 1.0: "sf1", 10.0: "sf10"}[sf]
    mix = traffic.read_json("traffic", "q14_q3_stream")
    q3 = traffic.template_of(mix, "q3")
    for table in q3["tables"]:
        t0 = time.time()
        sql(f"DROP TABLE IF EXISTS memory.{table}", sf=sf)
        sql(f"CREATE TABLE memory.{table} AS SELECT "
            f"{', '.join(config['columns'][table])} "
            f"FROM tpch.{schema}.{table}", sf=sf)
        log(f"loaded {table} in {time.time() - t0:.1f} s")
    text = traffic.statement_text("q3", "memory.", q3["sets"][0])
    root = prepare_plan(plan_sql(text), sf=sf)
    names = [f"{type(n).__name__}.{k}" for k, n in enumerate(preorder(root))]
    base = capacities(root, 1 << 16)
    log("capacity nodes", {names[k]: c for k, c in base.items()})
    assert sorted(base) == [4, 6, 7], base
    fitted = [int(w) for w in args.fitted.split(",")]
    variants = {"ladder": {7: args.ladder, 6: args.ladder, 4: args.ladder},
                "fitted": dict(zip((7, 6, 4), fitted))}
    report = {"sf": sf, "device": jax.devices()[0].device_kind,
              "variants": {}}
    rows = {}
    for name, caps in variants.items():
        plan = with_capacities(root, caps)

        def run():
            return run_query(plan, sf=sf, prepared=True,
                             session={"adaptive_capacity": False})
        t0 = time.time()
        first = run()
        first_s = time.time() - t0
        second, found = traced(run, os.path.join(
            ROOT, ".cache", "fitted_q3_trace"))
        rows[name] = second.rows()
        counters = second.query_stats.counters
        report["variants"][name] = {
            "capacities": {names[k]: c for k, c in caps.items()},
            "first_wall_s": first_s,
            "first_compile_s": first.stats.get("compile_s", {}).get("total"),
            "busy_s": found["busy_s"],
            "scopes": dict(sorted(found["scopes"].items(),
                                  key=lambda kv: -kv[1])[:24]),
            "counters": {k: counters.get(k) for k in COUNTERS},
            "execute_us": second.query_stats.stages["execute"].wall_us}
        log(name, json.dumps(report["variants"][name]))
    report["rows_agree"] = rows["ladder"] == rows["fitted"]
    if args.adaptive:
        report["adaptive"] = []
        for attempt in range(3):
            t0 = time.time()
            done = sql(text, sf=sf)
            counters = done.query_stats.counters
            report["adaptive"].append({
                "wall_s": time.time() - t0,
                "compile_s": done.stats.get("compile_s", {}).get("total"),
                "dispatches": done.query_stats.stages["dispatch"].invocations,
                "rows_agree": done.rows() == rows["ladder"],
                "counters": {k: counters.get(k) for k in COUNTERS}})
            log("adaptive", attempt, json.dumps(report["adaptive"][-1]))
    text = json.dumps(report, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0 if report["rows_agree"] else 1


if __name__ == "__main__":
    sys.exit(main())
