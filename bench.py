#!/usr/bin/env python
"""Benchmark: TPC-H q1 (BASELINE.md config 1) on the real chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

value        = rows/sec through the FULL SQL front door: the official
               q1 text goes parser -> analyzer/planner -> connector-NDV
               capacity refinement -> XLA lowering -> kernels (the
               engine pipeline the reference benchmarks with
               BenchmarkSuite.java:32; its HandTpchQuery1 hand-built
               variant is reported in detail.hand_built_rows_per_sec).
vs_baseline  = speedup vs a single-core numpy columnar implementation of
               the same query on this host (stand-in for the reference's
               per-worker Java operator pipeline, which publishes no
               absolute numbers -- BASELINE.md "published == {}").

The script measures the chip or nothing: it runs in the ONE process
that was started (a chip belongs to one process), and exits non-zero
when `jax.devices()[0].platform != "tpu"` instead of printing a CPU
number under a device metric's name. It runs what users run: the
engine's own choice of kernel forms, no override.

Env knobs: BENCH_SF (default 1.0), BENCH_ITERS (default 5),
BENCH_QUERY (q1 | q6).

`bench.py --full` runs q1 + q6 + the join config (BASELINE config 2:
q3, q14) + the sorted-mode large-G group-by microbench in the same one
process and prints one JSON line holding all of them.
"""

import json
import os
import time

import numpy as np

# Official TPC-H q1 (spec text, dialect-adapted to this engine's
# unprefixed tpch column names -- same adaptation documented in
# queries/tpch_queries.py).
TPCH_Q1 = """
SELECT returnflag, linestatus,
       sum(quantity) AS sum_qty,
       sum(extendedprice) AS sum_base_price,
       sum(extendedprice * (1 - discount)) AS sum_disc_price,
       sum(extendedprice * (1 - discount) * (1 + tax)) AS sum_charge,
       avg(quantity) AS avg_qty,
       avg(extendedprice) AS avg_price,
       avg(discount) AS avg_disc,
       count(*) AS count_order
FROM lineitem
WHERE shipdate <= date '1998-09-02'
GROUP BY returnflag, linestatus
ORDER BY returnflag, linestatus
"""


def _numpy_q1(cols, cutoff):
    """Single-core columnar oracle/baseline of q1."""
    m = cols["shipdate"] <= cutoff
    rf = cols["returnflag"][m]
    ls = cols["linestatus"][m]
    qty = cols["quantity"][m]
    price = cols["extendedprice"][m]
    disc = cols["discount"][m]
    tax = cols["tax"][m]
    key = np.char.add(rf.astype(str), ls.astype(str))
    uniq, inv = np.unique(key, return_inverse=True)
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    out = {}
    for i, k in enumerate(uniq):
        g = inv == i
        out[k] = (qty[g].sum(), price[g].sum(), disc_price[g].sum(),
                  charge[g].sum(), g.sum())
    return out


def _require_tpu() -> str:
    """The device gate every entry of this script passes first: no TPU,
    no number. Returns the platform for the artifact's `platform`."""
    import sys

    import jax
    from presto_tpu.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench.py: JAX found no TPU (platform {platform!r}); "
              "a CPU run is never written under a device metric's name",
              file=sys.stderr)
        sys.exit(1)
    return platform


def _bench_full():
    """`--full`: every benchmark in this one process (backend init and
    the compile cache are paid once)."""
    import contextlib
    import io

    platform = _require_tpu()
    sf = float(os.environ.get("BENCH_SF", "1"))
    iters = int(os.environ.get("BENCH_ITERS", "5"))
    results = {}

    def capture(name, fn):
        buf = io.StringIO()
        t0 = time.time()
        try:
            with contextlib.redirect_stdout(buf):
                fn()
            line = [l for l in buf.getvalue().splitlines()
                    if l.startswith("{")][-1]
            results[name] = json.loads(line)
        except Exception as e:  # noqa: BLE001 -- evidence for every bench
            results[name] = {"error": f"{type(e).__name__}: {e}"[:500]}
        results[name]["bench_wall_s"] = round(time.time() - t0, 1)

    os.environ["BENCH_QUERY"] = "q1"
    capture("q1", main)
    capture("q6", lambda: _bench_q6(sf, iters, platform))
    # no capacity hints: the connector-NDV refinement pass sizes group
    # tables and join capacities (the stats-driven path the round-3
    # verdict asked to stand on its own)
    capture("q3", lambda: _bench_sql_join("q3", TPCH_Q3, sf, platform))
    capture("q14", lambda: _bench_sql_join("q14", TPCH_Q14, sf, platform))
    capture("groupby_large_g", lambda: _bench_large_g(platform, iters))
    value = results.get("q1", {}).get("value", 0)
    vsb = results.get("q1", {}).get("vs_baseline", 0)
    print(json.dumps({
        "metric": "full_suite", "value": value, "unit": "rows/s",
        "vs_baseline": vsb,
        "detail": {"platform": platform,
                   "sf": sf,
                   "captured_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                                 time.gmtime()),
                   "benchmarks": results}}))


# Official TPC-H q3/q14 (BASELINE config 2), dialect-adapted like
# queries/tpch_queries.py (unprefixed generator columns via aliases).
TPCH_Q3 = """
SELECT l.orderkey, sum(l.extendedprice * (1 - l.discount)) AS revenue,
       o.orderdate, o.shippriority
FROM customer c
JOIN orders o ON c.custkey = o.custkey
JOIN lineitem l ON l.orderkey = o.orderkey
WHERE c.mktsegment = 'BUILDING'
  AND o.orderdate < date '1995-03-15' AND l.shipdate > date '1995-03-15'
GROUP BY l.orderkey, o.orderdate, o.shippriority
ORDER BY revenue DESC, o.orderdate
LIMIT 10
"""

TPCH_Q14 = """
SELECT 100.00 * sum(CASE WHEN p.type LIKE 'PROMO%'
                    THEN l.extendedprice * (1 - l.discount)
                    ELSE 0 END)
       / sum(l.extendedprice * (1 - l.discount)) AS promo_revenue
FROM lineitem l JOIN part p ON l.partkey = p.partkey
WHERE l.shipdate >= date '1995-09-01' AND l.shipdate < date '1995-10-01'
"""


def _bench_meta(platform):
    """Measurement-environment provenance recorded in every artifact:
    jax version, platform, the BENCH_SEED that pins data generation,
    and a run timestamp PASSED IN via BENCH_RUN_TS (the caller's clock
    -- scripts/perfgate.py must stay a pure function of its inputs, so
    nothing downstream reads one). The gate keys baselines on
    (metric, platform); the rest is for a human triaging WHY a sample
    moved (jax upgrade, reseeded data), not part of the key."""
    import jax
    return {"jax_version": getattr(jax, "__version__", "unknown"),
            "platform": platform,
            "seed": int(os.environ.get("BENCH_SEED", "0")),
            "timestamp": os.environ.get("BENCH_RUN_TS", ""),
            # pipeline-region fusion mode (exec/regions.py): =0 is the
            # per-operator A/B; artifacts must say which form ran
            "fusion": os.environ.get("PRESTO_TPU_FUSION", "1") != "0"}


def _latency_tail(run_once, runs=5):
    """p50/p99 per-query wall over `runs` invocations of `run_once` --
    the tail behavior the single-number BENCH headline has never
    captured (round-9 observability work)."""
    walls = []
    for _ in range(runs):
        t0 = time.time()
        run_once()
        walls.append(time.time() - t0)
    return {"p50_s": round(float(np.percentile(walls, 50)), 5),
            "p99_s": round(float(np.percentile(walls, 99)), 5),
            "runs": runs}


def _query_telemetry(res):
    """QueryStats -> the compile/execute split the BENCH json records
    (exec/stats.py structured telemetry; None when stats are absent)."""
    qs = getattr(res, "query_stats", None)
    if qs is None:
        return None
    out = {"compile_s": round(qs.compile_us / 1e6, 3),
           "execute_s": round(qs.stage_us("execute") / 1e6, 5),
           "staging_s": round(qs.stage_us("staging") / 1e6, 5),
           "rows": qs.output_rows,
           "peak_memory_bytes": qs.peak_memory_bytes}
    comp = qs.stages.get("compile")
    if comp is not None and comp.flops:
        out["flops"] = comp.flops
        out["bytes_accessed"] = comp.bytes_accessed
    return out


def _bench_sql_join(name, sql_text, sf, platform, **hints):
    """End-to-end wall time of a join config through the SQL front door
    (plan + NDV refine + stage + execute; second run reuses the XLA
    compile cache, so run2 - run1 separates compile from execute --
    and the engine's own QueryStats now report the split directly)."""
    from presto_tpu.connectors import tpch
    from presto_tpu.sql import sql as run_sql

    n = tpch.table_row_count("lineitem", sf)
    t0 = time.time()
    res_cold = run_sql(sql_text, sf=sf, **hints)
    cold_s = time.time() - t0
    t0 = time.time()
    res = run_sql(sql_text, sf=sf, **hints)
    warm_s = time.time() - t0
    # warm-path latency tail (p50/p99) + which kernels burned the
    # device, from the continuous profiler -- the BENCH artifact now
    # records tail behavior beside the compile/execute split
    latency = _latency_tail(lambda: run_sql(sql_text, sf=sf, **hints),
                            runs=3)
    print(json.dumps({
        "metric": f"tpch_sf{sf:g}_{name}_rows_per_sec",
        "value": round(n / warm_s), "unit": "rows/s", "vs_baseline": 0,
        "detail": {"path": "sql-front-door end-to-end (incl. staging)",
                   "cold_wall_s": round(cold_s, 3),
                   "warm_wall_s": round(warm_s, 3),
                   "rows": n, "row_count": res.row_count,
                   "telemetry_cold": _query_telemetry(res_cold),
                   "telemetry_warm": _query_telemetry(res),
                   "latency_warm": latency,
                   "platform": platform,
                   "meta": _bench_meta(platform)}}))


def _bench_large_g(platform, iters):
    """Sorted-mode group-by (the G>64 default since round 3, never yet
    measured on a chip): N=4M rows, G=128k groups, sum(int64)."""
    import jax

    from presto_tpu import types as T
    from presto_tpu.block import batch_from_numpy
    from presto_tpu.ops.aggregation import AggSpec, group_by

    n, g = 4_000_000, 1 << 17
    rng = np.random.default_rng(0)
    keys = rng.integers(0, g, n).astype(np.int64)
    vals = rng.integers(-(10 ** 6), 10 ** 6, n).astype(np.int64)
    batch = jax.block_until_ready(jax.device_put(
        batch_from_numpy([T.BIGINT, T.BIGINT], [keys, vals], capacity=n)))
    spec = [AggSpec("sum", 1, T.BIGINT)]

    t_compile = time.time()
    run = jax.jit(lambda b: group_by(b, [0], spec, g).batch)
    jax.device_get(run(batch))
    compile_s = time.time() - t_compile

    dt, fallback = _diff_windows(run, batch, iters)
    print(json.dumps({
        "metric": "groupby_sorted_128k_rows_per_sec",
        "value": round(n / dt), "unit": "rows/s", "vs_baseline": 0,
        "detail": {"n": n, "groups": g, "wall_s": round(dt, 5),
                   "compile_s": round(compile_s, 1),
                   "timing_fallback": fallback,
                   "platform": platform}}))


def _smallg_scatter_free() -> bool:
    from presto_tpu.ops.aggregation import _scatter_free
    return _scatter_free()


def main():
    sf = float(os.environ.get("BENCH_SF", "1"))
    iters = int(os.environ.get("BENCH_ITERS", "5"))
    query = os.environ.get("BENCH_QUERY", "q1")  # q1 | q6
    narrow_on = os.environ.get("PRESTO_TPU_NARROW", "1") != "0"
    platform = _require_tpu()

    if query == "q6":
        return _bench_q6(sf, iters, platform)

    from presto_tpu.connectors import tpch
    from presto_tpu.queries import Q1_COLUMNS, q1_local

    n = tpch.table_row_count("lineitem", sf)
    capacity = -(-n // 1024) * 1024

    t_gen = time.time()
    host_cols = tpch.generate_columns("lineitem", sf, Q1_COLUMNS)
    gen_s = time.time() - t_gen

    # numpy single-core baseline (one run)
    epoch = np.datetime64("1970-01-01")
    cutoff = int((np.datetime64("1998-09-02") - epoch).astype(int))
    t0 = time.time()
    _numpy_q1(host_cols, cutoff)
    numpy_s = time.time() - t0

    # --- SQL front door (the headline): parse/plan/refine ONCE, then
    # time the compiled engine pipeline exactly like the hand-built one
    t_plan = time.time()
    from presto_tpu.exec.planner import compile_plan
    from presto_tpu.plan.stats import refine_capacities
    from presto_tpu.plan.widths import annotate_widths
    from presto_tpu.sql.planner import plan_sql
    plan = refine_capacities(plan_sql(TPCH_Q1), sf)
    if narrow_on:
        # width inference (plan/widths.py): stage range-proven columns
        # at narrowed lanes -- the staged-MB delta below is the A/B
        # (PRESTO_TPU_NARROW=0 reverts)
        plan = annotate_widths(plan, sf)
    cp = compile_plan(plan)
    plan_s = time.time() - t_plan
    assert len(cp.scan_nodes) == 1
    scan_cols = cp.scan_nodes[0].columns
    sql_phys = cp.scan_nodes[0].physical_dtypes
    sql_host = tpch.generate_columns("lineitem", sf, scan_cols)
    dt_sql, sql_staged_bytes, sql_stage_s = _stage_and_time(
        sql_host, scan_cols, capacity, cp.fn, iters, wrap_seq=True,
        physical_dtypes=sql_phys)
    sql_fallback = _TIMING_FALLBACK

    # --- hand-built plan (HandTpchQuery1 analog), for engine-overhead
    # comparison -- staged with the same width inference
    hand_phys = None
    if narrow_on:
        from presto_tpu.plan.widths import infer_table_widths
        hand_phys = infer_table_widths(
            "tpch", "lineitem", Q1_COLUMNS,
            [tpch.column_type("lineitem", c) for c in Q1_COLUMNS], sf)
    dt_hand, staged_bytes, _hand_stage_s = _stage_and_time(
        host_cols, Q1_COLUMNS, capacity, q1_local(), iters,
        physical_dtypes=hand_phys)

    # fast telemetry smoke: one run_sql at sf=0.01 through the full
    # engine so every BENCH artifact carries the compile/execute split
    # (and XLA cost_analysis FLOPs) the QueryStats pipeline measures;
    # cheap and independent of the timed windows above
    from presto_tpu.sql import sql as run_sql
    telemetry_smoke = _query_telemetry(run_sql(
        TPCH_Q1, sf=0.01, session={"query_cost_analysis": True}))
    # per-query latency tail through the full front door at smoke
    # scale: the perf trajectory captures tail behavior
    latency_smoke = _latency_tail(lambda: run_sql(TPCH_Q1, sf=0.01),
                                  runs=5)
    # donation A/B at smoke scale: per-query pool peak with the
    # materialized executor, donation ON -- the perfgate-gated
    # `peak_memory_mb` sample -- beside the donation-off peak and the
    # bytes the K006-proven donating dispatches aliased in place
    donation_smoke = _donation_smoke()

    rows_per_sec = n / dt_sql
    baseline_rows_per_sec = n / numpy_s
    result = {
        "metric": f"tpch_sf{sf:g}_q1_rows_per_sec",
        "value": round(rows_per_sec),
        "unit": "rows/s",
        "vs_baseline": round(rows_per_sec / baseline_rows_per_sec, 3),
        "detail": {
            "path": "sql-front-door (parser->planner->NDV refine->XLA)",
            "query_wall_s": round(dt_sql, 5),
            "hand_built_wall_s": round(dt_hand, 5),
            "hand_built_rows_per_sec": round(n / dt_hand),
            "plan_wall_s": round(plan_s, 3),
            "numpy_singlecore_wall_s": round(numpy_s, 4),
            "datagen_wall_s": round(gen_s, 2),
            "rows": n,
            "staged_mb": round(sql_staged_bytes / 1e6, 1),
            "achieved_gb_per_s": round(sql_staged_bytes / dt_sql / 1e9, 1),
            # the MEASURED host->HBM staging rate (one device_put of
            # the q1 scan, synced): the perfgate-gated
            # `staging_gb_per_s` sample, the exact number ROADMAP
            # item 3's async split pipeline must raise past 1.0
            "staging_gb_per_s": round(
                sql_staged_bytes / max(sql_stage_s, 1e-9) / 1e9, 3),
            # per-hop achieved rates from the data-path waterfall
            # (exec/datapath.py; populated by the run_sql smoke runs)
            "datapath": _datapath_detail(),
            # per-query estimate-accuracy summary (exec/accuracy.py;
            # populated by the run_sql smoke runs): worst q-error and
            # the node that earned it ride every BENCH artifact
            "accuracy": _accuracy_detail(),
            "hand_built_staged_mb": round(staged_bytes / 1e6, 1),
            "timing_fallback": sql_fallback or _TIMING_FALLBACK,
            "telemetry_smoke_sf001": telemetry_smoke,
            "latency_smoke_sf001": latency_smoke,
            # proven-safe buffer donation (exec/donation.py): the gated
            # per-query peak rides top-level; the off-peak and donated
            # bytes ride the subsection for the A/B readout
            "peak_memory_mb": donation_smoke["peak_memory_mb"],
            "donation": donation_smoke,
            "platform": platform,
            "iters": iters,
            # which small-G group-by form ACTUALLY COMPILED for the
            # timed runs (recorded at trace time by ops/aggregation;
            # makes kernel A/Bs visible in artifacts) + what was asked
            "smallg_form": _executed_smallg_form(),
            # narrow-width execution A/B (PRESTO_TPU_NARROW): staged_mb
            # above reflects the narrowed lanes when on
            "narrow_width_execution": narrow_on,
            "meta": _bench_meta(platform),
        },
    }
    print(json.dumps(result))


def _donation_smoke():
    """Donation A/B of q1 at smoke scale under the materialized region
    executor: per-query MemoryPool peak with buffer donation off vs on
    (strictly lower when a K006-proven donation landed), plus the HBM
    bytes the donating dispatches aliased in place of fresh outputs."""
    from presto_tpu.exec.donation import donation_totals
    from presto_tpu.exec.memory import MemoryPool
    from presto_tpu.sql import sql as run_sql
    peaks = {}
    donated = 0
    for name, sess in (("off", {"fusion": False}),
                       ("on", {"fusion": False,
                               "buffer_donation": True})):
        pool = MemoryPool(1 << 34)
        before = donation_totals()["donated_bytes"]
        run_sql(TPCH_Q1, sf=0.01, session=sess, memory_pool=pool,
                query_id=f"bench-donation-{name}")
        peaks[name] = pool.peak_bytes
        if name == "on":
            donated = donation_totals()["donated_bytes"] - before
    return {"peak_memory_mb": round(peaks["on"] / 1e6, 3),
            "peak_memory_mb_donation_off": round(peaks["off"] / 1e6, 3),
            "donated_bytes": donated}


def _datapath_detail():
    """Per-hop byte totals + achieved GB/s from the process data-path
    ledger (exec/datapath.py) -- only hops the run exercised. The
    BENCH artifact records where the bytes went and how fast each hop
    moved them, beside the headline staging_gb_per_s."""
    from presto_tpu.exec.datapath import process_totals
    out = {}
    for hop, h in process_totals().items():
        if not h.invocations:
            continue
        rate = h.bytes / (h.wall_us / 1e6) if h.wall_us else 0.0
        out[hop] = {"bytes": h.bytes,
                    "achieved_gb_per_s": round(rate / 1e9, 3)}
    return out


def _accuracy_detail():
    """Per-unit estimate-accuracy roll-up from the process ledger
    (exec/accuracy.py) -- record/misestimate counts and the worst
    q-error per unit, so the BENCH artifact records whether the
    planner's cardinality/footprint estimates held for this run."""
    from presto_tpu.exec.accuracy import process_totals
    out = {}
    for unit, t in process_totals().items():
        if not t.get("records"):
            continue
        out[unit] = {"records": t["records"],
                     "under": t["under"], "over": t["over"],
                     "worst_q_error": round(t["worstQError"], 2),
                     "worst_node": t["worstNode"]}
    return out


def _executed_smallg_form():
    from presto_tpu.ops.aggregation import last_smallg_form
    return last_smallg_form() or (
        "einsum-MXU" if _smallg_scatter_free() else "scatter")


def _stage_and_time(host_cols, columns, capacity, pipeline_fn, iters,
                    wrap_seq=False, physical_dtypes=None):
    """The one staging/warmup/timing harness both benchmarks share.

    Timing is done by *differencing* two windows -- ``iters`` and
    ``2*iters`` executions, each ended by a real host fetch of the
    result (``jax.device_get``), so the fixed cost of the fetch
    cancels and per-iteration device time is left. Whether
    ``block_until_ready`` alone can be trusted on the attached chip is
    ROADMAP A0's to settle.

    ``wrap_seq``: pipeline_fn is a CompiledPlan.fn taking a SEQUENCE of
    scan batches (vs a single batch).

    Returns (per-iteration wall, staged bytes, staging wall): the
    third value is the measured host->HBM put of the scan batch
    (synced), the denominator of the gated ``staging_gb_per_s``.
    """
    import jax

    from presto_tpu.block import batch_from_numpy
    from presto_tpu.connectors import tpch

    types = [tpch.column_type("lineitem", c) for c in columns]
    t_stage0 = time.time()
    batch = jax.block_until_ready(jax.device_put(
        batch_from_numpy(types, [host_cols[c] for c in columns],
                         capacity=capacity,
                         physical_dtypes=physical_dtypes)))
    stage_s = time.time() - t_stage0
    fn = (lambda b: pipeline_fn([b])) if wrap_seq else pipeline_fn
    run = jax.jit(fn)
    warm = jax.device_get(run(batch))  # warm-up / compile + round trip
    if wrap_seq and int(np.asarray(warm[1]).reshape(-1)[0]) != 0:
        raise RuntimeError("benchmark plan overflowed a static capacity; "
                           "timing would measure garbage")

    global _TIMING_FALLBACK
    dt, _TIMING_FALLBACK = _diff_windows(run, batch, iters)
    staged_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(batch))
    return dt, staged_bytes, stage_s


def _diff_windows(run, batch, iters):
    """The one timing method every benchmark shares: time `iters` and
    `2*iters` windows (each ended by a real host fetch) and difference
    them, cancelling the fetch's fixed cost. Returns (dt, fallback);
    fallback=True means the differencing hit the noise floor and the
    larger window's mean (fetch included) was reported instead."""
    import jax

    def window(k):
        t0 = time.time()
        out = None
        for _ in range(k):
            out = run(batch)
        jax.device_get(out)  # real host fetch: cannot complete early
        return time.time() - t0

    t_small = window(iters)
    t_big = window(2 * iters)
    dt = (t_big - t_small) / iters
    if dt <= 0:
        return t_big / (2 * iters), True
    return dt, False


_TIMING_FALLBACK = False


def _bench_q6(sf, iters, platform):
    from presto_tpu.connectors import tpch
    from presto_tpu.queries import Q6_COLUMNS, q6_local

    n = tpch.table_row_count("lineitem", sf)
    capacity = -(-n // 1024) * 1024
    host = tpch.generate_columns("lineitem", sf, Q6_COLUMNS)
    dt, staged_bytes, stage_s = _stage_and_time(host, Q6_COLUMNS,
                                                capacity, q6_local(),
                                                iters)
    print(json.dumps({
        "metric": f"tpch_sf{sf:g}_q6_rows_per_sec",
        "value": round(n / dt), "unit": "rows/s", "vs_baseline": 0,
        "detail": {"query_wall_s": round(dt, 5), "rows": n,
                   "staged_mb": round(staged_bytes / 1e6, 1),
                   "achieved_gb_per_s": round(staged_bytes / dt / 1e9, 1),
                   "staging_gb_per_s": round(
                       staged_bytes / max(stage_s, 1e-9) / 1e9, 3),
                   "timing_fallback": _TIMING_FALLBACK,
                   "platform": platform,
                   "iters": iters,
                   "meta": _bench_meta(platform)}}))


if __name__ == "__main__":
    import sys
    if "--full" in sys.argv:
        _bench_full()
    else:
        main()
