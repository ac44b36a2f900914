"""Root-cause harness for the r01->r04 hand-built-q1 CPU delta.

Times the SAME staged data through three q1 kernel variants at HEAD:
  exact128   the shipped plan (sums -> decimal(38,x) = int128 13-bit
             limb exact accumulation, round-2+ behavior)
  int64acc   sums -> decimal(18,x) (int64 accumulation -- the round-1
             representation, exactness waived)
  f64acc     sums -> double (pure float64 accumulate, lower bound)

Runs on the CPU (it imports scripts/_cpu.py):
    python scripts/bench_bisect.py [sf] [iters]
"""

import json
import sys
import time

import os
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, _HERE)
import _cpu  # noqa: F401  (must precede jax)

import jax
import numpy as np


def build_variant(kind):
    from presto_tpu import types as T
    from presto_tpu.expr import (call, compile_filter, compile_projections,
                                 const, input_ref)
    from presto_tpu.ops.aggregation import AggSpec, group_by

    D2 = T.decimal(12, 2)
    rf, ls = input_ref(0, T.char(1)), input_ref(1, T.char(1))
    qty, price = input_ref(2, D2), input_ref(3, D2)
    disc, tax = input_ref(4, D2), input_ref(5, D2)
    ship = input_ref(6, T.DATE)
    one = const(100, D2)
    filt = compile_filter(call("le", T.BOOLEAN, ship,
                               const("1998-09-02", T.DATE)))
    if kind == "f64acc":
        fp = T.DOUBLE

        def asf(e):
            return call("cast", fp, e)
        disc_price = call("multiply", fp, asf(price),
                          call("subtract", fp, asf(one), asf(disc)))
        charge = call("multiply", fp, disc_price,
                      call("add", fp, asf(one), asf(tax)))
        proj = compile_projections([rf, ls, asf(qty), asf(price),
                                    disc_price, charge, asf(disc)])
        sty = [fp] * 4
        avg = fp
    else:
        disc_price = call("multiply", T.decimal(24, 4), price,
                          call("subtract", D2, one, disc))
        charge = call("multiply", T.decimal(36, 6), disc_price,
                      call("add", D2, one, tax))
        proj = compile_projections([rf, ls, qty, price,
                                    disc_price, charge, disc])
        p = 38 if kind == "exact128" else 18
        sty = [T.decimal(p, 2), T.decimal(p, 2),
               T.decimal(p, 4), T.decimal(p, 6)]
        avg = D2
    aggs = [AggSpec("sum", 2, sty[0]), AggSpec("sum", 3, sty[1]),
            AggSpec("sum", 4, sty[2]), AggSpec("sum", 5, sty[3]),
            AggSpec("avg", 2, avg), AggSpec("avg", 3, avg),
            AggSpec("avg", 6, avg),
            AggSpec("count_star", None, T.BIGINT)]

    def run(batch):
        b = proj(filt(batch))
        return group_by(b, [0, 1], aggs, 16)

    return run


def main():
    sf = float(sys.argv[1]) if len(sys.argv) > 1 else 1.0
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 3

    from presto_tpu import types as _T  # noqa: F401 (warm import)
    from presto_tpu.block import batch_from_numpy
    from presto_tpu.connectors import tpch
    from presto_tpu.queries import Q1_COLUMNS

    n = tpch.table_row_count("lineitem", sf)
    capacity = -(-n // 1024) * 1024
    host = tpch.generate_columns("lineitem", sf, Q1_COLUMNS)
    schema = dict(tpch.TPCH_SCHEMA["lineitem"])
    tys = [schema[c] for c in Q1_COLUMNS]
    batch = jax.device_put(batch_from_numpy(
        tys, [host[c] for c in Q1_COLUMNS], capacity=capacity))
    jax.block_until_ready(batch)

    out = {"sf": sf, "rows": n, "iters": iters,
           "platform": jax.devices()[0].platform}

    def timed_on(fn, arg):
        t0 = time.time()
        jax.block_until_ready(fn(arg))
        compile_s = time.time() - t0
        best = float("inf")
        for _ in range(iters):
            t0 = time.time()
            jax.block_until_ready(fn(arg))
            best = min(best, time.time() - t0)
        return {"wall_s": round(best, 4), "compile_s": round(compile_s, 1),
                "rows_per_sec": round(n / best)}

    def timed(fn):
        return timed_on(fn, batch)

    for kind in ("exact128", "int64acc", "f64acc"):
        out[kind] = timed(jax.jit(build_variant(kind)))
        print(kind, out[kind], flush=True)

    # stage split on the shipped (exact128) shape: where does the time go?
    from presto_tpu import types as T
    from presto_tpu.expr import (call, compile_filter, compile_projections,
                                 const, input_ref)
    D2 = T.decimal(12, 2)
    rf, ls = input_ref(0, T.char(1)), input_ref(1, T.char(1))
    qty, price = input_ref(2, D2), input_ref(3, D2)
    disc, tax = input_ref(4, D2), input_ref(5, D2)
    ship = input_ref(6, T.DATE)
    one = const(100, D2)
    filt = compile_filter(call("le", T.BOOLEAN, ship,
                               const("1998-09-02", T.DATE)))
    disc_price = call("multiply", T.decimal(24, 4), price,
                      call("subtract", D2, one, disc))
    charge = call("multiply", T.decimal(36, 6), disc_price,
                  call("add", D2, one, tax))
    proj = compile_projections([rf, ls, qty, price, disc_price, charge,
                                disc])
    out["filter_project"] = timed(jax.jit(lambda b: proj(filt(b))))
    print("filter_project", out["filter_project"], flush=True)

    from presto_tpu.ops.aggregation import AggSpec, group_by
    aggs = [AggSpec("sum", 2, T.decimal(38, 2)),
            AggSpec("sum", 3, T.decimal(38, 2)),
            AggSpec("sum", 4, T.decimal(38, 4)),
            AggSpec("sum", 5, T.decimal(38, 6)),
            AggSpec("avg", 2, D2), AggSpec("avg", 3, D2),
            AggSpec("avg", 6, D2), AggSpec("count_star", None, T.BIGINT)]
    projected = jax.jit(lambda b: proj(filt(b)))(batch)
    jax.block_until_ready(projected)
    gb = jax.jit(lambda b: group_by(b, [0, 1], aggs, 16))

    out["group_by_only"] = timed_on(gb, projected)
    print("group_by_only", out["group_by_only"], flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
