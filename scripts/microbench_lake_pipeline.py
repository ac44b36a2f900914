#!/usr/bin/env python3
"""Microbenchmark on the chip's host: staging a Q6 scan of a lake file,
hop after hop against a pipeline of row groups.

  python scripts/microbench_lake_pipeline.py [--out FILE.json] [--sf 10]
                                             [--widths 8,13]

Writes the four columns Q6 reads of TPC-H lineitem at `--sf` as the
benchmark's load writes `hive.lineitem` (pages of 10M rows, row groups
of 1,048,576, SNAPPY; at SF10 60 groups, 502 MB of column chunks) and
stages them to the device, median of five after one warm-up each:

  sequential       what a statement paid until PR 33, hop after hop:
                   every group read on the pool; every group decoded
                   into wide lanes allocated whole; the range guard's
                   min and max over them; `batch_from_numpy` (the
                   cast copies and the put) -- built here from the
                   pieces that stay (`arrow_to_engine`,
                   `checked_physical_dtypes`, `batch_from_numpy`)
  produce w=<n>    the producer alone (`parquet.scan_pieces` with the
                   narrowed dtypes: read, decode and range proof into
                   narrowed lanes, a group a task), nothing put, on a
                   pool of n threads; with the thread-seconds its groups
                   spent reading and decoding
  put whole        the 840 MB of narrowed lanes and masks, ready-made,
                   in one `device_put` of eight arrays
  put concat       the same bytes a row group at a time, the batch's
                   columns made by one `concatenate` under a jit
  put land         the same, each group landing in lanes allocated once
                   by a donated `dynamic_update_slice`
                   (`block.BatchBuilder`: what the stager uses)
  pipeline w=<n>   all of it together: `exec/runner._stage_pieces`, the
                   put of a group overlapping the read and decode of
                   the next, on a pool of n threads

Every form's batch is compared with the sequential one on the device.
Exits 3 without a TPU: a CPU time is no device number.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import presto_tpu  # noqa: E402,F401  (x64 on before any array exists)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from presto_tpu.block import (Batch, BatchBuilder, Column,  # noqa: E402
                              batch_from_numpy)
from presto_tpu.connectors import parquet  # noqa: E402
from presto_tpu.connectors.tpch import generator as g  # noqa: E402
from presto_tpu.exec import runner  # noqa: E402
from presto_tpu.exec.memory import batch_bytes  # noqa: E402
from presto_tpu.plan.widths import (checked_physical_dtypes,  # noqa: E402
                                    infer_table_widths)

COLUMNS = ["quantity", "extendedprice", "discount", "shipdate"]
TABLE = "microbench_lineitem"
PAGE_ROWS = 10_000_000


def write_file(path: str, sf: float) -> int:
    types = dict(g.TPCH_SCHEMA["lineitem"])
    rows = int(g.table_row_count("lineitem", sf))
    w = parquet.open_writer(path, parquet.arrow_schema(
        {c: types[c] for c in COLUMNS}))
    try:
        for at in range(0, rows, PAGE_ROWS):
            page = g.generate_columns("lineitem", sf, COLUMNS, at,
                                      min(PAGE_ROWS, rows - at))
            w.write_table(parquet.engine_to_arrow(page, types),
                          row_group_size=parquet.ROW_GROUP_ROWS)
    finally:
        w.close()
    return rows


def use_pool(width: int) -> None:
    """A decode pool of `width` threads, and the depth that follows."""
    if parquet._pool is not None:
        parquet._pool.shutdown(wait=True)
    parquet._pool = ThreadPoolExecutor(max_workers=width,
                                       thread_name_prefix="lake-decode")
    parquet._pool_width = lambda: width


def sequential(types, phys, rows):
    """The four hops one after another; (batch, walls in ms by hop)."""
    import pyarrow.parquet as pq
    ent = parquet._tables[TABLE]
    path, md = ent["path"], ent["pf"].metadata
    pool = parquet._decode_pool()
    walls, t0 = {}, time.perf_counter()

    def lap(name):
        nonlocal t0
        now = time.perf_counter()
        walls[name], t0 = (now - t0) * 1e3, now

    pieces = list(pool.map(
        lambda k: pq.ParquetFile(path, metadata=md).read_row_group(
            k, columns=COLUMNS, use_threads=False),
        range(md.num_row_groups)))
    lap("connector_read")
    starts = np.concatenate([[0], np.cumsum([t.num_rows for t in pieces])])
    values = [np.empty(rows, dtype=ty.to_dtype()) for ty in types]
    nulls = [np.zeros(rows, dtype=bool) for _ in types]

    def decode(k):
        lo, hi = int(starts[k]), int(starts[k + 1])
        for i, c in enumerate(COLUMNS):
            vals, nl = parquet.arrow_to_engine(pieces[k].column(c).chunk(0),
                                               types[i])
            values[i][lo:hi] = vals
            if nl is not None:
                nulls[i][lo:hi] = nl

    list(pool.map(decode, range(len(pieces))))
    del pieces
    lap("decode")
    checked = checked_physical_dtypes(phys, types, values, nulls=nulls)
    lap("narrow_cast")
    b = jax.block_until_ready(batch_from_numpy(
        types, values, nulls=nulls, capacity=rows, physical_dtypes=checked))
    lap("device_put")
    return b, walls


def scan(dtypes):
    return parquet.scan_pieces(TABLE, COLUMNS, dtypes=dtypes)


@jax.jit
def _concat(lanes, masks):
    return (tuple(jnp.concatenate(pieces) for pieces in lanes),
            tuple(jnp.concatenate(pieces) for pieces in masks))


def put_concat(types, pieces, rows):
    lanes = [[] for _ in types]
    masks = [[] for _ in types]
    for values, nulls, n in pieces:
        dev = jax.device_put(([v[:n] for v in values], [m[:n] for m in nulls]))
        for i in range(len(types)):
            lanes[i].append(dev[0][i])
            masks[i].append(dev[1][i])
    values, nulls = _concat(tuple(map(tuple, lanes)), tuple(map(tuple, masks)))
    return Batch(tuple(Column(v, m, ty) for v, m, ty
                       in zip(values, nulls, types)),
                 jnp.ones(rows, dtype=bool))


def put_land(types, dtypes, pieces, rows, room):
    builder = BatchBuilder(types, dtypes, rows, room)
    for values, nulls, n in pieces:
        builder.put(values, nulls, n)
    return builder.finish()


def same(a: Batch, b: Batch) -> bool:
    xs, ys = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(xs) == len(ys) and all(
        x.dtype == y.dtype and bool(jnp.array_equal(x, y))
        for x, y in zip(xs, ys))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None)
    ap.add_argument("--sf", type=float, default=10.0)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--widths", default=None,
                    help="pool widths to try (default: 8 and min(16, cores))")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print("no TPU: nothing is measured", file=sys.stderr)
        return 3
    report_rows = []

    def report(**row):
        report_rows.append(row)
        print(json.dumps(row), flush=True)

    def timed(form, fn, check=None, **extra):
        """One warm-up (its compiles and first touches), then the
        median and least of `--runs` calls that end with the batch
        ready on the device."""
        out = jax.block_until_ready(fn())
        ok = None if check is None else same(out, check)
        del out
        walls = []
        for _ in range(args.runs):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            walls.append((time.perf_counter() - t0) * 1e3)
        report(form=form, median_ms=statistics.median(walls),
               min_ms=min(walls), max_ms=max(walls), equal=ok, **extra)

    cores = os.cpu_count() or 1
    widths = sorted({min(8, cores), min(16, cores)}) if not args.widths \
        else [int(w) for w in args.widths.split(",")]
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "lineitem.parquet")
        t0 = time.perf_counter()
        rows = write_file(path, args.sf)
        parquet.register_table(TABLE, path)
        md = parquet._tables[TABLE]["pf"].metadata
        types = [parquet.column_type(TABLE, c) for c in COLUMNS]
        phys = infer_table_widths("parquet", TABLE, COLUMNS, types, args.sf)
        dtypes = [dt or ty.to_dtype() for dt, ty in zip(phys, types)]
        probe = scan(dtypes)
        report(form="file", cpu_count=cores, rows=rows,
               row_groups=md.num_row_groups, file_bytes=os.path.getsize(path),
               chunk_bytes=probe.file_bytes, physical_dtypes=list(phys),
               write_s=time.perf_counter() - t0, device=device.device_kind)

        use_pool(min(8, cores))
        want, _ = sequential(types, phys, rows)
        report(form="staged", staged_bytes=batch_bytes(want))
        hops = []
        for _ in range(args.runs):
            b, walls = sequential(types, phys, rows)
            del b
            hops.append(walls)
        report(form="sequential", **{
            h + "_ms": statistics.median(w[h] for w in hops)
            for h in hops[0]},
            median_ms=statistics.median(sum(w.values()) for w in hops),
            min_ms=min(sum(w.values()) for w in hops))

        for width in widths:
            use_pool(width)
            spent = {"read": [], "decode": []}

            def produce():
                read = decode = 0.0
                for piece in scan(dtypes):
                    read += piece.read_at[1] - piece.read_at[0]
                    decode += piece.decode_at[1] - piece.decode_at[0]
                spent["read"].append(read)
                spent["decode"].append(decode)
                return ()

            timed(f"produce w={width}", produce, depth=probe.depth)
            report(form=f"produce w={width} thread-seconds",
                   read_s=statistics.median(spent["read"]),
                   decode_s=statistics.median(spent["decode"]))

        # the narrowed lanes and masks, ready-made on the host
        pieces = [([p.values[c] for c in COLUMNS],
                   [p.nulls[c] for c in COLUMNS], p.rows)
                  for p in scan(dtypes)]
        whole = ([np.concatenate([v[i][:n] for v, _m, n in pieces])
                  for i in range(len(COLUMNS))],
                 [np.concatenate([m[i][:n] for _v, m, n in pieces])
                  for i in range(len(COLUMNS))])

        def put_whole():
            values, nulls = jax.device_put(whole)
            return Batch(tuple(Column(v, m, ty) for v, m, ty
                               in zip(values, nulls, types)),
                         jnp.ones(rows, dtype=bool))

        timed("put whole", put_whole, want)
        timed("put concat", lambda: put_concat(types, pieces, rows), want)
        timed("put land",
              lambda: put_land(types, dtypes, pieces, rows, probe.room), want)
        del pieces, whole

        for width in widths:
            use_pool(width)
            timed(f"pipeline w={width}",
                  lambda: runner._stage_pieces(scan(dtypes), types, rows),
                  want)
        stats = device.memory_stats() or {}
        report(form="device", peak_bytes_in_use=stats.get("peak_bytes_in_use"))
        parquet.unregister_table(TABLE)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report_rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
