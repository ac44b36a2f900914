#!/usr/bin/env python3
"""Microbenchmark on the chip: the prefix-sum expansion's slot -> row map.

  python scripts/microbench_slot_rows.py [--out FILE.json] [--blocks 64,256,rule]

`ops/join._slot_rows` (a block directory and log2(block) gathers of a
32-bit lane; blocks of a given size, or of what `_slot_block` says:
"rule") against the map `hash_join` held until PR 29
(`jnp.searchsorted` over the int64 offsets, then 64-bit gathers of
`off`, `emit`, `cnt`), at the shapes the three joins of the benchmark's
Q14 and Q3 hand it at TPC-H SF10 and SF1 (n probe rows, Q output
slots). Each form is compiled once (seconds reported) and timed over
five calls that end in `block_until_ready`; the answers are compared on
the device. Exits 3 without a TPU: a CPU time is no device number.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import presto_tpu  # noqa: E402,F401  (x64 on before any array exists)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from presto_tpu.ops import join  # noqa: E402

# (name, n, Q, share of the rows that emit one slot)
SHAPES = [
    ("sf10.q3.JoinNode.7", 60_000_000, 4_194_304, 0.025),
    ("sf10.q3.JoinNode.6", 4_194_304, 4_194_304, 0.07),
    ("sf10.q14.JoinNode.4", 60_000_000, 1_048_576, 0.0125),
    ("sf1.q3.JoinNode.7", 6_000_000, 262_144, 0.025),
    ("sf1.q3.JoinNode.6", 262_144, 262_144, 0.07),
    ("sf1.q14.JoinNode.4", 6_000_000, 262_144, 0.0125),
]


def searched(off, emit, cnt, slots):
    """The map as hash_join held it: int64 all the way."""
    emit, cnt = emit.astype(jnp.int64), cnt.astype(jnp.int64)
    total = off[-1] + emit[-1]
    k = jnp.arange(slots, dtype=jnp.int64)
    row = jnp.clip(jnp.searchsorted(off, k, side="right") - 1,
                   0, off.shape[0] - 1)
    j = k - off[row]
    return (row.astype(jnp.int32), (k < total) & (j < emit[row]),
            j < cnt[row])


RULE = join._slot_block


def directed(block):
    """The map as hash_join holds it, with blocks of `block` rows, or
    of what `ops/join._slot_block` says ("rule"); a function of its own
    for each, because jit keeps a program by function."""

    def fn(off, emit, cnt, slots):
        # read at trace time
        join._slot_block = RULE if block == "rule" else lambda n, slots: block
        total = off[-1] + emit[-1]
        k = jnp.arange(slots, dtype=jnp.int32)
        row, j, trips = join._slot_rows(off, slots)
        fn.trips = trips
        row = jnp.clip(row, 0, off.shape[0] - 1)
        return row, (k < total) & (j < emit[row]), j < cnt[row]

    return fn


def timed(fn, args, slots, runs=5):
    t0 = time.perf_counter()
    compiled = jax.jit(fn, static_argnums=3).lower(*args, slots).compile()
    compile_s = time.perf_counter() - t0
    out = jax.block_until_ready(compiled(*args))
    walls = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        walls.append((time.perf_counter() - t0) * 1e3)
    return out, compile_s, statistics.median(walls), min(walls)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None)
    ap.add_argument("--blocks", default="64,256,rule")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print("no TPU: nothing is measured", file=sys.stderr)
        return 3
    rows = []
    for name, n, slots, share in SHAPES:
        emit = jax.random.bernoulli(jax.random.PRNGKey(n % 1000 + slots),
                                    share, (n,)).astype(jnp.int32)
        off = jnp.cumsum(emit, dtype=jnp.int64) - emit
        operands = (off, emit, emit)
        want, compile_s, median_ms, min_ms = timed(searched, operands, slots)
        rows.append({"shape": name, "n": n, "slots": slots, "form": "search",
                     "trips": (n - 1).bit_length(), "compile_s": compile_s,
                     "median_ms": median_ms, "min_ms": min_ms, "equal": True})
        print(json.dumps(rows[-1]), flush=True)
        for block in args.blocks.split(","):
            fn = directed(block if block == "rule" else int(block))
            got, compile_s, median_ms, min_ms = timed(fn, operands, slots)
            equal = all(bool(jnp.array_equal(g, w))
                        for g, w in zip(got, want))
            rows.append({"shape": name, "n": n, "slots": slots,
                         "form": f"block {block}",
                         "trips": fn.trips,
                         "compile_s": compile_s, "median_ms": median_ms,
                         "min_ms": min_ms, "equal": equal})
            print(json.dumps(rows[-1]), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"device": device.device_kind, "rows": rows}, f,
                      indent=1)
    return 0 if all(r["equal"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
