#!/usr/bin/env python3
"""Microbenchmark on the chip: a join's lookup (`ops/join._match_ranges`)
against lookups answered by its bucket directory alone.

  python scripts/microbench_match_ranges.py [--out FILE.json]
      [--shapes sf10] [--forms repo,packed_lane] [--rehearse]

Shapes (queries, build rows, the directory's width over
2**ceil(log2 build rows)):

  sf10.q3       60M ascending orderkeys (lineitem, four a key) into a
                15M-row dense build (orders 1..15M), 48% usable: Q3's
                first join at SF10
  mesh.q3.x1    56M queries into a 14M-row build whose keys are a hash
                quarter of 1..45M (a chip's share of orders after the
                exchange at SF30), the directory at 1x: its span does
                not fit, so only the searched form answers
  mesh.q3.x4    the same with the directory 4x wider: it fits
  sf1.q3        6M queries into a 1.5M-row build: a table under 16 MB

Forms, each an exact [start, end) a query (all compared on the device
with the first form's answer):

  repo          `join._match_ranges` as the checkout has it, with the
                shape's directory width (PR 37's step 0 ran the parent's,
                which took none)
  directory     the directory alone: the histogram and its running sum
  pair_rows     (start, end) as one gathered row of two int32 lanes
  two_lanes     directory[v] and directory[v + 1]: two int32 gathers
  u64_lane      start << 32 | end in one uint64 lane: one gather
  packed_lane   start << k | (end - start) in one int32 lane, k the bits
                of the longest run: one 32-bit gather

Each is compiled once (seconds and XLA's temporaries reported) and timed
over five calls that end in `block_until_ready`. Exits 3 without a TPU
(a CPU time is no device number) unless --rehearse, which runs every
form at a thousandth of the shapes on whatever backend there is and
claims no time.
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
import numpy as np  # noqa: E402

from presto_tpu.ops import join  # noqa: E402

MAXW = np.uint64(0xFFFFFFFFFFFFFFFF)
# (name, queries, build rows, key span, usable share, directory width)
SHAPES = [
    ("sf10.q3", 60_000_000, 15_000_000, 15_000_000, 0.48, 1),
    ("mesh.q3.x1", 56_000_000, 14_000_000, 45_000_000, 0.48, 1),
    ("mesh.q3.x4", 56_000_000, 14_000_000, 45_000_000, 0.48, 4),
    ("sf1.q3", 6_000_000, 1_500_000, 1_500_000, 0.48, 1),
]


def _mix(k):
    """A 64-bit mixer to pick a hash quarter of the keys, as an
    exchange's does (splitmix64's finalizer)."""
    k = (k ^ (k >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    k = (k ^ (k >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return k ^ (k >> np.uint64(31))


def inputs(nq, nb, span, share, width, seed):
    """(sorted build keys with the MAX tail, n_usable, queries) on the
    device. One chip's keys: 1..span, or the hash quarter of them where
    the directory is widened for a mesh's share."""
    rng = np.random.default_rng(seed)
    keys = np.arange(1, span + 1, dtype=np.uint64)
    if span > nb:  # a chip's quarter after a hash exchange
        keys = keys[_mix(keys) % np.uint64(4) == 0]
    usable = rng.random(len(keys)) < share
    sb = np.full(nb, MAXW, dtype=np.uint64)
    n = int(usable.sum())
    sb[:n] = keys[usable]
    q = np.repeat(keys, 4)[:nq]  # ascending, four lineitems an order
    q = np.concatenate([q, np.zeros(nq - len(q), np.uint64)])  # padding
    return jnp.asarray(sb), jnp.asarray(n, dtype=jnp.int32), jnp.asarray(q)


def _directory(sorted_keys, n_usable, width):
    """The bucket directory `_match_ranges` builds (running sum along
    rows), with `width` times 2**ceil(log2 nb) buckets; shift 0 where
    the span fits it. Returns (directory, kmin, fits, D)."""
    nb = sorted_keys.shape[0]
    log2d = max((nb - 1).bit_length(), 1) + (width - 1).bit_length()
    d = 1 << log2d
    n = n_usable.astype(jnp.int32)
    kmin = sorted_keys[0]
    kmax = sorted_keys[jnp.maximum(n - 1, 0)]
    fits = (kmax - kmin) < d
    b = jnp.minimum(jnp.clip(sorted_keys, kmin, kmax) - kmin,
                    d - 1).astype(jnp.int32)
    pos = jnp.arange(nb, dtype=jnp.int32)
    hist = jnp.zeros(d + 1, dtype=jnp.int32).at[b + 1].add(
        (pos < n).astype(jnp.int32), indices_are_sorted=True)
    return join._running_sum(hist), kmin, fits, d


def _index(q, kmin, d):
    """A query's row of a (D + 2)-row table: 0 below kmin, b + 1 for
    bucket b, D + 1 at and past kmin + D."""
    return jnp.where(q < kmin, 0,
                     jnp.minimum(q - kmin, d).astype(jnp.int32) + 1)


def form(name, width):
    def fn(sb, n, q):
        if name == "repo":
            return join._match_ranges(sb, n, q, spread=width)[:2]
        directory, kmin, fits, d = _directory(sb, n, width)
        if name == "directory":
            return directory, fits
        n32 = n.astype(jnp.int32)
        lo = jnp.concatenate([jnp.zeros(1, jnp.int32), directory])  # D + 2
        hi = jnp.concatenate([jnp.zeros(1, jnp.int32), directory[1:],
                              n32[None]])
        v = _index(q, kmin, d)
        if name == "pair_rows":
            r = jnp.stack([lo, hi], axis=1)[v]
            return r[:, 0], r[:, 1]
        if name == "two_lanes":
            return lo[v], hi[v]
        if name == "u64_lane":
            lane = (lo.astype(jnp.uint64) << 32) | hi.astype(jnp.uint64)
            g = lane[v]
            return (g >> 32).astype(jnp.int32), g.astype(jnp.uint32).astype(
                jnp.int32)
        if name == "packed_lane":
            fullest = jnp.max(directory[1:] - directory[:-1])
            k = 32 - jax.lax.clz(fullest)
            g = (lo << k) | (hi - lo)
            g = g[v]
            s = g >> k
            return s, s + (g & ((1 << k) - 1))
        raise ValueError(name)

    return fn


FORMS = ["repo", "directory", "pair_rows", "two_lanes", "u64_lane",
         "packed_lane"]


def timed(fn, args, runs):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    try:
        temp = compiled.memory_analysis().temp_size_in_bytes
    except Exception:  # noqa: BLE001 - a backend without the analysis
        temp = None
    out = jax.block_until_ready(compiled(*args))
    walls = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        walls.append((time.perf_counter() - t0) * 1e3)
    return out, compile_s, temp, statistics.median(walls), min(walls)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None)
    ap.add_argument("--shapes", default="",
                    help="only the shapes whose name holds this")
    ap.add_argument("--forms", default=",".join(FORMS),
                    help="the forms to time, by name, comma-separated")
    ap.add_argument("--rehearse", action="store_true",
                    help="a thousandth of the shapes, any backend, no time")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.rehearse:
        print("no TPU: nothing is measured", file=sys.stderr)
        return 3
    scale = 1000 if args.rehearse else 1
    rows = []
    for name, nq, nb, span, share, width in SHAPES:
        if args.shapes not in name:
            continue
        operands = inputs(nq // scale, nb // scale, span // scale, share,
                          width, seed=nq + width)
        want = None
        for f in args.forms.split(","):
            if f != "repo" and f != "directory" and width == 1 \
                    and span > nb:
                continue  # the span does not fit a 1x directory
            out, compile_s, temp, median_ms, min_ms = timed(
                form(f, width), operands, 1 if args.rehearse else 5)
            row = {"shape": name, "form": f, "queries": nq // scale,
                   "build_rows": nb // scale, "width": width,
                   "compile_s": round(compile_s, 3), "temp_bytes": temp}
            if f == "directory":
                row["fits"] = bool(out[1])
            else:
                if want is None:
                    want = out
                row["equal"] = bool(jnp.array_equal(out[0], want[0])
                                    & jnp.array_equal(out[1], want[1]))
            if not args.rehearse:
                row.update(median_ms=median_ms, min_ms=min_ms,
                           ns_per_query=median_ms * 1e6 / nq)
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": device.device_kind, "rows": rows}, f,
                      indent=1)
    return 0 if all(r.get("equal", True) for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
