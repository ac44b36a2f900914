#!/usr/bin/env python3
"""Microbenchmark on the chip: the probe side of `hash_join`, looked up
whole against compacted to the rows that can emit a slot.

  python scripts/microbench_probe_compact.py [--out FILE.json] [--shapes sf1]

`ops/join._probe_side` at the shapes the three joins of the benchmark's
Q14 and Q3 hand it at TPC-H SF10 and SF1 (probe rows, output slots,
build rows), over probes of which 1.25%, 5%, 25% and 54% can emit:

  full        every probe row looked up (compact capacity 0: the form
              `hash_join` held until PR 31)
  rule        what `hash_join` compiles: `_compact_capacity`'s capacity
              and a `cond` on the count of emitting rows; taken at the
              shares that fit the capacity, not taken at the others
              (what the branch not taken costs)
  fit <share> the compacted form with room for that share of the probe
              (the power of two at or above its emitting rows), whatever
              the rule says: where it stops paying

and the compaction's own pieces, `_running_sum` and `_compact_probe`.
Each form is compiled once (seconds reported) and timed over five calls
that end in `block_until_ready`; every form's slots are compared with
`full`'s on the device. Exits 3 without a TPU: a CPU time is no device
number.
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

from presto_tpu import types as T  # noqa: E402
from presto_tpu.block import Column  # noqa: E402
from presto_tpu.ops import join  # noqa: E402
from presto_tpu.ops.keys import key_words  # noqa: E402

# (name, probe rows, output slots, build rows)
SHAPES = [
    ("sf10.q3.JoinNode.7", 60_000_000, 4_194_304, 15_000_000),
    ("sf10.q3.JoinNode.6", 4_194_304, 4_194_304, 1_500_000),
    ("sf10.q14.JoinNode.4", 60_000_000, 1_048_576, 2_000_000),
    ("sf1.q3.JoinNode.7", 6_000_000, 262_144, 1_500_000),
    ("sf1.q3.JoinNode.6", 262_144, 262_144, 150_000),
    ("sf1.q14.JoinNode.4", 6_000_000, 262_144, 200_000),
]
SHARES = [0.0125, 0.05, 0.25, 0.54]


def probe_side(slots, capacity):
    """`_probe_side` of an inner join on an integer column (32 bits on
    the device, as the benchmark's keys), as a function of its own for
    each capacity (jit keeps a program by function)."""

    def fn(sorted_keys, b_usable, p_keys, p_active):
        key = Column(p_keys, jnp.zeros(p_keys.shape, dtype=bool), T.INTEGER)
        return join._probe_side([sorted_keys], b_usable, [key], p_active,
                                False, slots, capacity)

    return fn


def timed(fn, args, runs=5):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.perf_counter() - t0

    def call(*args):
        out = jax.block_until_ready(compiled(*args))
        walls = []
        for _ in range(runs):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*args))
            walls.append((time.perf_counter() - t0) * 1e3)
        return out, statistics.median(walls), min(walls)

    return call, compile_s


def same_slots(got, want) -> bool:
    """Live slots name the same probe and build rows; every slot agrees
    on whether it is live, and the totals agree."""
    prow, valid, matched, srow, total = got[:5]
    wprow, wvalid, wmatched, wsrow, wtotal = want[:5]
    return bool(jnp.array_equal(valid, wvalid)
                & jnp.array_equal(prow, wprow)
                & jnp.all(~valid | ((matched == wmatched) & (srow == wsrow)))
                & (total == wtotal))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=None)
    ap.add_argument("--shapes", default="",
                    help="only the shapes whose name holds this")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print("no TPU: nothing is measured", file=sys.stderr)
        return 3
    rows = []

    def report(**row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    for name, npr, slots, nb in SHAPES:
        if args.shapes not in name:
            continue
        key = jax.random.PRNGKey(npr % 1000 + slots)
        # dense keys 1..nb, as `keys.key_words` orders an integer column
        build_key = Column(jnp.arange(1, nb + 1, dtype=jnp.int32),
                           jnp.zeros(nb, dtype=bool), T.INTEGER)
        sorted_keys = key_words([build_key])[0][1]
        b_usable = jnp.ones(nb, dtype=bool)
        p_keys = jax.random.randint(key, (npr,), 1, nb + 1, dtype=jnp.int32)
        masks = {share: jax.random.bernoulli(jax.random.fold_in(key, i),
                                             share, (npr,))
                 for i, share in enumerate(SHARES)}
        emitting = {share: int(jnp.sum(m)) for share, m in masks.items()}
        operands = (sorted_keys, b_usable, p_keys)
        shape = {"shape": name, "probe_rows": npr, "slots": slots,
                 "build_rows": nb}

        run, compile_s = timed(probe_side(slots, 0),
                               (*operands, masks[SHARES[0]]))
        want = {}
        for share, mask in masks.items():
            want[share], median_ms, min_ms = run(*operands, mask)
            report(**shape, form="full", share=share,
                   emitting=emitting[share], capacity=0, taken=False,
                   compile_s=compile_s, median_ms=median_ms, min_ms=min_ms,
                   equal=True)

        forms = [("rule", join._compact_capacity(npr, slots), SHARES)]
        forms += [(f"fit {share}", 1 << (emitting[share] - 1).bit_length(),
                   [share]) for share in SHARES]
        for form, capacity, shares in forms:
            if not capacity:  # the rule compiles no second form here
                report(**shape, form=form, capacity=0)
                continue
            run, compile_s = timed(probe_side(slots, capacity),
                                   (*operands, masks[shares[0]]))
            for share in shares:
                got, median_ms, min_ms = run(*operands, masks[share])
                report(**shape, form=form, share=share,
                       emitting=emitting[share], capacity=capacity,
                       taken=bool(got[-1]), compile_s=compile_s,
                       median_ms=median_ms, min_ms=min_ms,
                       equal=same_slots(got, want[share]))

        # the compaction's own pieces, at the rule's capacity (or the
        # output's, where the rule gives none)
        capacity = join._compact_capacity(npr, slots) or slots
        mask = masks[SHARES[0]]
        for form, fn in [
                ("_running_sum", lambda m: join._running_sum(
                    m.astype(jnp.int32))),
                ("_compact_probe", lambda m: join._compact_probe(
                    m, capacity))]:
            run, compile_s = timed(fn, (mask,))
            _, median_ms, min_ms = run(mask)
            report(**shape, form=form, share=SHARES[0], capacity=capacity,
                   compile_s=compile_s, median_ms=median_ms, min_ms=min_ms)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": device.device_kind, "rows": rows}, f,
                      indent=1)
    return 0 if all(r.get("equal", True) for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
