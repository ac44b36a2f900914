#!/usr/bin/env python3
"""Microbenchmark on the chip: a hash exchange's packing, form by form.

  python scripts/microbench_exchange.py [--rows N] [--out FILE.json]

The batch is Q3's lineitem side as the plan exchanges it at TPC-H SF30
over four chips: one chip's shard of 45M rows, 54% of them active after
the shipdate filter, the narrowed lanes orderkey int32, extendedprice
int32, discount int8, a null mask a lane and `active`. Four
destinations.

On one chip (`--chips 1`, the default where JAX has one device) the
packing alone, which is a chip's own work, without the collective:

  as_is(slot)     the parent's `_route_rows`: a sort of (dest, index),
                  a `searchsorted`, then a gather and a scatter a lane
                  and a mask; with `slot = capacity` (the parent's) and
                  with `slot_for(capacity, n)` (this PR's)
  ranked(slot)    this PR's `_route_rows`: a running sum a destination,
                  lanes packed into 32-bit words, one scatter a word
  sorted(slot)    the sort kept, lanes packed, one gather a word from
                  the sorted order
  parts           the sort alone, `searchsorted`, one lane's gather and
                  scatter, the running sums, one word's scatter

With four devices the whole `exchange_by_hash` under `shard_map`, the
parent's form at both slot sizes against this PR's, and the
`all_to_all` alone. Each form is compiled once (seconds and planned HBM
reported) and timed over five calls that end in `block_until_ready`;
every form's packed rows are compared with `as_is`'s on the device.
Exits 3 without a TPU unless `--allow-cpu` (a rehearsal: no time of it
is a device number).
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
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from presto_tpu import types as T  # noqa: E402
from presto_tpu.block import Batch, Column  # noqa: E402
from presto_tpu.parallel import exchange as X  # noqa: E402
from presto_tpu.parallel.mesh import WORKERS_AXIS, make_mesh  # noqa: E402

N = 4  # destinations: the cell's four workers


def shard(rows: int, seed: int) -> Batch:
    """One chip's lineitem shard after Q3's filter, on the host."""
    rng = np.random.default_rng(seed)
    okey = (np.arange(rows, dtype=np.int64) // 4 + seed * rows).astype(
        np.int32)
    price = rng.integers(90_000, 10_500_000, rows, dtype=np.int32)
    disc = rng.integers(0, 11, rows, dtype=np.int8)
    none = np.zeros(rows, dtype=bool)
    return Batch((Column(okey, none, T.BIGINT),
                  Column(price, none, T.decimal(12, 2)),
                  Column(disc, none, T.decimal(12, 2))),
                 rng.random(rows) < 0.54)


def destinations(batch: Batch, n: int):
    h = X._row_hash([batch.column(0)])
    dest = (h % jnp.uint64(n)).astype(jnp.int32)
    return jnp.where(batch.active, dest, n)


# -- the parent's form (PR 33's `_route_rows`, kept here to be measured) --

def as_is_place(dest, n, slot):
    cap = dest.shape[0]
    s_dest, perm = jax.lax.sort([dest, jnp.arange(cap, dtype=jnp.int32)],
                                num_keys=1)
    start = jnp.searchsorted(s_dest, jnp.arange(n + 1, dtype=jnp.int32))
    place = jnp.arange(cap, dtype=jnp.int32) - start[jnp.clip(s_dest, 0, n)]
    flat = jnp.clip(s_dest, 0, n - 1) * slot + jnp.clip(place, 0, slot - 1)
    keep = (s_dest < n) & (place < slot)
    return perm, jnp.where(keep, flat, n * slot)


def as_is_pack(batch: Batch, n: int, slot: int):
    perm, idx = as_is_place(destinations(batch, n), n, slot)
    send = n * slot

    def pack(arr):
        zeros = jnp.zeros((send + 1,) + arr.shape[1:], dtype=arr.dtype)
        return zeros.at[idx].set(arr[perm])[:send]

    cols = tuple(Column(pack(c.values), pack(c.nulls), c.type)
                 for c in batch.columns)
    active = jnp.zeros(send + 1, dtype=bool).at[idx].set(True)[:send]
    return Batch(cols, active)


# -- this PR's form, and the one that keeps the sort ----------------------

def ranked_place(dest, n, slot):
    return X._slot_places(dest, n, slot)[0]


def ranked_pack(batch: Batch, n: int, slot: int):
    place = ranked_place(destinations(batch, n), n, slot)
    leaves, treedef = jax.tree_util.tree_flatten(
        batch.with_active(jnp.ones(batch.capacity, dtype=bool)))
    lanes, unpack = X._pack_lanes(leaves)
    sent = [jnp.zeros((n * slot,) + x.shape[1:], dtype=x.dtype)
            .at[place].set(x, mode="drop", unique_indices=True)
            for x in lanes]
    return jax.tree_util.tree_unflatten(treedef, unpack(sent))


def sorted_pack(batch: Batch, n: int, slot: int):
    dest = destinations(batch, n)
    cap = dest.shape[0]
    s_dest, perm = jax.lax.sort([dest, jnp.arange(cap, dtype=jnp.int32)],
                                num_keys=1)
    start = jnp.searchsorted(s_dest, jnp.arange(n + 1, dtype=jnp.int32))
    j = jnp.arange(slot, dtype=jnp.int32)
    at = (start[:n, None] + j[None, :]).reshape(-1)
    live = (j[None, :] < (start[1:] - start[:-1])[:, None]).reshape(-1)
    take = perm[jnp.clip(at, 0, cap - 1)]
    leaves, treedef = jax.tree_util.tree_flatten(
        batch.with_active(jnp.ones(cap, dtype=bool)))
    lanes, unpack = X._pack_lanes(leaves)
    sent = [jnp.where(live.reshape((-1,) + (1,) * (x.ndim - 1)), x[take],
                      jnp.zeros((), x.dtype)) for x in lanes]
    return jax.tree_util.tree_unflatten(treedef, unpack(sent))


# -- timing ---------------------------------------------------------------

def timed(name, fn, *args, repeats=5):
    """Compile once, run `repeats` times; ms, compile seconds, planned
    HBM (arguments + outputs + temporaries - aliased)."""
    t0 = time.time()
    compiled = jax.jit(fn).lower(*args).compile()
    compile_s = time.time() - t0
    ma = compiled.memory_analysis()
    planned = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                  + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    out = jax.block_until_ready(compiled(*args))
    walls = []
    for _ in range(repeats):
        t0 = time.time()
        jax.block_until_ready(compiled(*args))
        walls.append((time.time() - t0) * 1e3)
    line = {"form": name, "ms": round(statistics.median(walls), 3),
            "min_ms": round(min(walls), 3), "max_ms": round(max(walls), 3),
            "compile_s": round(compile_s, 2), "planned_bytes": planned}
    print(json.dumps(line), flush=True)
    return line, out


def same(a, b) -> bool:
    """Two packed batches hold the same rows in the same places (a lane
    is compared where the place is active)."""
    act = np.asarray(a.active)
    if not np.array_equal(act, np.asarray(b.active)):
        return False
    for x, y in zip(jax.tree_util.tree_leaves(a.columns),
                    jax.tree_util.tree_leaves(b.columns)):
        if not np.array_equal(np.asarray(x)[act], np.asarray(y)[act]):
            return False
    return True


def one_chip(rows: int, lines: list):
    batch = jax.device_put(shard(rows, 0))
    slot_new = X.slot_for(rows, N)
    want = {}
    for slot, label in ((rows, "capacity"), (slot_new, "slot_for")):
        line, out = timed(f"as_is({label}={slot})",
                          lambda b, s=slot: as_is_pack(b, N, s), batch)
        lines.append(line)
        want[slot] = out
    del want[rows]  # 4 x capacity rows a lane: let it go
    for name, fn in (("ranked", ranked_pack), ("sorted", sorted_pack)):
        line, out = timed(f"{name}(slot_for={slot_new})",
                          lambda b, f=fn: f(b, N, slot_new), batch)
        line["equals_as_is"] = same(out, want[slot_new])
        print(json.dumps({"form": line["form"],
                          "equals_as_is": line["equals_as_is"]}), flush=True)
        lines.append(line)
        del out
    want.clear()
    dest = jax.block_until_ready(jax.jit(
        lambda b: destinations(b, N))(batch))
    idx = jnp.arange(rows, dtype=jnp.int32)
    parts = [
        ("part: sort (dest, index)",
         lambda d: jax.lax.sort([d, idx], num_keys=1), dest),
        ("part: searchsorted of n + 1 starts",
         lambda d: jnp.searchsorted(jnp.sort(d), jnp.arange(
             N + 1, dtype=jnp.int32)), dest),
        ("part: running sums, one a destination",
         lambda d: ranked_place(d, N, slot_new), dest),
    ]
    for name, fn, arg in parts:
        lines.append(timed(name, fn, arg)[0])
    perm, place_sorted = jax.block_until_ready(jax.jit(
        lambda d: as_is_place(d, N, slot_new))(dest))
    place = jax.block_until_ready(jax.jit(
        lambda d: ranked_place(d, N, slot_new))(dest))
    lane = batch.column(1).values
    word = jax.lax.bitcast_convert_type(lane, jnp.uint32)
    send = N * slot_new
    for name, fn, args in (
            ("part: one int32 lane gathered by the sort's permutation",
             lambda a, p: a[p], (lane, perm)),
            ("part: one int32 lane scattered, indices sorted (as_is)",
             lambda a, i: jnp.zeros(send + 1, a.dtype).at[i].set(a)[:send],
             (lane, place_sorted)),
            ("part: one bool mask scattered, indices sorted (as_is)",
             lambda a, i: jnp.zeros(send + 1, a.dtype).at[i].set(a)[:send],
             (batch.active, place_sorted)),
            ("part: one 32-bit word scattered, unique unsorted (ranked)",
             lambda a, i: jnp.zeros(send, a.dtype).at[i].set(
                 a, mode="drop", unique_indices=True), (word, place)),
            ("part: one 32-bit word gathered from the sorted order",
             lambda a, i: a[jnp.clip(i, 0, rows - 1)], (word, place))):
        lines.append(timed(name, fn, *args)[0])


def four_chips(rows: int, lines: list):
    mesh = make_mesh(N)
    spec = NamedSharding(mesh, P(WORKERS_AXIS))
    host = [shard(rows, k) for k in range(N)]
    batch = jax.tree_util.tree_map(
        lambda *xs: jax.make_array_from_single_device_arrays(
            (N * rows,) + xs[0].shape[1:], spec,
            [jax.device_put(x, d) for x, d in zip(xs, mesh.devices.flat)]),
        *host)
    slot_new = X.slot_for(rows, N)

    def whole(pack, slot):
        def run(b):
            sent = pack(b, N, slot)
            return jax.tree_util.tree_map(
                lambda x: jax.lax.all_to_all(x, WORKERS_AXIS, 0, 0,
                                             tiled=True), sent)
        return jax.shard_map(run, mesh=mesh, in_specs=(P(WORKERS_AXIS),),
                             out_specs=P(WORKERS_AXIS), check_vma=False)

    def engine(b):  # the program's own function, counters and all
        out, overflow = X.exchange_by_hash(b, [0], WORKERS_AXIS, slot_new)
        return out, jax.lax.psum(overflow.astype(jnp.int32), WORKERS_AXIS)

    # the parent's slots last: on the chips that form ended the process
    # without a line in PR 34 (send buffers of 180M rows a lane)
    forms = [
        (f"exchange as_is(slot_for={slot_new})", whole(as_is_pack, slot_new)),
        (f"exchange ranked(slot_for={slot_new})",
         whole(ranked_pack, slot_new)),
        (f"exchange_by_hash of the program (slot_for={slot_new})",
         jax.shard_map(engine, mesh=mesh, in_specs=(P(WORKERS_AXIS),),
                       out_specs=(P(WORKERS_AXIS), P()), check_vma=False)),
        (f"exchange as_is(capacity={rows})", whole(as_is_pack, rows)),
    ]
    want = None
    for name, fn in forms:
        line, out = timed(name, fn, batch)
        if isinstance(out, tuple):
            line["overflow"] = int(out[1])
            out = out[0]
        if "capacity" in name:
            del out
        elif want is None:
            want = out
        else:
            line["equals_as_is"] = same(out, want)
            print(json.dumps({"form": name,
                              "equals_as_is": line["equals_as_is"]}),
                  flush=True)
        lines.append(line)
    sent = jax.block_until_ready(jax.jit(jax.shard_map(
        lambda b: ranked_pack(b, N, slot_new), mesh=mesh,
        in_specs=(P(WORKERS_AXIS),), out_specs=P(WORKERS_AXIS),
        check_vma=False))(batch))
    lines.append(timed(
        "part: all_to_all alone, the packed lanes and masks",
        jax.shard_map(lambda b: jax.tree_util.tree_map(
            lambda x: jax.lax.all_to_all(x, WORKERS_AXIS, 0, 0, tiled=True),
            b), mesh=mesh, in_specs=(P(WORKERS_AXIS),),
            out_specs=P(WORKERS_AXIS), check_vma=False), sent)[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=45_000_000,
                    help="rows of one chip's shard")
    ap.add_argument("--chips", type=int, default=None, choices=(1, 4))
    ap.add_argument("--out", default=None)
    ap.add_argument("--allow-cpu", action="store_true")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu" and not args.allow_cpu:
        print("needs a TPU: a CPU time is no device number", file=sys.stderr)
        return 3
    chips = args.chips or (N if len(devices) >= N else 1)
    lines = []
    head = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "devices": len(devices), "chips": chips, "rows": args.rows,
            "destinations": N, "slot_for": X.slot_for(args.rows, N)}
    print(json.dumps(head), flush=True)
    (four_chips if chips == N else one_chip)(args.rows, lines)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({**head, "forms": lines}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
