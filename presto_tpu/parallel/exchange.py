"""Partitioned exchange over ICI: the shuffle data plane.

Reference surface: operator/repartition/PartitionedOutputOperator.java:394
(hash rows -> per-partition buffers -> outputBuffer.enqueue:484) and the
consumer side operator/ExchangeClient.java:255 (HTTP long-poll pull of
SerializedPages with token acks). The TPU-native redesign (SURVEY.md
§2.3, §5 "north star") replaces the serialize->HTTP->deserialize hop
with `jax.lax.all_to_all` between gang-scheduled stages on the mesh:
rows hash to a destination worker, get packed into fixed-size per-
destination send slots in HBM, and one collective moves every slot to
its owner -- no host round-trip, no wire format, backpressure becomes a
static slot-capacity overflow flag (exec reruns with a bigger bucket,
the maxBufferedBytes analog).

All functions here must run INSIDE shard_map over the workers axis.
"""

from __future__ import annotations

import contextlib
import math
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..block import (Batch, Block, Column, DictionaryColumn, Int128Column,
                     StringColumn)
from ..expr.functions import combine_hash, hash64_block
from ..ops.join import _running_sum
from ..ops.keys import lex_sort

__all__ = ["exchange_by_hash", "exchange_by_range", "broadcast_build",
           "gather_to_root", "slot_for", "ExchangeLog", "logging_exchanges"]

# A hash exchange's slots start at this share over an even split of the
# sender's capacity. What matters is that only active rows take a place
# in a slot: a filter's or a join's dropped rows never leave their chip.
# Over what is left, a hash spreads rows evenly to well under a percent
# at the sizes where a slot's size matters (a million rows a destination
# stray by a thousand), so a quarter is room for a batch that is nearly
# full; `_SLOT_FLOOR` rows more cover the small tables, where a handful
# of groups can all hash to one chip. A slot that overflows sets the
# status word's slot bit and the runner's ladder reruns at twice the
# slots (clamped at the sender's capacity, where nothing can overflow).
SLOT_HEADROOM = 1.25
# A range exchange's splitters come from 64 samples a worker: a range's
# share lies within tens of percent of even, not within one.
RANGE_HEADROOM = 2.0
_SLOT_FLOOR = 64


def slot_for(sender_capacity: int, n_workers: int,
             headroom: float = SLOT_HEADROOM) -> int:
    """Rows a sender keeps for each destination, sized from its own
    shard: the receiver's capacity is `n_workers` of these, a little
    over the sender's, where a slot as large as the sender's whole
    capacity made it `n_workers` times that."""
    even = math.ceil(headroom * sender_capacity / max(n_workers, 1))
    slot = -(-(even + _SLOT_FLOOR) // 8) * 8
    return max(min(slot, sender_capacity), 1)


class ExchangeLog:
    """What the exchanges of one traced program are: constants of its
    shapes (how many of each kind, the bytes one chip's collectives
    move) and, traced, the bytes of rows the hash and range exchanges
    really routed. `compile_plan` keeps the constants with the
    `CompiledPlan` by argument shapes, so a statement served by a cached
    program reports what the one that traced it did."""

    def __init__(self):
        self.kinds: Dict[str, int] = {}
        self.moved_bytes = 0      # slots and gathered copies, one chip
        self.slot_bytes = 0       # the hash and range exchanges' share
        self.routed: List = []    # traced: active rows routed x row bytes

    def counters(self) -> Dict[str, int]:
        out = {"exchanges": sum(self.kinds.values()),
               "exchange_bytes": self.moved_bytes,
               "exchange_slot_bytes": self.slot_bytes}
        out.update({f"exchange.{k}": v for k, v in self.kinds.items()})
        return out


_ambient = threading.local()


@contextlib.contextmanager
def logging_exchanges(log: ExchangeLog):
    """The exchanges lowered inside the block note themselves on `log`."""
    before = getattr(_ambient, "log", None)
    _ambient.log = log
    try:
        yield log
    finally:
        _ambient.log = before


def _note_exchange(kind: str, axis_name: str, moved_bytes: int,
                   routed=None) -> None:
    """Every exchange notes itself where it is lowered: its kind, the
    bytes of slots or gathered copies one chip's collective moves (a
    constant of the shapes) and, for a hash or range exchange, the
    traced count of bytes of rows it really routes. Under
    `compile_plan` that goes to the program's `ExchangeLog`, which the
    compiled plan keeps: a statement reports its program's exchanges
    (`exchanges`, `exchange.<kind>`, `exchange_bytes`,
    `exchange_row_bytes`) on a plan-cache hit as on the statement that
    traced it. An exchange lowered outside a compiled plan
    (`parallel/stages.py` called by hand) counts its kind on the ambient
    collector, once, where it is traced."""
    log: Optional[ExchangeLog] = getattr(_ambient, "log", None)
    if log is not None:
        log.kinds[kind] = log.kinds.get(kind, 0) + 1
        log.moved_bytes += moved_bytes
        if routed is not None:
            log.slot_bytes += moved_bytes
            log.routed.append(routed)
        return
    from ..exec.stats import current_collector
    c = current_collector()
    if c is not None:
        c.note(f"exchange.{kind}")
        c.note("exchanges")
        # exchange shape is a silent plan decision a post-mortem wants
        # on the timeline
        from ..server.flight_recorder import record_event
        record_event("exchange_shape", query_id=c.query_id,
                     shape=kind, axis=axis_name)


def _row_bytes(batch: Batch) -> int:
    """Bytes one row of `batch` takes over all its lanes and masks."""
    return sum(x.dtype.itemsize * int(np.prod(x.shape[1:], dtype=np.int64))
               for x in jax.tree_util.tree_leaves(batch))




def _row_hash(cols: Sequence[Block]) -> jnp.ndarray:
    h = None
    for c in cols:
        if isinstance(c, DictionaryColumn):
            c = c.decode()
        hc = hash64_block(c)
        h = hc if h is None else combine_hash(h, hc)
    return h


def _map_block(b: Block, fn) -> Block:
    if isinstance(b, DictionaryColumn):
        b = b.decode()
    if isinstance(b, StringColumn):
        return StringColumn(fn(b.chars), fn(b.lengths), fn(b.nulls), b.type)
    if isinstance(b, Int128Column):
        return Int128Column(fn(b.hi), fn(b.lo), fn(b.nulls), b.type)
    from ..block import ArrayColumn, MapColumn, RowColumn
    if isinstance(b, ArrayColumn):
        return ArrayColumn(fn(b.elements), fn(b.elem_nulls), fn(b.lengths),
                           fn(b.nulls), b.type)
    if isinstance(b, MapColumn):
        return MapColumn(fn(b.keys), fn(b.values), fn(b.value_nulls),
                         fn(b.lengths), fn(b.nulls), b.type)
    if isinstance(b, RowColumn):
        return RowColumn(tuple(_map_block(f, fn) for f in b.fields),
                         fn(b.nulls), b.type)
    return Column(fn(b.values), fn(b.nulls), b.type)


@jax.named_scope("exchange_by_hash")
def exchange_by_hash(batch: Batch, key_channels: Sequence[int], axis_name: str,
                     slot_capacity: int) -> Tuple[Batch, jnp.ndarray]:
    """All-to-all repartition by key hash (call inside shard_map).

    Every worker packs its active rows into `n_workers` buckets of
    `slot_capacity` rows each and exchanges bucket i with worker i. The
    returned batch has capacity n_workers * slot_capacity and holds all
    rows whose keys hash to this worker. Also returns an `overflow` flag
    (any source bucket exceeded slot_capacity; rows beyond it dropped --
    exec layer must retry with a bigger bucket).

    Hash routing matches the reference's HashPartitionFunction: workers
    see disjoint key sets, so downstream per-worker group-by/join is
    exact (SystemPartitioningHandle FIXED_HASH_DISTRIBUTION).
    """
    n = jax.lax.psum(1, axis_name)
    h = _row_hash([batch.column(c) for c in key_channels])
    dest = (h % jnp.uint64(n)).astype(jnp.int32)
    dest = jnp.where(batch.active, dest, n)  # inactive rows -> dropped bucket
    out, overflow, routed = _route_rows(batch, dest, n, axis_name,
                                        slot_capacity)
    width = _row_bytes(batch)
    _note_exchange("hash", axis_name, n * slot_capacity * width,
                   routed.astype(jnp.int64) * width)
    return out, overflow


_SMALL = {1: jnp.uint8, 2: jnp.uint16}


def _pack_lanes(leaves):
    """The integer and boolean lanes of `leaves` (1-D, one a row) as
    32-bit words, a row's bits side by side: a 64-bit lane is two words
    (a 64-bit gather or scatter costs the chip two 32-bit ones anyway),
    a 32-bit lane one, and the 16-bit, 8-bit and boolean lanes share
    words, a mask taking a bit. Moving a row is an index a word, where
    it was an index a lane and a mask (PERF.md, PR 34). Returns the
    lanes to move (the words, then every leaf that is not packed: floats
    and the matrices of strings, as they are) and the function that
    takes the moved lanes apart again."""
    words, plan, loose = [], [], []
    small = []  # (leaf index, bits)
    for i, x in enumerate(leaves):
        kind, size = x.dtype.kind, x.dtype.itemsize
        if x.ndim != 1 or kind not in "iub":
            plan.append(("loose", len(loose)))
            loose.append(x)
        elif size == 8:
            u = jax.lax.bitcast_convert_type(x, jnp.uint64)
            plan.append(("wide", len(words)))
            words.append((u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
            words.append((u >> jnp.uint64(32)).astype(jnp.uint32))
        elif size == 4:
            plan.append(("word", len(words)))
            words.append(jax.lax.bitcast_convert_type(x, jnp.uint32))
        else:
            plan.append(None)  # placed below
            small.append((i, 1 if kind == "b" else 8 * size))
    # first fit, widest first: a word holds 32 bits of small lanes
    room: List[int] = []      # bits taken in each shared word
    shared: List = []         # the shared words, built up by OR
    for i, bits in sorted(small, key=lambda f: -f[1]):
        x = leaves[i]
        u = x.astype(jnp.uint32) if x.dtype.kind == "b" else \
            jax.lax.bitcast_convert_type(
                x, _SMALL[x.dtype.itemsize]).astype(jnp.uint32)
        k = next((k for k, taken in enumerate(room) if taken + bits <= 32),
                 len(room))
        if k == len(room):
            room.append(0)
            shared.append(jnp.zeros_like(u))
        shared[k] = shared[k] | (u << jnp.uint32(room[k]))
        plan[i] = ("bits", k, room[k], bits)
        room[k] += bits
    n_words = len(words)
    lanes = words + shared + loose

    def unpack(moved):
        out = []
        for x, how in zip(leaves, plan):
            if how[0] == "loose":
                out.append(moved[n_words + len(shared) + how[1]])
            elif how[0] == "wide":
                lo, hi = moved[how[1]], moved[how[1] + 1]
                u = lo.astype(jnp.uint64) | (hi.astype(jnp.uint64)
                                             << jnp.uint64(32))
                out.append(jax.lax.bitcast_convert_type(u, x.dtype))
            elif how[0] == "word":
                out.append(jax.lax.bitcast_convert_type(moved[how[1]],
                                                        x.dtype))
            else:
                _, k, at, bits = how
                u = (moved[n_words + k] >> jnp.uint32(at)) \
                    & jnp.uint32((1 << bits) - 1)
                if x.dtype.kind == "b":
                    out.append(u != 0)
                else:
                    out.append(jax.lax.bitcast_convert_type(
                        u.astype(_SMALL[x.dtype.itemsize]), x.dtype))
        return out

    return lanes, unpack


def _slot_places(dest: jnp.ndarray, n: int, slot_capacity: int):
    """Where each row goes in a send buffer of `n` slots: its
    destination's slot, at its rank among the rows before it that go
    the same way (a running sum a destination, `ops/join._running_sum`:
    no sort). A row that stays behind (dest == n, or beyond a full
    slot) gets an index of its own past the buffer's end, which a
    scatter in "drop" mode leaves out. Also the overflow flag and the
    rows placed (int32)."""
    place = n * slot_capacity + jnp.arange(dest.shape[0], dtype=jnp.int32)
    overflow = jnp.zeros((), dtype=bool)
    routed = jnp.zeros((), dtype=jnp.int32)
    for d in range(n):
        to_d = dest == d
        rank = _running_sum(to_d.astype(jnp.int32))  # 1 for the first
        count = rank[-1]
        overflow = overflow | (count > slot_capacity)
        routed = routed + jnp.minimum(count, slot_capacity)
        place = jnp.where(to_d & (rank <= slot_capacity),
                          d * slot_capacity + rank - 1, place)
    return place, overflow, routed


@jax.named_scope("_route_rows")
def _route_rows(batch: Batch, dest: jnp.ndarray, n, axis_name: str,
                slot_capacity: int):
    """Pack rows into per-destination send slots and all_to_all them.
    `dest` is an int32 per-row destination in [0, n); rows with dest == n
    are dropped (inactive). Shared data plane of the hash and range
    exchanges. Returns the received batch (capacity n * slot_capacity),
    the overflow flag and the rows this chip routed (int32).

    A row's place comes from `_slot_places`; the batch's lanes and
    masks are packed into 32-bit words (`_pack_lanes`) and each word is
    scattered once into the send buffer."""
    cap = batch.capacity
    send_rows = n * slot_capacity
    place, overflow, routed = _slot_places(dest, n, slot_capacity)

    leaves, treedef = jax.tree_util.tree_flatten(batch.with_active(
        jnp.ones(cap, dtype=bool)))
    lanes, unpack = _pack_lanes(leaves)

    def move(lane):
        sent = jnp.zeros((send_rows,) + lane.shape[1:], dtype=lane.dtype) \
            .at[place].set(lane, mode="drop", unique_indices=True)
        return jax.lax.all_to_all(sent, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)

    # the `active` lane was set to ones before packing: a place that no
    # row was scattered to reads 0 in every lane, so it arrives inactive
    out = jax.tree_util.tree_unflatten(
        treedef, unpack([move(lane) for lane in lanes]))
    return out, overflow, routed


@jax.named_scope("exchange_by_range")
def exchange_by_range(batch: Batch, sort_keys, axis_name: str,
                      slot_capacity: int,
                      samples_per_worker: int = 64
                      ) -> Tuple[Batch, jnp.ndarray]:
    """Sampled range repartition by sort keys (call inside shard_map):
    worker d receives the d-th key range, so locally sorting each
    worker's slice afterwards yields a GLOBALLY sorted distributed
    result -- the full row set never lands on one device. This is the
    TPU-native replacement for the gather-then-sort rule and the mesh
    lowering of the MERGE exchange (MergeOperator.java:45; splitter
    sampling mirrors the reference's range-partitioning sampler in
    spirit, but runs inside the compiled SPMD program).

    Rows comparing equal on the full key tuple land on one worker
    (splitter comparison is lexicographic over the same order-preserving
    key words the sort uses), so ordering ties never straddle a worker
    boundary. Heavy key skew shows up as bucket overflow -> the usual
    rerun-with-bigger-slots policy.
    """
    from ..ops.sort import _column_words
    n = jax.lax.psum(1, axis_name)
    cap = batch.capacity
    words: list = []
    for sk in sort_keys:
        words.extend(_column_words(batch.column(sk[0]), sk[1], sk[2]))
    nw = len(words)

    # draw evenly spaced samples from the locally ordered active rows
    act_word = jnp.where(batch.active, jnp.uint64(0), jnp.uint64(1))
    local_sorted = lex_sort([act_word] + words, num_keys=1 + nw)[1:]
    count = jnp.sum(batch.active.astype(jnp.int64))
    s = samples_per_worker
    pos = ((jnp.arange(s, dtype=jnp.int64) * 2 + 1) * count) // (2 * s)
    pos = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)
    full = jnp.uint64(0xFFFFFFFFFFFFFFFF)
    samp = [jnp.where(count > 0, w[pos], full) for w in local_sorted]

    # global splitters: gather + sort all workers' samples, take n-1
    # quantiles (lexicographic over the word tuple)
    gathered = [jax.lax.all_gather(w, axis_name, axis=0, tiled=True)
                for w in samp]
    gsorted = jax.lax.sort(gathered, num_keys=nw)
    spos = jnp.arange(s, n * s, s, dtype=jnp.int32)  # (n-1,) quantiles
    splitters = [w[spos] for w in gsorted]  # each (n-1,)

    # dest = #splitters <= row, compared lexicographically word by word
    ge = jnp.ones((max(n - 1, 0), cap), dtype=bool)
    for w_r, w_s in zip(reversed(words), reversed(splitters)):
        r, sv = w_r[None, :], w_s[:, None]
        ge = (r > sv) | ((r == sv) & ge)
    dest = jnp.sum(ge, axis=0, dtype=jnp.int32)
    dest = jnp.where(batch.active, dest, n)
    out, overflow, routed = _route_rows(batch, dest, n, axis_name,
                                        slot_capacity)
    width = _row_bytes(batch)
    _note_exchange("range", axis_name, n * slot_capacity * width,
                   routed.astype(jnp.int64) * width)
    return out, overflow


def _all_gather(batch: Batch, axis_name: str) -> Batch:
    def ag(arr):
        return jax.lax.all_gather(arr, axis_name, axis=0, tiled=True)
    cols = tuple(_map_block(c, ag) for c in batch.columns)
    return Batch(cols, ag(batch.active))


@jax.named_scope("broadcast_build")
def broadcast_build(batch: Batch, axis_name: str) -> Batch:
    """Replicate a (typically small) build-side batch to every worker:
    the FIXED_BROADCAST_DISTRIBUTION / BroadcastOutputBuffer analog, as
    an all_gather over ICI. Output capacity = n_workers * capacity."""
    n = jax.lax.psum(1, axis_name)
    _note_exchange("broadcast", axis_name,
                   n * batch.capacity * _row_bytes(batch))
    return _all_gather(batch, axis_name)


@jax.named_scope("gather_to_root")
def gather_to_root(batch: Batch, axis_name: str) -> Batch:
    """Gather all workers' rows everywhere (root picks its copy): the
    single-node SINGLE_DISTRIBUTION output stage / coordinator result
    fetch analog."""
    n = jax.lax.psum(1, axis_name)
    _note_exchange("gather", axis_name,
                   n * batch.capacity * _row_bytes(batch))
    return _all_gather(batch, axis_name)
