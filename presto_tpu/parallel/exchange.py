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

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..block import (Batch, Block, Column, DictionaryColumn, Int128Column,
                     StringColumn)
from ..expr.functions import combine_hash, hash64_block
from ..ops.keys import lex_sort

__all__ = ["exchange_by_hash", "exchange_by_range", "broadcast_build",
           "gather_to_root"]


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


def exchange_by_hash(batch: Batch, key_channels: Sequence[int], axis_name: str,
                     slot_capacity: int) -> Tuple[Batch, jnp.ndarray]:
    """All-to-all repartition by key hash (call inside shard_map).

    Every worker packs its rows into `n_workers` buckets of
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
    return _route_rows(batch, dest, n, axis_name, slot_capacity)


def _route_rows(batch: Batch, dest: jnp.ndarray, n, axis_name: str,
                slot_capacity: int) -> Tuple[Batch, jnp.ndarray]:
    """Pack rows into per-destination send slots and all_to_all them.
    `dest` is an int32 per-row destination in [0, n); rows with dest == n
    are dropped (inactive). Shared data plane of the hash and range
    exchanges."""
    cap = batch.capacity
    # slot within destination bucket: rank among same-dest rows
    order = jax.lax.sort([dest, jnp.arange(cap, dtype=jnp.int32)], num_keys=1)
    s_dest, perm = order
    bucket_start = jnp.searchsorted(s_dest, jnp.arange(n + 1, dtype=jnp.int32))
    pos_in_sorted = jnp.arange(cap, dtype=jnp.int32)
    slot = pos_in_sorted - bucket_start[jnp.clip(s_dest, 0, n)]
    counts = bucket_start[1:] - bucket_start[:-1]  # per-dest counts (n,)
    overflow = jnp.any(counts > slot_capacity)

    send_rows = n * slot_capacity
    flat = jnp.clip(s_dest, 0, n - 1) * slot_capacity + jnp.clip(slot, 0, slot_capacity - 1)
    keep = (s_dest < n) & (slot < slot_capacity)
    # dropped/overflowed rows park in an extra scratch slot that is
    # sliced away -- never a real slot (scatter order is unspecified)
    idx = jnp.where(keep, flat, send_rows)

    def pack(arr):
        # arr: (cap, ...) in original row order -> (send_rows, ...) bucketed
        src = arr[perm]
        zeros = jnp.zeros((send_rows + 1,) + arr.shape[1:], dtype=arr.dtype)
        return zeros.at[idx].set(src)[:send_rows]

    sent_active = jnp.zeros(send_rows + 1, dtype=bool).at[idx].set(True)[:send_rows]

    def a2a(arr):
        return jax.lax.all_to_all(arr, axis_name, split_axis=0, concat_axis=0,
                                  tiled=True)

    new_cols = tuple(_map_block(c, lambda a: a2a(pack(a))) for c in batch.columns)
    new_active = a2a(sent_active)
    return Batch(new_cols, new_active), overflow


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
    return _route_rows(batch, dest, n, axis_name, slot_capacity)


def broadcast_build(batch: Batch, axis_name: str) -> Batch:
    """Replicate a (typically small) build-side batch to every worker:
    the FIXED_BROADCAST_DISTRIBUTION / BroadcastOutputBuffer analog, as
    an all_gather over ICI. Output capacity = n_workers * capacity."""
    def ag(arr):
        g = jax.lax.all_gather(arr, axis_name, axis=0, tiled=True)
        return g
    cols = tuple(_map_block(c, ag) for c in batch.columns)
    return Batch(cols, ag(batch.active))


def gather_to_root(batch: Batch, axis_name: str) -> Batch:
    """Gather all workers' rows everywhere (root picks its copy): the
    single-node SINGLE_DISTRIBUTION output stage / coordinator result
    fetch analog."""
    return broadcast_build(batch, axis_name)
