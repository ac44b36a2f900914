"""Joins: the HashBuilderOperator / LookupJoinOperator analog.

Reference surface: operator/HashBuilderOperator.java:55 (build side ->
LookupSource), operator/LookupJoinOperator.java:52 (probe loop),
JoinCompiler's generated hash strategies, and the join plan nodes
(JoinNode INNER/LEFT/RIGHT/FULL, SemiJoinNode).

TPU-first redesign: no pointer-chasing hash table. The build side is
SORTED by key words once (O(n log n) on device), and a probe row looks
its key up ONCE (`_match_ranges`): a bucket directory over the sorted
keys, indexed by the high bits of key - min (Velox HashTable's array
mode). Where the keys' span fits the directory (a bucket is one key
value: primary keys, dense ranks) the directory IS the answer, packed
one int32 a bucket, and a probe row costs one 32-bit gather; where it
does not, the directory brackets the key to a handful of build rows, a
binary search runs only as deep as the fullest bracket needs (never
more than log n), and the end of the match range is read off the build
side's run ends: three or four gathers. The device picks by its own
directory's shift. A gather pass over the probe is the unit of cost on
the chip. 1:N matches expand through a static-capacity prefix-sum
expansion:

  start[i], end[i] = _match_ranges(build, probe_i)
  cnt[i]   = end - start  (0 for null/missing keys)
  off      = exclusive_cumsum(cnt)
  out row k maps back to probe row row[k] = (#i : off[i] <= k) - 1
  (`_slot_rows`), and to build row start[row] + (k - off[row])

The slots are arange(out_capacity): dense, sorted, known at trace time.
So a slot finds its row without a search of the whole table: offsets
clipped to the capacity in int32, the first offset of every block of
n / out_capacity rows counted into the slots (a histogram of about
out_capacity updates and one cumsum) to give each slot its block, and
log2(block) gathers of one 32-bit lane inside the block. What is gathered per slot afterwards
(off, emit, cnt, start) is int32 too.

What a probe row costs: nothing but a place in one prefix sum, where
the rows that can emit a slot (usable key; under LEFT / FULL, active)
are few. A filter upstream leaves most of a fact table inactive (TPC-H
Q14: 1.2% of lineitem pass l_shipdate), and a row that cannot emit
needs no lookup. Where the probe is at least four times as long as
the output (`_compact_capacity`, shapes alone) the program holds two
forms of the probe side (`_probe_side`) in a `cond` on the count of
emitting rows, which the device sees and the host never reads: where
they fit the output capacity, `_slot_rows` over the mask's prefix sum
names the k-th emitting row (`_compact_probe`), its key columns are
gathered, and lookup, running sum and expansion run over out_capacity
rows (`_probe_slots`, the same function at another length; the
expansion's table is then no longer than its output: blocks of one
row); where they do not, every probe row is looked up as above. Slots
fill in probe row order either way, so the two answer alike slot for
slot. A probe that does not fit is no overflow: nothing reruns.

Everything is a fixed-shape gather -- the dynamic result size only
shows up in the output's active mask and an `overflow` flag when the
out_capacity bucket is too small (exec layer re-runs bigger, the
LookupJoinOperator yield/rebatch analog).

Sort order on multiple words: lexicographic. The lookup works on a
single key, so a multi-word tuple is first reduced to a single
total-order rank (`_pack_ranks`: one union sort per word), and the
dense ranks go through the same lookup. The common one-word case
(bigint keys) looks its word up directly.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..block import Batch, Block, Column, DictionaryColumn, StringColumn
from .keys import key_words, lex_sort

__all__ = ["hash_join", "JoinResult", "semi_join_mask"]


@dataclasses.dataclass
class JoinResult:
    batch: Batch          # probe columns ++ build columns
    num_rows: jnp.ndarray
    overflow: jnp.ndarray
    search_steps: jnp.ndarray  # binary-search trips the lookups took
    compacted: jnp.ndarray  # 1 where the probe took the compacted form
    direct: jnp.ndarray  # lookups the directory answered alone
    expand_steps: int = 0  # gather trips a slot of `_slot_rows` took


jax.tree_util.register_dataclass(JoinResult,
                                 data_fields=["batch", "num_rows", "overflow",
                                              "search_steps", "compacted",
                                              "direct"],
                                 meta_fields=["expand_steps"])


def _pad_chars(c: StringColumn, width: int) -> StringColumn:
    if c.chars.shape[1] == width:
        return c
    return StringColumn(jnp.pad(c.chars,
                                ((0, 0), (0, width - c.chars.shape[1]))),
                        c.lengths, c.nulls, c.type)


def _align_key_widths(p_keys: Sequence[Block], b_keys: Sequence[Block]):
    """String key columns on the two sides may declare different widths
    (ca_county vs s_county): their key words would then disagree in
    COUNT and the multi-word lexicographic search compares misaligned
    words. Pad the narrower side per column so both sides build
    identical word layouts."""
    out_p, out_b = [], []
    for pc, bc in zip(p_keys, b_keys):
        pd = pc.decode() if isinstance(pc, DictionaryColumn) else pc
        bd = bc.decode() if isinstance(bc, DictionaryColumn) else bc
        if isinstance(pd, StringColumn) and isinstance(bd, StringColumn):
            w = max(pd.chars.shape[1], bd.chars.shape[1])
            pd, bd = _pad_chars(pd, w), _pad_chars(bd, w)
        out_p.append(pd)
        out_b.append(bd)
    return out_p, out_b


def _combined_key(cols: Sequence[Block], active) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Reduce a key tuple to sortable words; returns (words stacked as a
    (k, n) list, usable_mask). Null keys never match in joins."""
    words, any_null = key_words(cols)
    # drop per-column null words (null keys are excluded wholesale)
    usable = active & ~any_null
    vwords = []
    i = 0
    for c in cols:
        if isinstance(c, DictionaryColumn):
            c = c.decode()
        nw = 1 + ((c.max_len + 7) // 8 if isinstance(c, StringColumn) else 1)
        vwords.extend(words[i + 1: i + nw])  # skip the null word
        i += nw
    return vwords, usable


_MAXW = np.uint64(0xFFFFFFFFFFFFFFFF)


@jax.named_scope("_sort_build")
def _sort_build(b_words: List[jnp.ndarray], b_usable: jnp.ndarray,
                payload: Optional[jnp.ndarray]):
    """Sort build rows so the word arrays are globally sorted AND
    searchsorted-safe: unusable rows have all words forced to MAX so
    they sink to the end without breaking sortedness; within equal
    words, usable rows sort first (trailing tiebreak) so clamping
    match ranges to n_usable keeps exactly the genuine rows."""
    masked = [jnp.where(b_usable, w, _MAXW) for w in b_words]
    tiebreak = jnp.where(b_usable, np.uint64(0), np.uint64(1))
    ops = [*masked, tiebreak]
    if payload is not None:
        ops.append(payload)
    out = lex_sort(ops, num_keys=len(masked) + 1)
    sorted_words = out[:len(masked)]
    sorted_payload = out[-1] if payload is not None else None
    return sorted_words, sorted_payload


@jax.named_scope("_pack_ranks")
def _pack_ranks(build_words: List[jnp.ndarray], probe_words: List[jnp.ndarray]):
    """Reduce multi-word keys to single int64 ranks, exactly.

    Build side: sort rows by words; the rank of a build row is its dense
    key index (cumsum of boundaries). Probe side: for each level,
    compute the probe's position among build keys by searchsorted on
    that level *given* the accumulated equality on previous levels --
    implemented by mapping (prev_rank, word) pairs to a fresh dense rank
    via another sort over the union. Cost: O((b+p) log(b+p)) per word.
    """
    nb = build_words[0].shape[0]
    npr = probe_words[0].shape[0]
    b_rank = jnp.zeros(nb, dtype=jnp.int64)
    p_rank = jnp.zeros(npr, dtype=jnp.int64)
    for bw, pw in zip(build_words, probe_words):
        # union sort of (rank, word, is_probe, idx)
        ranks = jnp.concatenate([b_rank, p_rank])
        words = jnp.concatenate([bw, pw])
        is_probe = jnp.concatenate([jnp.zeros(nb, dtype=jnp.uint64),
                                    jnp.ones(npr, dtype=jnp.uint64)])
        idx = jnp.arange(nb + npr, dtype=jnp.int32)
        r, w, tag, pi = lex_sort(
            [ranks.astype(jnp.uint64), words, is_probe, idx], num_keys=3)
        # dense rank over (rank, word) pairs
        boundary = (r != jnp.concatenate([r[:1], r[:-1]])) | \
                   (w != jnp.concatenate([w[:1], w[:-1]]))
        boundary = boundary.at[0].set(False)
        dense = jnp.cumsum(boundary.astype(jnp.int64))
        new = jnp.zeros(nb + npr, dtype=jnp.int64).at[pi].set(dense)
        b_rank, p_rank = new[:nb], new[nb:]
    return b_rank, p_rank


def _halves(words: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """uint64 words as (high, low) uint32 lanes."""
    return (words >> 32).astype(jnp.uint32), words.astype(jnp.uint32)


def _directory_bits(nb: int, spread: int) -> int:
    """log2 of the buckets `_match_ranges`' directory has over `nb`
    build rows: 2**ceil(log2 nb), times `spread` (rounded up to a power
    of two) where the build side is one of `spread` hash shares of its
    keys (it reached the join through `exchange_by_hash`, so its keys
    lie about `spread` apart over the whole span)."""
    return max((nb - 1).bit_length(), 1) + (spread - 1).bit_length()


@jax.named_scope("_match_ranges")
def _match_ranges(sorted_keys: jnp.ndarray, n_usable: jnp.ndarray,
                  queries: jnp.ndarray, spread: int = 1):
    """The lookup that turns a probe key into its match range. For each
    query, [start, end) are the positions of its key among the first
    `n_usable` rows of `sorted_keys` (uint64, ascending; the rows behind
    them are `_sort_build`'s MAX-masked tail and never match): the
    integers searchsorted(side="left") / (side="right") give once
    clamped to n_usable, as int32. Also returns `steps`, the
    binary-search trips taken, and `direct`, whether the directory
    answered alone (device scalars).

    1. A directory of D buckets (`_directory_bits`) over the usable
       keys' range: bucket(key) = (key - min) >> shift, monotone in the
       key, so directory[b] = first sorted position whose bucket is >= b,
       and a query's range lies inside [directory[b(q)],
       directory[b(q) + 1]]. O(nb + D), no pass over the queries.
    2. One gather a query from a table of D + 2 rows (a row for below
       the smallest key, one for past the largest). Where the span fits
       the directory (shift 0: a bucket is one key value, as for the
       primary keys TPC-H joins on, or dense ranks) and n << k fits 31
       bits, k the bits of the longest run, a row holds its bucket's
       range packed, start << k | (end - start): that is the answer,
       with no search trip (`steps` 0) and no second gather. Else it
       holds the bracket's low end, and 3-4 follow. The device chooses
       by its own shift; no host read.
    3. A lower-bound search inside the bracket, as deep as the fullest
       bracket needs: the log of the longest run of equal keys where
       they repeat, ceil(log2 nb) at worst (one far outlier: a plain
       search's depth); keys are read as two uint32 halves.
    4. `end` without a second search: the search remembers whether the
       row it settles on holds the query's key; if so its run of equal
       keys ends where the build side says (`run_end`, O(nb)), read in
       the one branch of a `cond` the direct form does not take.

    A pass over the queries costs by its gathers (7.6-9.8 ns an index
    for one 32-bit lane, 18.8 where the indices scatter over a 15M-row
    table; a uint64 lane 19-29): one a query in the direct form, four
    where the search takes one trip (PERF.md, PR 37: step 0's layouts;
    a row of two lanes, or a uint64 one, costs more)."""
    nb = sorted_keys.shape[0]
    if nb == 0:
        zero = jnp.zeros(queries.shape, dtype=jnp.int32)
        return (zero, zero, jnp.zeros((), dtype=jnp.int32),
                jnp.zeros((), dtype=bool))
    if nb == 1:
        # a second, tail row: XLA:CPU folds the cumsum of a ONE-update
        # scatter to a wrong constant (met with one query, jax 0.9)
        sorted_keys = jnp.concatenate([sorted_keys, sorted_keys])
        nb = 2
    log2d = _directory_bits(nb, spread)
    d = 1 << log2d
    n = n_usable.astype(jnp.int32)
    pos = jnp.arange(nb, dtype=jnp.int32)

    kmin = sorted_keys[0]
    kmax = sorted_keys[jnp.maximum(n - 1, 0)]
    span_bits = 64 - jax.lax.clz(kmax - kmin)
    shift = jnp.maximum(span_bits, log2d) - log2d  # uint64, as the keys

    def bucket(keys):
        return ((jnp.clip(keys, kmin, kmax) - kmin) >> shift) \
            .astype(jnp.int32)

    # the build rows' buckets ascend with their position, tail included
    # (clipped to kmax's bucket, weight 0); summed along rows, as a flat
    # cumsum over 2**26 buckets would compile for minutes (PERF.md, PR 29)
    hist = jnp.zeros(d + 1, dtype=jnp.int32).at[
        bucket(sorted_keys) + 1].add((pos < n).astype(jnp.int32),
                                     indices_are_sorted=True)
    directory = _running_sum(hist)
    fullest = jnp.max(directory[1:] - directory[:-1])
    k = 32 - jax.lax.clz(fullest)  # ceil(log2(fullest + 1))
    # a bucket is one key value, and n << k fits an int32's 31 bits
    direct = (shift == 0) & (k + 32 - jax.lax.clz(n) <= 31)

    # one gather a query: row 0 below the smallest key, b + 1 for bucket
    # b, D + 1 past the last. Directly the row holds start << k | (end -
    # start), the answer; else the bracket's low end (its high end is at
    # most `fullest` rows on, and the rows between the true one and that
    # belong to higher buckets: greater keys, so the search is the same)
    bits = jnp.where(direct, k, 0)
    length = jnp.concatenate([directory[1:] - directory[:-1],
                              jnp.zeros(1, dtype=jnp.int32)])
    table = jnp.concatenate([jnp.zeros(1, dtype=jnp.int32),
                             jnp.where(direct, (directory << k) | length,
                                       directory)])
    row = jnp.where(queries < kmin, 0, jnp.minimum(
        (queries - kmin) >> shift, d).astype(jnp.int32) + 1)
    packed = table[row]
    lo = packed >> bits
    hi = jnp.where(direct, lo + (packed & ((1 << bits) - 1)),
                   jnp.minimum(lo + fullest, n))
    high, low = _halves(sorted_keys)
    q_high, q_low = _halves(queries)

    def halve(_, state):
        lo, hi, hit = state
        mid = jnp.minimum((lo + hi) >> 1, nb - 1)  # == nb once closed
        k_high, k_low = high[mid], low[mid]
        left = (lo < hi) & ((k_high > q_high)
                            | ((k_high == q_high) & (k_low >= q_low)))
        return (jnp.where((lo < hi) & ~left, mid + 1, lo),
                jnp.where(left, mid, hi),
                jnp.where(left, (k_high == q_high) & (k_low == q_low), hit))

    # hit: the row settled on holds the query's key; none yet (lo > hi
    # is all False, and as varying as the carry under shard_map). No
    # trip where the directory answered: [lo, hi) is the range
    steps = jnp.where(direct, 0, k)
    start, top, hit = jax.lax.fori_loop(0, steps, halve, (lo, hi, lo > hi))

    def searched():
        # run_end[i]: one past the last usable position holding row i's
        # key, through run ids (cumsum is the one scan XLA:TPU compiles
        # in seconds: 6 s at 1.5M rows where cummin takes 30)
        prev = jnp.concatenate([sorted_keys[:1], sorted_keys[:-1]])
        run = jnp.cumsum(((sorted_keys != prev) | (pos == n))
                         .astype(jnp.int32), dtype=jnp.int32)
        run_start = jnp.full(nb + 1, nb, dtype=jnp.int32).at[run].min(
            pos, indices_are_sorted=True)
        run_end = jnp.minimum(run_start[run + 1], n)
        return jnp.where(hit, run_end[jnp.minimum(start, nb - 1)], start)

    # the end of the range: the directory's, or read off the build
    # side's run ends (a second gather a query) where it searched
    end = jax.lax.cond(direct, lambda: top, searched)
    return start, end, steps, direct


def _lookup(sorted_words: Sequence[jnp.ndarray], usable: jnp.ndarray,
            query_words: Sequence[jnp.ndarray], spread: int = 1):
    """`_match_ranges` of `query_words` in one side's `_sort_build`
    output; several words a key go through `_pack_ranks` first (dense
    ranks: the directory answers them)."""
    n_usable = jnp.sum(usable, dtype=jnp.int32)
    if len(query_words) == 1:
        return _match_ranges(sorted_words[0], n_usable, query_words[0],
                             spread)
    ranks, q_ranks = _pack_ranks(list(sorted_words), list(query_words))
    return _match_ranges(ranks.astype(jnp.uint64), n_usable,
                         q_ranks.astype(jnp.uint64))


def _slot_block(n: int, slots: int) -> int:
    """Rows a block of `_slot_rows`' directory holds: the power of two
    at or above n / slots. The directory's histogram then takes about
    as many updates as there are slots and the search inside a block
    log2(n / slots) trips: both follow the output, not the table (on
    the chip an update costs about what a gathered index does; PERF.md,
    PR 29, has the table). A table no longer than the output is its own
    directory: blocks of one row, no search."""
    return 1 << ((n - 1) // slots).bit_length()


def _slot_trips(n: int, slots: int) -> int:
    """Gather trips a slot of `_slot_rows` takes over a table of `n`
    rows: log2 of the block, or a search of the whole table where it is
    one block; a constant of the shapes."""
    if n == 0 or slots == 0:
        return 0
    block = _slot_block(n, slots)
    return n.bit_length() if n <= block else block.bit_length() - 1


def _running_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of int32 `x`, along rows of 1,024 and then
    over the rows' totals: XLA:TPU compiles that in a second at any
    length, the flat cumsum in 18-22 s at a million elements (PERF.md,
    PR 29)."""
    n = x.shape[0]
    rows = jnp.pad(x, (0, -n % 1024)).reshape(-1, 1024)
    sums = jnp.cumsum(rows, axis=1, dtype=jnp.int32)
    above = jnp.cumsum(sums[:, -1], dtype=jnp.int32) - sums[:, -1]
    return (sums + above[:, None]).reshape(-1)[:n]


@jax.named_scope("_slot_rows")
def _slot_rows(off: jnp.ndarray, slots: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """The prefix-sum expansion's map from an output slot to the row
    that emits it: for a nondecreasing offset table `off` (any integer
    type) and the slots k = arange(slots), row[k] = (#i : off[i] <= k)
    - 1 as int32, the integer searchsorted(off, k, side="right") - 1
    gives (-1 where off[0] > k). Also returns j[k] = k - off[row[k]],
    the slot's place among those its row emits (int32; row -1 read as
    row 0), and the gather trips a slot takes to find its row, a
    constant of the shapes.

    1. off32 = clip(off, 0, slots) as int32: for a slot k < slots,
       off > k iff off32 > k, whatever the width `off` needs.
    2. The first offset of every block of B rows is counted into a bin
       a slot and one behind them (n / B sorted updates) and the counts
       summed along the slots: the
       count of blocks that start at or before k, less one, is the last
       block whose first offset is <= k, and it holds the answer
       (`_running_sum`).
    3. log2 B trips inside the block, from its first row (known <= k):
       a trip moves on by half the rows left where the row there is <=
       k too, at one gather of a 32-bit lane.

    B is `_slot_block`'s. A table of at most B rows (one row, or under
    three slots) is one block entered before its first row."""
    n = off.shape[0]
    k = jnp.arange(slots, dtype=jnp.int32)
    if n == 0 or slots == 0:
        return jnp.full(slots, -1, dtype=jnp.int32), k, 0
    off32 = jnp.clip(off, 0, slots).astype(jnp.int32)
    block = _slot_block(n, slots)
    trips = _slot_trips(n, slots)
    if n <= block:  # n + 1 answers, -1 among them
        # -1 (the clip's floor is 0), and as varying as the carry under
        # shard_map
        row = jnp.broadcast_to(jnp.minimum(off32[0], 0) - 1, (slots,))
    else:
        hist = jnp.zeros((slots // 1024 + 1) * 1024, dtype=jnp.int32).at[
            off32[::block]].add(1, indices_are_sorted=True)
        starts = _running_sum(hist)[:slots]
        # no block starts at or before k: -1, and no row after it is <= k
        row = jnp.where(starts > 0, (starts - 1) * block, -1)

    def advance(trip, row):
        ahead = row + ((1 << trips) >> trip + 1)
        there = off32[jnp.minimum(ahead, n - 1)]
        return jnp.where((ahead < n) & (there <= k), ahead, row)

    row = jax.lax.fori_loop(0, trips, advance, row)
    return row, k - off32[jnp.maximum(row, 0)], trips


def _compact_capacity(npr: int, out_capacity: int) -> int:
    """Rows the compacted probe holds: the join's output capacity, where
    the probe is at least four times as long; else 0, and the join
    compiles no second form. A probe row that can emit emits at least
    one slot, so emitting rows that pass the capacity would overflow it
    too: the capacity the ladder learned for the output fits the
    probe."""
    return out_capacity if 0 < 4 * out_capacity <= npr else 0


@jax.named_scope("_compact_probe")
def _compact_probe(emits: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """The rows of the probe that can emit a slot, in row order: the
    row of the k-th True of `emits` for k < capacity (int32; the last
    row where there is no k-th). The mask's exclusive prefix sum is an
    offset table of rows that emit 0 or 1 slots, so `_slot_rows` over
    it is the map."""
    e = emits.astype(jnp.int32)
    crow, _, _ = _slot_rows(_running_sum(e) - e, capacity)
    return jnp.clip(crow, 0, emits.shape[0] - 1)


def _probe_slots(sb_words: Sequence[jnp.ndarray], b_usable: jnp.ndarray,
                 p_words: Sequence[jnp.ndarray], p_usable: jnp.ndarray,
                 p_active: jnp.ndarray, outer_probe: bool, out_capacity: int,
                 spread: int):
    """The probe side of the join over the rows given (the whole probe,
    or its compacted rows): look every row up, sum what each emits, and
    map the output slots back. Returns, per slot, the row that emits it
    (int32), whether the slot is live, whether it carries a build row,
    and that row's place in the sorted build side; then the slots the
    rows emit in all (int64), the lookup's search trips and whether its
    directory answered alone."""
    n = p_usable.shape[0]
    nb = b_usable.shape[0]
    # match ranges inside the usable (sorted-front) region
    start, end, steps, direct = _lookup(sb_words, b_usable, p_words, spread)

    # per probe row in int32 (they are gathered per slot), and so their
    # running sum: exact where the total fits 31 bits, and a total that
    # does not overflows every capacity (what the slots then hold is
    # discarded with the dispatch). The total itself is exact, in int64.
    # (A 64-bit scan is no option inside a `cond`: XLA:TPU fails to
    # place some, by their length, "allocating on stack"; PERF.md, PR 31.)
    cnt = jnp.where(p_usable, end - start, 0)
    if outer_probe:
        emit = jnp.where(p_active, jnp.maximum(cnt, 1), 0)
    else:
        emit = cnt
    off = _running_sum(emit) - emit  # exclusive
    total = jnp.sum(emit, dtype=jnp.int64)

    k = jnp.arange(out_capacity, dtype=jnp.int32)
    # map output slot -> probe row, and its place j in that row's run
    prow, j, _ = _slot_rows(off, out_capacity)
    prow = jnp.clip(prow, 0, n - 1)
    valid = (k < total) & (j < emit[prow])
    matched = j < cnt[prow]
    srow = jnp.clip(start[prow] + j, 0, nb - 1)
    return prow, valid, matched, srow, total, steps, direct


def _probe_side(sb_words: Sequence[jnp.ndarray], b_usable: jnp.ndarray,
                p_keys: Sequence[Block], p_active: jnp.ndarray,
                outer_probe: bool, out_capacity: int, compact_capacity: int,
                spread: int = 1):
    """`_probe_slots` over the probe rows that can emit a slot, where
    there are at most `compact_capacity` of them (`_compact_probe`; the
    key columns gathered at those rows), and over every probe row where
    there are more, or the capacity is 0. Both forms are in the one
    program and the device chooses by the count it sees: no host read,
    no rerun. Slots are filled in probe row order in both, so they
    answer alike, slot for slot; the last value returned says which
    ran. The forms take the key columns, not their words: what a
    `cond` takes in is held in memory whole, and a key's words are
    twice its column."""
    npr = p_active.shape[0]

    def slots(keys, active):
        words, usable = _combined_key(keys, active)
        return _probe_slots(sb_words, b_usable, words, usable, active,
                            outer_probe, out_capacity, spread)

    def full():
        return slots(p_keys, p_active)

    if not compact_capacity:
        return (*full(), jnp.zeros((), dtype=bool))
    # an inner join's row emits what it matches; an outer probe's active
    # row emits at least its one NULL-extended slot, NULL key or not (its
    # lookup is masked in either form)
    emits = p_active if outer_probe else _combined_key(p_keys, p_active)[1]
    n_emit = jnp.sum(emits, dtype=jnp.int32)

    def compacted():
        crow = _compact_probe(emits, compact_capacity)
        live = jnp.arange(compact_capacity, dtype=jnp.int32) < n_emit
        prow, valid, matched, srow, total, steps, direct = slots(
            [_gather(c, crow) for c in p_keys], live)
        # a slot past the total names the last probe row, as in `full`
        return (jnp.where(valid, crow[prow], npr - 1), valid, matched, srow,
                total, steps, direct)

    took = n_emit <= compact_capacity
    return (*jax.lax.cond(took, compacted, full), took)


@jax.named_scope("hash_join")
def hash_join(probe: Batch, build: Batch,
              probe_key_channels: Sequence[int],
              build_key_channels: Sequence[int],
              out_capacity: int,
              join_type: str = "inner",
              build_output_channels: Optional[Sequence[int]] = None,
              spread: int = 1) -> JoinResult:
    """Join probe x build. join_type in {inner, left, right, full}
    (spi/plan/JoinType.java:20-23). Output columns are probe.columns ++
    build.columns[build_output_channels].

    Outer-build emission (RIGHT/FULL, LookupOuterOperator analog): the
    reference scatters per-build-row match flags during the probe loop
    and walks unvisited positions afterwards. Here the match flag comes
    from a scatter-free REVERSE probe -- build keys binary-search the
    sorted probe keys -- and unmatched build rows append after the
    matched region through the same prefix-sum expansion, with NULL
    probe columns. Under a mesh this requires PARTITIONED distribution
    (each build row must live on exactly one worker; plan.distribute
    forces it).

    The probe side is `_probe_side`: one 32-bit gather a probe row where
    every row is looked up and the build keys' span fits the lookup's
    directory (three or four where it does not: `direct` counts the
    lookups answered by the directory, the counter join_lookup_direct),
    none for a row that cannot emit where those that can fit
    `out_capacity` and the probe is four times as long (the result's
    `compacted` says which ran: the counter join_probe_compacted);
    `expand_steps` is the same in both forms. `spread` is the number of
    hash shares the build side is one of (it was exchanged by its keys
    over that many chips): its keys then lie that far apart, and the
    lookup's directory is that many times wider."""
    assert join_type in ("inner", "left", "right", "full"), join_type
    if build_output_channels is None:
        build_output_channels = range(build.num_columns)

    p_keys = [probe.column(c) for c in probe_key_channels]
    b_keys = [build.column(c) for c in build_key_channels]
    p_keys, b_keys = _align_key_widths(p_keys, b_keys)
    p_words, p_usable = _combined_key(p_keys, probe.active)
    b_words, b_usable = _combined_key(b_keys, build.active)

    nb = build.capacity
    npr = probe.capacity

    # sort build by key words (unusable rows masked to MAX, sorted last)
    sb_words, b_perm = _sort_build(b_words, b_usable,
                                   jnp.arange(nb, dtype=jnp.int32))
    prow, valid, matched, srow, total, steps, direct, took = _probe_side(
        sb_words, b_usable, p_keys, probe.active,
        join_type in ("left", "full"), out_capacity,
        _compact_capacity(npr, out_capacity), spread)
    direct = direct.astype(jnp.int32)
    expand_steps = _slot_trips(npr, out_capacity)

    outer_build = join_type in ("right", "full")
    if outer_build:
        # reverse probe: does any usable probe row carry this build key?
        sp_words, _ = _sort_build(p_words, p_usable, None)
        bs, be, steps2, direct2 = _lookup(sp_words, p_usable, b_words,
                                          spread)
        steps = steps + steps2
        direct = direct + direct2.astype(jnp.int32)
        b_matched = b_usable & (be > bs)
        unmatched = build.active & ~b_matched
        u = unmatched.astype(jnp.int32)
        # exclusive, original build row order, behind region 1's slots
        off2 = total + jnp.cumsum(u, dtype=jnp.int64) - u
        total2 = off2[-1] + u[-1]
    else:
        total2 = total
    overflow = total2 > out_capacity

    k = jnp.arange(out_capacity, dtype=jnp.int32)
    brow = b_perm[srow]  # back to original build row order

    build_valid = valid & matched
    all_valid = valid
    if outer_build:
        # region 2: slots [total, total2) emit unmatched build rows
        brow2, j2, expand_steps2 = _slot_rows(off2, out_capacity)
        expand_steps += expand_steps2
        brow2 = jnp.clip(brow2, 0, nb - 1)
        valid2 = (k >= total) & (k < total2) & (j2 < u[brow2])
        brow = jnp.where(valid2, brow2, brow)
        build_valid = build_valid | valid2
        all_valid = all_valid | valid2

    out_cols: List[Block] = []
    for c in probe.columns:
        out_cols.append(_gather(c, prow, valid))
    for ci in build_output_channels:
        c = build.column(ci)
        g = _gather(c, brow, build_valid)
        out_cols.append(g)
    out = Batch(tuple(out_cols), all_valid)
    return JoinResult(out, total2, overflow, steps, took.astype(jnp.int32),
                      direct, expand_steps)


from ..block import gather_block as _gather  # shared row gather


@jax.named_scope("semi_join_mask")
def semi_join_mask(probe: Batch, build: Batch,
                   probe_key_channels: Sequence[int],
                   build_key_channels: Sequence[int],
                   null_keys_match: bool = False,
                   steps_out: Optional[list] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SemiJoinNode analog: per-probe-row 'key IN build side' with SQL
    three-valued semantics. Returns (match, null_flag):

      match            TRUE iff the non-null key has a build match
      null_flag        the IN result is NULL: probe key is NULL, or no
                       match but the build side contains a NULL key

    `NOT IN` then composes correctly through Kleene NOT + filters.

    With null_keys_match=True, NULL keys compare EQUAL (IS NOT DISTINCT
    FROM) and null_flag is always False -- the INTERSECT/EXCEPT and
    mark-distinct membership semantics.

    The lookup's binary-search trip count and whether its directory
    answered alone are appended to `steps_out`."""
    p_keys = [probe.column(c) for c in probe_key_channels]
    b_keys = [build.column(c) for c in build_key_channels]
    p_keys, b_keys = _align_key_widths(p_keys, b_keys)
    if null_keys_match:
        # include the per-column null words as key material: NULL == NULL
        p_words, _ = key_words(p_keys)
        b_words, _ = key_words(b_keys)
        p_usable = probe.active
        b_usable = build.active
    else:
        p_words, p_usable = _combined_key(p_keys, probe.active)
        b_words, b_usable = _combined_key(b_keys, build.active)
    sb_words, _ = _sort_build(b_words, b_usable, None)
    start, end, steps, direct = _lookup(sb_words, b_usable, p_words)
    if steps_out is not None:
        steps_out.append((steps, direct))
    match = p_usable & (end > start)
    if null_keys_match:
        return match, jnp.zeros_like(match)
    build_has_null = jnp.any(build.active & ~b_usable)
    probe_key_null = probe.active & ~p_usable
    null_flag = probe_key_null | (probe.active & ~match & build_has_null)
    return match, null_flag
