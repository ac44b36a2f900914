"""Window functions: the WindowOperator / TopNRowNumberOperator analog.

Reference surface: operator/WindowOperator.java + operator/window/
(RowNumberFunction, RankFunction, DenseRankFunction, framed aggregate
windows; PagesIndex sorts each partition then streams frames).

TPU-first redesign: one global lax.sort by (partition keys, order keys)
turns every window computation into segmented prefix scans over the
sorted order -- no per-partition loops:

  part_start[i]  first sorted position of i's partition
  run_start[i]   first sorted position of i's (partition, order) peer run
  row_number     pos - part_start + 1
  rank           run_start - part_start + 1
  dense_rank     (# order boundaries in partition before pos) + 1
  sum/count/avg/min/max over RANGE UNBOUNDED PRECEDING..CURRENT ROW
                 prefix-scan value at the END of the peer run (peers are
                 ties -- they share the frame result), minus the prefix
                 before part_start
  full-partition frame (UNBOUNDED..UNBOUNDED): value at partition end

Results scatter back to original row positions through the sort
permutation. NULLS in aggregates are skipped (masked to identity).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..block import (Batch, Block, Column, DictionaryColumn, Int128Column,
                     StringColumn)
from .keys import key_words, lex_sort
from .sort import SortKey, _column_words

__all__ = ["WindowSpec", "window"]

_FUNCS = ("row_number", "rank", "dense_rank", "sum", "count", "avg", "min",
          "max", "first_value", "last_value", "ntile", "percent_rank",
          "cume_dist", "lag", "lead", "nth_value")


@dataclasses.dataclass(frozen=True)
class WindowSpec:
    name: str
    input_channel: Optional[int] = None
    output_type: T.Type = T.BIGINT
    # frame: "range_current" (default: RANGE UNBOUNDED PRECEDING..CURRENT
    # ROW), "full" (whole partition), or a ROWS frame ("rows", start,
    # end) with signed row offsets (None = unbounded on that side)
    frame: object = "range_current"
    ntile_buckets: int = 0
    offset: int = 1  # lag/lead distance; nth_value's n

    def __post_init__(self):
        assert self.name in _FUNCS, self.name
        if self.name == "ntile":
            assert self.ntile_buckets > 0, "ntile requires a positive bucket count"
        if self.name == "nth_value":
            assert self.offset >= 1, "nth_value's n must be at least 1"
        if isinstance(self.frame, (tuple, list)):
            assert self.frame[0] in ("rows", "range"), self.frame


def _seg_positions(words: List[jnp.ndarray]) -> jnp.ndarray:
    """Boundary mask: True where any word differs from the previous row."""
    n = words[0].shape[0]
    b = jnp.zeros(n, dtype=bool)
    for w in words:
        b = b | (w != jnp.concatenate([w[:1], w[:-1]]))
    return b.at[0].set(True)


def window(batch: Batch, partition_channels: Sequence[int],
           order_keys: Sequence[SortKey], specs: Sequence[WindowSpec]) -> Batch:
    """Returns the input batch with one appended column per spec (same
    row order as the input; padding rows get nulls)."""
    n = batch.capacity
    pos = jnp.arange(n, dtype=jnp.int64)

    pwords, _ = key_words([batch.column(c) for c in partition_channels])
    owords: List[jnp.ndarray] = []
    for sk in order_keys:
        owords.extend(_column_words(batch.column(sk.channel), sk.descending,
                                    sk.nulls_last))
    lead = jnp.where(batch.active, np.uint64(0), np.uint64(1))
    ops = [lead, *pwords, *owords, pos.astype(jnp.int32)]
    sorted_ops = lex_sort(ops, num_keys=len(ops) - 1, is_stable=True)
    perm = sorted_ops[-1]
    s_active = sorted_ops[0] == 0
    s_pwords = sorted_ops[1:1 + len(pwords)]
    s_owords = sorted_ops[1 + len(pwords):-1]

    if s_pwords:
        part_bound = _seg_positions(list(s_pwords)) | ~s_active
    else:
        # OVER () / no PARTITION BY: one whole-input partition
        part_bound = jnp.zeros(n, dtype=bool).at[0].set(True) | ~s_active
    run_bound = part_bound | (_seg_positions(list(s_owords)) if s_owords
                              else jnp.zeros(n, dtype=bool))

    spos = jnp.arange(n, dtype=jnp.int64)
    part_start = jnp.where(part_bound, spos, 0)
    part_start = jax.lax.cummax(part_start)
    run_start = jnp.where(run_bound, spos, 0)
    run_start = jax.lax.cummax(run_start)

    # partition end: next partition boundary - 1 (computed by reverse cummin)
    next_bound = jnp.where(part_bound, spos, n)
    # shift: boundary at i means partition ends at i-1 for previous rows
    nb = jnp.concatenate([next_bound[1:], jnp.full((1,), n, dtype=jnp.int64)])
    part_end = jax.lax.cummin(nb[::-1])[::-1]  # first boundary at/after i+1
    part_end = part_end - 1
    # run end likewise
    nrb = jnp.where(run_bound, spos, n)
    nrb = jnp.concatenate([nrb[1:], jnp.full((1,), n, dtype=jnp.int64)])
    run_end = jax.lax.cummin(nrb[::-1])[::-1] - 1

    row_number = spos - part_start + 1
    rank = run_start - part_start + 1
    # dense rank: count of run boundaries in (part_start, pos]
    rb = jnp.cumsum(run_bound.astype(jnp.int64))
    dense = rb - rb[part_start] + 1
    part_rows = part_end - part_start + 1

    out_cols: List[Block] = list(batch.columns)
    inv = jnp.zeros(n, dtype=jnp.int64).at[perm].set(spos)

    # RANGE value-offset frames search the (single, ASC) order key's
    # values within each partition; null-order-key rows are overridden
    # to their peer run by _frame_bounds, and the sentinel keeps the
    # binary search from wandering into the null zone.
    o_vals_sorted = o_nulls_sorted = None
    if any(isinstance(s.frame, (tuple, list)) and s.frame[0] == "range"
           for s in specs):
        assert len(order_keys) == 1, \
            "RANGE value frames require exactly one ORDER BY key"
        ch, desc, nulls_last = order_keys[0]
        assert not desc, "RANGE value frames over DESC order keys"
        ocol = batch.column(ch)
        if isinstance(ocol, DictionaryColumn):
            ocol = ocol.decode()
        assert not isinstance(ocol, (StringColumn, Int128Column)), \
            "RANGE value frame over unsupported order-key column"
        o_nulls_sorted = (ocol.nulls | ~batch.active)[perm]
        ov = ocol.values[perm]
        if ocol.type.is_floating:
            sent = jnp.inf if nulls_last else -jnp.inf
        else:
            info = jnp.iinfo(ov.dtype)
            sent = info.max if nulls_last else info.min
        o_vals_sorted = jnp.where(o_nulls_sorted, sent, ov)

    def frame_bounds(frame):
        return _frame_bounds(frame, spos, part_start, part_end, run_end,
                             o_vals_sorted, o_nulls_sorted, run_start)

    for spec in specs:
        name = spec.name
        if name == "row_number":
            vals_sorted = row_number
            nulls_sorted = ~s_active
        elif name == "rank":
            vals_sorted = rank
            nulls_sorted = ~s_active
        elif name == "dense_rank":
            vals_sorted = dense
            nulls_sorted = ~s_active
        elif name == "percent_rank":
            denom = jnp.maximum(part_rows - 1, 1).astype(jnp.float64)
            vals_sorted = jnp.where(part_rows == 1, 0.0,
                                    (rank - 1).astype(jnp.float64) / denom)
            nulls_sorted = ~s_active
        elif name == "cume_dist":
            vals_sorted = (run_end - part_start + 1).astype(jnp.float64) / \
                part_rows.astype(jnp.float64)
            nulls_sorted = ~s_active
        elif name == "ntile":
            k = spec.ntile_buckets
            r0 = (row_number - 1)
            vals_sorted = jnp.minimum(r0 * k // jnp.maximum(part_rows, 1), k - 1) + 1
            nulls_sorted = ~s_active
        elif name in ("lag", "lead"):
            col = batch.column(spec.input_channel)
            if isinstance(col, DictionaryColumn):
                col = col.decode()
            assert not isinstance(col, StringColumn), \
                "lag/lead over strings is not yet supported"
            v_sorted = col.values[perm]
            n_sorted = col.nulls[perm]
            k = spec.offset if name == "lag" else -spec.offset
            src = jnp.clip(spos - k, 0, n - 1)
            same_part = part_start[src] == part_start
            in_rng = (spos - k >= 0) & (spos - k < n)
            ok = in_rng & same_part & s_active
            vals_sorted = jnp.where(ok, v_sorted[src], v_sorted)
            nulls_sorted = jnp.where(ok, n_sorted[src], True) | ~s_active
        elif name == "count" and spec.input_channel is None:
            # count(*) over frame: rows (not non-null values)
            f_lo, f_hi = frame_bounds(spec.frame)
            vals_sorted = jnp.maximum(f_hi - f_lo + 1, 0)
            nulls_sorted = ~s_active
        elif name in ("sum", "count", "avg", "min", "max", "first_value",
                      "last_value", "nth_value"):
            col = batch.column(spec.input_channel)
            if isinstance(col, DictionaryColumn):
                col = col.decode()
            assert not isinstance(col, StringColumn), \
                f"window {name} over strings is not yet supported"
            f_lo, f_hi = frame_bounds(spec.frame)
            f_hi_c = jnp.clip(f_hi, 0, n - 1)
            f_lo_c = jnp.clip(f_lo, 0, n - 1)
            empty_frame = f_hi < f_lo

            def frame_total(contrib):
                """Inclusive [f_lo, f_hi] totals via padded-cumsum diff."""
                ps = jnp.cumsum(contrib)
                base = jnp.where(f_lo > 0, ps[jnp.maximum(f_lo - 1, 0)], 0)
                return jnp.where(empty_frame, 0, ps[f_hi_c] - base)

            if isinstance(col, Int128Column):
                # long-decimal inputs (aggregation states feeding a
                # window stage, the q53/q12/q51 shapes): EXACT windowed
                # sums via 13-bit limb cumsums recombined to (hi, lo);
                # avg divides with the decimal half-up rule; min/max by
                # a segmented 128-bit-lexicographic scan; value picks by
                # frame-edge gathers
                from ..int128 import (combine_limb_totals_128,
                                      div128_by_count, limbs13_of_128)
                nn_sorted = (~col.nulls & batch.active)[perm]
                if name in ("first_value", "last_value", "nth_value"):
                    if name == "first_value":
                        idx = f_lo_c
                    elif name == "last_value":
                        idx = f_hi_c
                    else:
                        idx = jnp.clip(f_lo + (spec.offset - 1), 0, n - 1)
                    in_frame = (~empty_frame) & \
                        (f_lo + (spec.offset - 1 if name == "nth_value"
                                 else 0) <= f_hi)
                    nl = (col.nulls | ~batch.active)[perm]
                    nulls = nl[idx] | ~in_frame | ~s_active
                    out_cols.append(Int128Column(
                        col.hi[perm][idx][inv], col.lo[perm][idx][inv],
                        nulls[inv], spec.output_type))
                    continue
                if name in ("min", "max"):
                    if isinstance(spec.frame, (tuple, list)) and \
                            spec.frame[1] is not None:
                        raise NotImplementedError(
                            "bounded-start ROWS min/max over long "
                            "decimals")
                    minimize = name == "min"
                    ih = (jnp.iinfo(jnp.int64).max if minimize
                          else jnp.iinfo(jnp.int64).min)
                    il = jnp.uint64(0xFFFFFFFFFFFFFFFF) if minimize \
                        else jnp.uint64(0)
                    h_s = jnp.where(nn_sorted, col.hi[perm], ih)
                    l_s = jnp.where(nn_sorted, col.lo[perm], il)
                    sh, sl = _segmented_extreme128(h_s, l_s, part_bound,
                                                   minimize)
                    wcnt = frame_total(nn_sorted.astype(jnp.int64))
                    empty = (wcnt == 0) | empty_frame | ~s_active
                    out_cols.append(Int128Column(
                        sh[f_hi_c][inv], sl[f_hi_c][inv],
                        empty[inv], spec.output_type))
                    continue
                if name not in ("sum", "avg", "count"):
                    raise NotImplementedError(
                        f"window {name} over long decimals")
                wcnt = frame_total(nn_sorted.astype(jnp.int64))
                if name == "count":
                    out_cols.append(Column(wcnt[inv],
                                           (~s_active)[inv],
                                           spec.output_type))
                    continue
                totals = [frame_total(jnp.where(nn_sorted, l[perm], 0))
                          for l in limbs13_of_128(col.hi, col.lo)]
                hi, lo = combine_limb_totals_128(
                    jnp.stack(totals, axis=-1))
                empty = (wcnt == 0) | ~s_active
                if name == "avg":
                    qv = div128_by_count(hi, lo, jnp.maximum(wcnt, 1))
                    hi = (qv >> 63).astype(hi.dtype)
                    lo = qv.astype(jnp.uint64)
                out_cols.append(Int128Column(hi[inv], lo[inv],
                                             empty[inv],
                                             spec.output_type))
                continue
            v_sorted = col.values[perm]
            nn_sorted = (~col.nulls & batch.active)[perm]
            if name in ("sum", "avg", "count"):
                sv = v_sorted.astype(jnp.float64 if col.type.is_floating
                                     else jnp.int64)
                wsum = frame_total(jnp.where(nn_sorted, sv, 0))
                wcnt = frame_total(nn_sorted.astype(jnp.int64))
                if name == "sum":
                    vals_sorted = wsum
                    nulls_sorted = (wcnt == 0) | ~s_active
                elif name == "count":
                    vals_sorted = wcnt
                    nulls_sorted = ~s_active
                else:
                    vals_sorted = wsum.astype(jnp.float64) / \
                        jnp.maximum(wcnt, 1).astype(jnp.float64)
                    if not spec.output_type.is_floating:
                        # decimal-typed avg: scaled float mean -> scaled int
                        vals_sorted = jnp.round(vals_sorted)
                    nulls_sorted = (wcnt == 0) | ~s_active
            elif name in ("min", "max"):
                minimize = name == "min"
                ident = (jnp.iinfo(jnp.int64).max if minimize
                         else jnp.iinfo(jnp.int64).min)
                if col.type.is_floating:
                    ident = jnp.inf if minimize else -jnp.inf
                sv = jnp.where(nn_sorted, v_sorted, ident)
                bounded_start = isinstance(spec.frame, (tuple, list)) \
                    and spec.frame[1] is not None
                if bounded_start:
                    # general bounded-start frame: sparse-table range
                    # extreme. For ROWS frames with a bounded end the
                    # static offsets cap the frame length, so only
                    # log2(w) levels are built; RANGE value offsets say
                    # nothing about row counts, so no cap applies.
                    _s, _e = spec.frame[1], spec.frame[2]
                    cap = (_e - _s + 1) if (_e is not None and
                                            spec.frame[0] == "rows") else None
                    vals_sorted = _range_extreme(sv, f_lo_c, f_hi_c,
                                                 ident, minimize,
                                                 max_len=cap)
                else:
                    # frame starts at the partition head: the cheaper
                    # O(n) segmented running scan answers any end bound
                    scan = jax.lax.cummin if minimize else jax.lax.cummax
                    ps = _segmented_scan(sv, part_bound, scan)
                    vals_sorted = ps[f_hi_c]
                wcnt = frame_total(nn_sorted.astype(jnp.int64))
                nulls_sorted = (wcnt == 0) | empty_frame | ~s_active
            elif name in ("first_value", "last_value", "nth_value"):
                if name == "first_value":
                    idx = f_lo_c
                elif name == "last_value":
                    idx = f_hi_c
                else:  # nth_value(x, n): n-th row of the frame
                    idx = jnp.clip(f_lo + (spec.offset - 1), 0, n - 1)
                # membership is tested on the UNCLIPPED index: a clipped
                # idx can land back on a valid slot (e.g. n beyond the
                # frame at the last array position) and must stay NULL
                in_frame = (~empty_frame) & \
                    (f_lo + (spec.offset - 1 if name == "nth_value" else 0)
                     <= f_hi)
                vals_sorted = v_sorted[idx]
                nulls_sorted = col.nulls[perm][idx] | ~in_frame | ~s_active
        else:
            raise NotImplementedError(name)

        # every branch above produces traced jnp arrays; indexing them
        # directly keeps the jit region wrapper-free (tpulint H001)
        vals = vals_sorted[inv]
        nulls = nulls_sorted[inv]
        dt = spec.output_type.to_dtype()
        vals = vals.astype(dt)
        out_cols.append(Column(vals, nulls, spec.output_type))

    return Batch(tuple(out_cols), batch.active)


def _seg_search(vals, targets, seg_lo, seg_hi_excl, side: str):
    """Vectorized per-row binary search: insertion point of targets[i]
    within the sorted slice vals[seg_lo[i]:seg_hi_excl[i]] ('left' or
    'right' side). O(log n) unrolled where-steps, no gather loops."""
    n = vals.shape[0]
    lo = seg_lo.astype(jnp.int64)
    hi = seg_hi_excl.astype(jnp.int64)
    steps = max(1, int(np.ceil(np.log2(max(n, 2)))) + 1)
    for _ in range(steps):
        mid = (lo + hi) // 2
        v = vals[jnp.clip(mid, 0, n - 1)]
        go_right = (v < targets) if side == "left" else (v <= targets)
        active = lo < hi
        lo = jnp.where(active & go_right, mid + 1, lo)
        hi = jnp.where(active & ~go_right, mid, hi)
    return lo


def _frame_bounds(frame, spos, part_start, part_end, run_end,
                  order_vals=None, order_nulls=None, run_start=None):
    """Inclusive [lo, hi] sorted-position bounds of each row's frame.
    "range_current" = RANGE UNBOUNDED PRECEDING..CURRENT ROW (peer-
    inclusive via run_end); "full" = whole partition; ("rows", s, e) =
    signed row offsets; ("range", s, e) = ORDER-KEY VALUE offsets (both:
    None = unbounded on that side). Value frames search the partition's
    sorted order values; rows whose order key is NULL frame over their
    null-peer run (the SQL null-peers rule)."""
    if isinstance(frame, (tuple, list)) and frame[0] == "range":
        _mode, s, e = frame
        v = order_vals
        if s is None:
            lo = part_start
        else:
            lo = _seg_search(v, v + s, part_start, part_end + 1, "left")
        if e is None:
            hi = part_end
        else:
            hi = _seg_search(v, v + e, part_start, part_end + 1,
                             "right") - 1
        if order_nulls is not None:
            # null-order-key rows treat all null rows as peers, but ONLY
            # on offset-bounded sides: an UNBOUNDED side still reaches
            # the partition edge for them (Presto/Postgres null-peers
            # semantics)
            if s is not None:
                lo = jnp.where(order_nulls, run_start, lo)
            if e is not None:
                hi = jnp.where(order_nulls, run_end, hi)
        return lo, hi
    if isinstance(frame, (tuple, list)):
        _mode, s, e = frame
        lo = part_start if s is None else jnp.maximum(part_start, spos + s)
        hi = part_end if e is None else jnp.minimum(part_end, spos + e)
        return lo, hi
    if frame == "full":
        return part_start, part_end
    return part_start, run_end


def _range_extreme(sv, lo, hi, ident, minimize: bool, max_len=None):
    """Min/max over arbitrary inclusive [lo, hi] ranges via a sparse
    table: level k holds extrema of length-2^k blocks; a query combines
    the two blocks covering the range (O(n log n) build, O(1) gathers
    per row -- the vectorizable answer to sliding-window extrema).
    `max_len` (a static bound on hi-lo+1, when the caller knows one)
    caps the level count at log2(max_len)."""
    n = sv.shape[0]
    op = jnp.minimum if minimize else jnp.maximum
    levels = [sv]
    k = 1
    k_stop = max(min(n, max_len if max_len is not None else n), 1)
    while k < k_stop:
        prev = levels[-1]
        shifted = jnp.concatenate(
            [prev[k:], jnp.full((min(k, n),), ident, dtype=sv.dtype)])
        levels.append(op(prev, shifted))
        k *= 2
    table = jnp.stack(levels)  # (L, n)
    length = jnp.maximum(hi - lo + 1, 1)
    # floor(log2(length)) seeded by f32 log2, then corrected one step in
    # each direction: f32 rounding is off by at most 1 (e.g. log2 of
    # 2^21 - 1 rounds UP to exactly 21.0, which would overshoot the
    # frame by one element and leak an out-of-frame value into min/max)
    kk = jnp.floor(jnp.log2(length.astype(jnp.float32))).astype(jnp.int64)
    kk = jnp.clip(kk, 0, len(levels) - 1)
    one = jnp.int64(1)
    kk = jnp.where(jnp.left_shift(one, kk) > length, kk - 1, kk)
    kk = jnp.where((kk + 1 < len(levels)) &
                   (jnp.left_shift(one, kk + 1) <= length), kk + 1, kk)
    kk = jnp.clip(kk, 0, len(levels) - 1).astype(jnp.int32)
    a = table[kk, lo]
    blk = jnp.left_shift(jnp.int64(1), kk.astype(jnp.int64))
    b = table[kk, jnp.clip(hi - blk + 1, 0, n - 1)]
    return op(a, b)


def _segmented_extreme128(h, l, seg_bound, minimize: bool):
    """Inclusive segmented running min/max over int128 (hi, lo) lanes:
    the (flag, value) associative combine with a 128-bit lexicographic
    comparison (signed hi, unsigned lo) picking the winner."""
    from ..int128 import cmp128

    def combine(a, b):
        fa, ha, la = a
        fb, hb, lb = b
        a_lt_b, _ = cmp128(ha, la, hb, lb)
        pick_b = fb | (a_lt_b if not minimize else ~a_lt_b)
        return (fa | fb,
                jnp.where(pick_b, hb, ha),
                jnp.where(pick_b, lb, la))

    _, sh, sl = jax.lax.associative_scan(combine, (seg_bound, h, l))
    return sh, sl


def _segmented_scan(vals, seg_bound, scan):
    """Inclusive segmented cummin/cummax: restart at each boundary.
    Implemented with the standard (flag, value) associative combine."""
    def combine(a, b):
        af, av = a
        bf, bv = b
        keep = bf
        if scan is jax.lax.cummin:
            nv = jnp.where(keep, bv, jnp.minimum(av, bv))
        else:
            nv = jnp.where(keep, bv, jnp.maximum(av, bv))
        return (af | bf, nv)

    flags = seg_bound
    _, out = jax.lax.associative_scan(combine, (flags, vals))
    return out
