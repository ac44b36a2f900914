"""Grouped aggregation: the HashAggregationOperator analog.

Reference surface: operator/HashAggregationOperator.java:56,
operator/aggregation/builder/InMemoryHashAggregationBuilder.java:56,
GroupByHash/BigintGroupByHash/MultiChannelGroupByHash (operator/*.java)
and the partial/final split the planner produces
(PushPartialAggregationThroughExchange rule).

TPU-first redesign: no pointer-chasing hash table, no row loop. TWO
kernels, picked by the static group capacity (measured on a v5e chip,
6M rows -- see scripts/microbench_groupby.py):

SMALL tables (max_groups <= _SMALL_G, the TPC-H q1 shape): XLA lowers
large scatters to a serialized per-update loop on TPU (436ms for ONE
6M->16 scatter-add on v5e), so the small path uses none:

  1. group ids by FIRST-OCCURRENCE EXTRACTION: a lax.while_loop that,
     per round, finds the first unresolved row (argmin), broadcasts its
     key words, and resolves every equal row -- at most max_groups data
     passes, 8.6ms vs the hash kernel's 364ms
  2. integer/decimal sums ride the MXU exactly: values split into
     13-bit limbs, one-hot(ids) @ limbs einsum in f32 over 2048-row
     chunks (each chunk sum < 2^24, exact in f32), chunk partials
     combined in int64 -- 1.2ms per 6M-row aggregate
  3. float sums and min/max reduce with per-group masked reductions
     (max_groups fused where+reduce passes, ~1ms at G=16)

LARGE tables: the HASH-SLOT kernel:

  1. normalize key columns to uint64 words (ops/keys.py), splitmix-hash
     them to a slot in a power-of-two table of 2*max_groups slots
  2. rows claim empty slots with a scatter-min of their row id; a row
     whose slot owner has EQUAL key words (exact, all words compared)
     resolves to that slot, others probe again (triangular probing)
     in a lax.while_loop -- one round suffices when collisions are rare
  3. occupied slots get dense ids by prefix-sum; rows that could not
     resolve within the probe budget raise the overflow flag (the
     exec-layer rerun/spill trigger), mirroring capacity overflow
  4. every aggregate becomes a masked scatter-add/min/max into a dense
     (max_groups,) table

(A sort-based kernel is kept as _group_ids_sort for A/B via
BENCH_GROUPBY=sort in bench.py.)

`max_groups` is a static capacity (shape-bucketing policy lives in the
exec layer; overflow is reported via the result's `overflow` flag --
the spill path's trigger, the SpillableHashAggregationBuilder analog).

Partial and final aggregation share this kernel: a partial result is
itself a Batch of (keys..., states...) rows, and `merge_partials`
re-groups them with the merge combinators (sum<-sum, count<-sum,
min<-min, max<-max, avg = (sum, count) pair).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..block import (Batch, Block, Column, DictionaryColumn, Int128Column,
                     StringColumn)
from . import device
from .keys import key_words, lex_sort

__all__ = ["AggSpec", "GroupByResult", "group_by", "grouped_aggregate",
           "merge_partials", "finalize_states", "last_smallg_form"]


# aggregate function names supported round 1 (reference: the ~250-file
# operator/aggregation/ library; the long tail lands with the function
# registry's aggregation side). approx_distinct is computed exactly via
# the hash-slot distinct kernel (within any epsilon; HLL sketch states
# land with the sketch library).
_AGGS = ("sum", "count", "count_star", "min", "max", "avg",
         "var_samp", "var_pop", "stddev_samp", "stddev_pop", "stddev",
         "variance", "bool_and", "bool_or", "every", "min_by", "max_by",
         "count_distinct", "approx_distinct", "arbitrary", "any_value",
         "approx_percentile", "corr", "covar_samp", "covar_pop",
         "regr_slope", "regr_intercept", "geometric_mean", "checksum")

# two-input statistics over (y, x) pairs: six shared f64 moments
# (operator/aggregation/Central/CovarianceAggregation analog)
_PAIR_MOMENT_AGGS = ("corr", "covar_samp", "covar_pop", "regr_slope",
                     "regr_intercept")

# canonical name -> implementation family
_ALIAS = {"stddev": "stddev_samp", "variance": "var_samp",
          "every": "bool_and", "any_value": "arbitrary"}

# HyperLogLog (approx_distinct): dense 2^p x int8 register vectors --
# a natural TPU state (flat, fixed-shape, merged by elementwise max).
# p=11 gives ~2.3% standard error (the reference default maps
# approx_distinct's 2.3% max error to the same register count --
# ApproximateCountDistinctAggregation.java).
_HLL_P = 11
_HLL_M = 1 << _HLL_P


def hll_state_type() -> T.Type:
    return T.array_of(T.TINYINT)


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregate: `name(input_channel)` -> output of `output_type`.
    input_channel is None for count(*); min_by/max_by order by
    `second_channel`."""
    name: str
    input_channel: Optional[int]
    output_type: T.Type
    second_channel: Optional[int] = None
    second_type: Optional[T.Type] = None  # order-value type for min_by/max_by
    parameter: Optional[float] = None     # percentile fraction etc.
    # BOOLEAN channel restricting which rows this aggregate consumes
    # (Aggregation.getMask() -- the MarkDistinct + masked-agg lowering of
    # DISTINCT aggregates, and FILTER (WHERE ...) clauses)
    mask_channel: Optional[int] = None

    # NOTE: unknown names are allowed at construction so plan JSON from a
    # newer coordinator can still be dry-run through validate_plan (the
    # plan-checker router use case); execution fails in _acc_columns.

    @property
    def canonical(self) -> str:
        return _ALIAS.get(self.name, self.name)


@dataclasses.dataclass
class GroupByResult:
    """Dense group table: `batch` holds one row per group (key columns
    then aggregate state columns), active for slots < num_groups.
    `overflow` is True when distinct keys exceeded max_groups (results
    for the overflowed tail are dropped -- exec layer must re-run with a
    bigger bucket or spill). `counted` says whether `num_groups` is the
    distinct keys' count whatever it is (the sorted kernel), or stops
    at the kernel's table once that overflows (the small and hash
    kernels: then a lower bound)."""
    batch: Batch
    num_groups: jnp.ndarray
    overflow: jnp.ndarray
    counted: bool = True


jax.tree_util.register_dataclass(GroupByResult,
                                 data_fields=["batch", "num_groups", "overflow"],
                                 meta_fields=["counted"])


from ..expr.functions import _GOLD as _GOLDEN, _mix64 as _splitmix64

_MAX_PROBES = 64  # probe budget; exhaustion raises the overflow flag


def _hash_words(words) -> jnp.ndarray:
    h = jnp.full(words[0].shape, _GOLDEN, dtype=jnp.uint64)
    for w in words:
        h = _splitmix64(h ^ w)
    return h


_SMALL_G = 64  # crossover below which the scatter-free kernels win


def _scatter_free() -> bool:
    """Whether the small-table kernels should avoid scatters. On TPU,
    XLA serializes large scatters (436ms for ONE 6M->16 scatter-add on
    v5e) so the MXU limb-einsum / masked-reduction forms win ~100x; on
    CPU it is the exact reverse (one 600k-row limb einsum = 83ms vs
    0.8ms for the scatter-add -- scripts/bench_bisect.py, the r01->r04
    CPU-fallback q1 'regression' root cause). Trace-time static, so
    each backend compiles its winning form. Override:
    PRESTO_TPU_SMALLG=einsum|scatter."""
    mode = _os.environ.get("PRESTO_TPU_SMALLG", "auto")
    if mode == "einsum":
        return True
    if mode == "scatter":
        return False
    return device.on_tpu()


def _group_ids(key_cols: Sequence[Block], active: jnp.ndarray, max_groups: int):
    """Dense group ids per row (exact). Returns (ids, perm_first,
    num_groups, overflow) where perm_first[g] is the row index of a
    representative member of group g, used to gather key values.
    Dispatches on the static table size (see module docstring)."""
    n = active.shape[0]
    words, _ = key_words(key_cols)
    if not words:  # global aggregation: every active row is group 0
        ids = jnp.zeros(n, dtype=jnp.int32)
        perm_first = jnp.zeros(max_groups, dtype=jnp.int32)
        num_groups = jnp.any(active).astype(jnp.int32)
        return ids, perm_first, num_groups, jnp.zeros((), dtype=bool)
    if max_groups <= _SMALL_G:
        return _group_ids_small(words, active, max_groups)
    return _group_ids_hash(words, active, max_groups)


def _group_ids_small(words, active: jnp.ndarray, max_groups: int):
    """First-occurrence extraction (no scatters): each round resolves
    one whole group -- find the first unresolved row, broadcast its key
    words, match all equal rows. At most max_groups rounds; leftover
    unresolved active rows mean >max_groups distinct keys -> overflow
    (parked in the last slot, invalidated by the rerun).

    Narrow-width execution: the (n,)-sized id payload is int16 when the
    table provably fits (G < 2^15) -- every consumer compares or
    indexes, both exact under the downcast -- halving the id lanes'
    HBM traffic through the aggregate pipeline."""
    n = active.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    id_dt = jnp.int16 if (_narrow_kernels() and max_groups < (1 << 15)) \
        else jnp.int32

    def cond(state):
        g, ids, _ = state
        return (g < max_groups) & jnp.any(active & (ids < 0))

    def body(state):
        g, ids, first = state
        unres = active & (ids < 0)
        i = jnp.min(jnp.where(unres, rows, n))
        i_safe = jnp.clip(i, 0, n - 1)
        match = unres
        for w in words:
            match = match & (w == w[i_safe])
        ids = jnp.where(match, g.astype(id_dt), ids)
        first = first.at[g].set(i_safe)  # single-element scatter: cheap
        return g + jnp.int32(1), ids, first

    num_groups, ids, perm_first = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.full(n, -1, dtype=id_dt),
                     jnp.zeros(max_groups, dtype=jnp.int32)))
    overflow = jnp.any(active & (ids < 0))
    ids = jnp.where(active & (ids >= 0), ids,
                    jnp.asarray(max_groups - 1, dtype=id_dt)).astype(id_dt)
    return ids, perm_first, num_groups, overflow


def _narrow_kernels() -> bool:
    """Trace-time gate for the narrow kernel forms (the fused
    cross-aggregate limb pool; bf16 operands where the MXU exists).
    PRESTO_TPU_NARROW=0 reverts every form to the round-5 wide kernels
    for A/B. ONE shared gate with the plan layer (plan/widths.py)."""
    from ..plan.widths import kernel_narrow_enabled
    return kernel_narrow_enabled()


def _mxu_bf16() -> bool:
    """bf16 one-hot/limb operands with 8-bit limbs: ONE MXU pass vs
    f32-HIGHEST's six. TPU-only by default -- a CPU backend has no bf16
    units (XLA emulates, measured ~2x slower than its native f32 dot),
    and CPU f32 dots are true f32 so the 13-bit f32 form is already
    exact there. PRESTO_TPU_BF16=1|0 overrides for exactness tests /
    chip A/Bs."""
    mode = _os.environ.get("PRESTO_TPU_BF16", "auto")
    if mode == "1":
        return _narrow_kernels()
    if mode == "0":
        return False
    return _narrow_kernels() and device.on_tpu()


# which small-G sum form the last trace actually emitted (trace-time
# static, like the form choice itself) -- bench.py reports this instead
# of re-deriving the decision, so artifacts name the executed kernel
_LAST_SMALLG_FORM = [None]


def _note_form(form: str) -> None:
    _LAST_SMALLG_FORM[0] = form


def last_smallg_form():
    return _LAST_SMALLG_FORM[0]


def _fused_limb_sums(ids, requests, max_groups: int,
                     chunk: int = 2048):
    """ONE one-hot matmul for every integer seg-sum in `requests`
    (list of (contrib lanes, value_bits)) -> list of (G,) exact int64
    totals. This is the fused single-pass form of the scan-side
    aggregation: the one-hot is built (and ids read) once for ALL
    aggregates instead of once per accumulator.

    Narrow form (PRESTO_TPU_NARROW, default on): 8-bit limbs staged as
    int16 lanes, one-hot AND limbs as bf16 MXU operands with f32
    accumulation -- ONE MXU pass, exact because one-hot entries are 0/1,
    every limb value lies in [-128, 255] (integers bf16 holds exactly),
    and per-chunk f32 sums stay < 2^19 << 2^24. Wide form: 13-bit limbs
    as f32 with precision=HIGHEST (six bf16 passes), the round-2
    numerics, bit-identical results.

    On TPU the one-hot+matmul runs as a fused Pallas kernel (the
    one-hot never stages through HBM), always compiled, never in
    interpret mode: if Mosaic refuses it the query fails, nothing
    gives way to the einsum form behind the user's back.
    PRESTO_TPU_SMALLG_PALLAS=0 selects the XLA einsum form (chip A/B)."""
    from ..int128 import limbs_of_i64
    narrow = _mxu_bf16()
    limb_bits = 8 if narrow else 13
    stage_dt = jnp.int16 if narrow else jnp.float32
    limb_cols = []
    spans = []
    for contrib, value_bits in requests:
        nl = max(-(-int(value_bits) // limb_bits), 1)
        x = contrib.astype(jnp.int64)
        limbs = limbs_of_i64(x, limb_bits, nl) if nl > 1 else [x]
        spans.append((len(limb_cols), nl))
        limb_cols.extend(limbs)
    n = ids.shape[0]
    L = len(limb_cols)
    lm = jnp.stack([l.astype(stage_dt) for l in limb_cols], axis=1)
    if _os.environ.get("PRESTO_TPU_SMALLG_PALLAS", "1") != "0" \
            and device.on_tpu():
        from .pallas_kernels import limb_partial_sums
        _note_form("pallas-bf16" if narrow else "pallas")
        part = limb_partial_sums(
            ids.astype(jnp.int32), lm, max_groups, interpret=False,
            compute_dtype=jnp.bfloat16 if narrow else jnp.float32)
    else:
        c = -(-n // chunk)
        pad = c * chunk - n
        i = jnp.pad(ids.astype(jnp.int32), (0, pad),
                    constant_values=max_groups)
        lmp = jnp.pad(lm, ((0, pad), (0, 0))).reshape(c, chunk, L)
        ohb = (i.reshape(c, chunk)[:, :, None]
               == jnp.arange(max_groups, dtype=jnp.int32))
        if narrow:
            _note_form("einsum-MXU-bf16")
            part = jnp.einsum("ckg,ckl->cgl", ohb.astype(jnp.bfloat16),
                              lmp.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        else:
            _note_form("einsum-MXU")
            part = jnp.einsum("ckg,ckl->cgl", ohb.astype(jnp.float32),
                              lmp.astype(jnp.float32),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)
    # ONE numerics-critical combine for all forms: per-chunk/tile f32
    # partials (each exact) recombine in int64
    tot = jnp.sum(part.astype(jnp.int64), axis=0)  # (G, L)
    out = []
    for start, nl in spans:
        t = tot[:, start:start + nl]
        scale = jnp.int64(1) << (limb_bits
                                 * jnp.arange(nl, dtype=jnp.int64))
        out.append(jnp.sum(t * scale[None, :], axis=1))
    return out


def _limb_matmul_sum(ids, v, max_groups: int, value_bits: int = 64,
                     chunk: int = 2048) -> jnp.ndarray:
    """Exact int64 per-group sums on the MXU (single-request form of
    _fused_limb_sums; `value_bits=1` covers 0/1 count flags)."""
    return _fused_limb_sums(ids, [(v, value_bits)], max_groups,
                            chunk=chunk)[0]


# ambient fused-sum pool: group_by's small-table path installs one so
# every integer accumulator across ALL aggregates lands in a single
# one-hot matmul (a collect pass discovers the requests, the serve pass
# reads the batched results -- see _SegSumPool)
import threading as _threading

_pool_tls = _threading.local()


def _seg_pool():
    return getattr(_pool_tls, "pool", None)


class _SegSumPool:
    """Two-phase cross-aggregate seg-sum batcher. Collect: _seg_add /
    _seg_count enqueue (contrib, value_bits) and hand back int64
    placeholders (the collect pass's outputs are discarded, so
    everything not feeding a request is dead code XLA eliminates).
    Compute: ONE _fused_limb_sums call over every request. Serve: the
    same call sites replay in the same order and receive the batched
    totals. Both passes run the identical spec walk, so the request
    sequence is deterministic by construction; `check_served` guards
    the invariant."""

    def __init__(self, ids, max_groups: int):
        self.ids = ids
        self.g = max_groups
        self.collecting = True
        self.requests = []
        self.results = []
        self._i = 0

    def add(self, contrib, value_bits: int):
        if self.collecting:
            self.requests.append((contrib, value_bits))
            return jnp.zeros(self.g, dtype=jnp.int64)
        out = self.results[self._i]
        self._i += 1
        return out

    def compute(self):
        if self.requests:
            self.results = _fused_limb_sums(self.ids, self.requests,
                                            self.g)
        self.collecting = False

    def check_served(self):
        assert self._i == len(self.results), \
            (f"fused-sum pool drift: collected {len(self.results)} "
             f"requests, served {self._i}")


class _pooled:
    def __init__(self, pool):
        self.pool = pool

    def __enter__(self):
        self.prev = _seg_pool()
        _pool_tls.pool = self.pool
        return self.pool

    def __exit__(self, *exc):
        _pool_tls.pool = self.prev
        return False


def _seg_add(ids, contrib, max_groups: int,
             value_bits: int = 64) -> jnp.ndarray:
    """Per-group sum of `contrib` (already masked: dead rows contribute
    the dtype's zero). Small tables avoid TPU scatter: exact limb
    matmuls for integers (batched across aggregates through the ambient
    pool when one is installed), per-group masked reductions for
    floats."""
    if max_groups == 1:
        # global aggregation: ONE group -- a plain reduction beats any
        # scatter/matmul on every backend (contrib is pre-masked, and
        # integer sums here are exact by the callers' limb discipline)
        return jnp.sum(contrib)[None]
    if max_groups <= _SMALL_G and _scatter_free():
        if contrib.dtype in (jnp.int64, jnp.int32):
            pool = _seg_pool()
            # the pool batches by ITS captured ids: a caller grouping by
            # a transformed id array must not fold into it (identity
            # check is deterministic across the collect/serve walks)
            if pool is not None and ids is pool.ids:
                return pool.add(contrib.astype(jnp.int64), value_bits)
            return _limb_matmul_sum(ids, contrib, max_groups,
                                    value_bits=value_bits)
        zero = jnp.zeros((), dtype=contrib.dtype)
        return jnp.stack([jnp.sum(jnp.where(ids == g, contrib, zero))
                          for g in range(max_groups)])
    _note_form("scatter")
    return jnp.zeros(max_groups, dtype=contrib.dtype).at[ids].add(contrib)


def _seg_count(ids, flags, max_groups: int) -> jnp.ndarray:
    """Per-group count of True flags (int64)."""
    if max_groups == 1:
        return jnp.sum(flags.astype(jnp.int64))[None]
    if max_groups <= _SMALL_G and _scatter_free():
        pool = _seg_pool()
        if pool is not None and ids is pool.ids:
            return pool.add(flags.astype(jnp.int64), 1)
        return _limb_matmul_sum(ids, flags.astype(jnp.int64), max_groups,
                                value_bits=1)
    _note_form("scatter")
    return jnp.zeros(max_groups, dtype=jnp.int64).at[ids].add(
        flags.astype(jnp.int64))


def _seg_min(ids, contrib, max_groups: int, ident) -> jnp.ndarray:
    """Per-group min of `contrib` (dead rows pre-masked to `ident`)."""
    if max_groups == 1:
        return jnp.min(contrib)[None]
    if max_groups <= _SMALL_G and _scatter_free():
        return jnp.stack([jnp.min(jnp.where(ids == g, contrib, ident))
                          for g in range(max_groups)])
    return jnp.full(max_groups, ident, dtype=contrib.dtype).at[ids].min(contrib)


def _seg_max(ids, contrib, max_groups: int, ident) -> jnp.ndarray:
    if max_groups == 1:
        return jnp.max(contrib)[None]
    if max_groups <= _SMALL_G and _scatter_free():
        return jnp.stack([jnp.max(jnp.where(ids == g, contrib, ident))
                          for g in range(max_groups)])
    return jnp.full(max_groups, ident, dtype=contrib.dtype).at[ids].max(contrib)


def _sum128(ids, col, live, max_groups: int):
    """Exact per-group 128-bit sums (the SpillableHashAggregationBuilder
    never needs this in the reference because Java BigDecimal-backed
    states exist; here the TPU lanes are 64-bit, so sums that can exceed
    int64 decompose into 13-bit limbs whose int64/matmul totals are
    exact, then recombine into (hi, lo) once per group -- no 128-bit
    pairwise adds anywhere in the hot loop)."""
    from ..int128 import (combine_limb_totals_128, limbs13_of_128,
                          limbs13_of_i64)
    if isinstance(col, Int128Column):
        limbs = limbs13_of_128(col.hi, col.lo)  # 10 x int64
    else:
        # lane-width-proven limb count: narrowed int16/int32 lanes need
        # 2/3 limbs, not int64's 5 (the fused-pool matmul width and the
        # scatter count shrink with them)
        limbs = limbs13_of_i64(col.values, _nlimbs13(col.values))
    # every limb's magnitude is < 2^13 (signed top included), so one
    # 13-bit request suffices: f32 chunk sums stay exact
    # (2048 * 8191 < 2^24) and the bf16 form splits to its 8-bit limbs
    totals = [_seg_add(ids, jnp.where(live, l, 0), max_groups,
                       value_bits=13)
              for l in limbs]
    return combine_limb_totals_128(jnp.stack(totals, axis=-1))


@jax.named_scope("_group_ids_hash")
def _group_ids_hash(words, active: jnp.ndarray, max_groups: int):
    """Hash-slot kernel for large tables (see module docstring)."""
    n = active.shape[0]
    m = max(1024, 1 << int(max(2 * max_groups - 1, 1)).bit_length())
    mask = np.uint64(m - 1)
    h = _hash_words(words)
    rows = jnp.arange(n, dtype=jnp.int32)
    safe_hi = max(n - 1, 0)

    def cond(state):
        r, rep, slot_of = state
        return (r < _MAX_PROBES) & jnp.any(active & (slot_of < 0))

    def body(state):
        r, rep, slot_of = state
        unres = active & (slot_of < 0)
        # triangular probing: offsets 0,1,3,6,... cover every slot of a
        # power-of-two table exactly once over m rounds
        off = (r * (r + 1) // 2).astype(jnp.uint64)
        slot = ((h + off) & mask).astype(jnp.int32)
        occupied = rep[slot] < n
        claim = jnp.where(unres & ~occupied, rows, n)
        rep = rep.at[slot].min(claim)
        owner = rep[slot]
        match = unres & (owner < n)
        own = jnp.clip(owner, 0, safe_hi)
        for w in words:
            match = match & (w == w[own])
        slot_of = jnp.where(match, slot, slot_of)
        return r + jnp.int32(1), rep, slot_of

    _, rep, slot_of = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.full(m, n, dtype=jnp.int32),
                     jnp.full(n, -1, dtype=jnp.int32)))

    occupied = rep < n
    num_groups = jnp.sum(occupied.astype(jnp.int32))
    dense = jnp.cumsum(occupied.astype(jnp.int32)) - 1  # slot -> dense id
    unresolved = active & (slot_of < 0)
    overflow = (num_groups > max_groups) | jnp.any(unresolved)
    gid = jnp.clip(dense[jnp.clip(slot_of, 0, m - 1)], 0, max_groups - 1)
    # park inactive and probe-exhausted rows in the last slot (their
    # contributions are masked / invalidated by the overflow rerun)
    ids = jnp.where(active & (slot_of >= 0), gid, max_groups - 1) \
        .astype(jnp.int32)
    slot_gid = jnp.where(occupied, jnp.clip(dense, 0, max_groups - 1),
                         max_groups - 1)
    perm_first = jnp.zeros(max_groups, dtype=jnp.int32).at[slot_gid].max(
        jnp.where(occupied, jnp.clip(rep, 0, safe_hi), 0))
    return ids, perm_first, num_groups, overflow


@jax.named_scope("_group_ids_sort")
def _group_ids_sort(key_cols: Sequence[Block], active: jnp.ndarray,
                    max_groups: int):
    """Sort-based variant of _group_ids (kept for A/B measurement):
    lax.sort rows by key words, adjacent-inequality boundaries ->
    dense ids in key-sorted order."""
    n = active.shape[0]
    words, _ = key_words(key_cols)
    # inactive rows sort last: leading word 1 for inactive
    lead = jnp.where(active, np.uint64(0), np.uint64(1))
    operands = [lead, *words, jnp.arange(n, dtype=jnp.int32)]
    sorted_ops = lex_sort(operands, num_keys=len(operands) - 1)
    s_words = sorted_ops[:-1]
    perm = sorted_ops[-1]
    s_active = s_words[0] == 0
    # boundary where any word differs from previous row
    diffs = jnp.zeros(n, dtype=bool)
    for w in s_words:
        diffs = diffs | (w != jnp.concatenate([w[:1], w[:-1]]))
    diffs = diffs.at[0].set(False)
    seg = jnp.cumsum(diffs.astype(jnp.int32))  # dense ids in sorted order
    num_groups = jnp.where(jnp.any(s_active), seg[jnp.sum(s_active.astype(jnp.int32)) - 1] + 1, 0)
    overflow = num_groups > max_groups
    seg = jnp.minimum(seg, max_groups - 1)
    seg = jnp.where(s_active, seg, max_groups - 1)  # park inactive in last slot
    ids = jnp.zeros(n, dtype=jnp.int32).at[perm].set(seg)
    # representative row per group: first sorted row of each segment
    first_mask = (jnp.concatenate([jnp.ones(1, dtype=bool), diffs[1:]])) & s_active
    perm_first = jnp.zeros(max_groups, dtype=jnp.int32).at[
        jnp.where(first_mask, seg, max_groups - 1)].max(
        jnp.where(first_mask, perm, 0))
    return ids, perm_first, num_groups, overflow


from ..block import gather_block as _gather_block  # shared row gather


# ---------------------------------------------------------------------------
# Sorted-mode group-by: the large-table kernel (G in 2^7 .. 2^20+)
# ---------------------------------------------------------------------------
# XLA lowers big scatters to a serialized per-update loop on TPU (436 ms
# for ONE 6M->16 scatter-add on v5e; scripts/microbench_groupby.py), so
# the hash-slot kernel and its per-accumulator scatters cannot carry
# TPC-DS-scale cardinalities (MultiChannelGroupByHash.java:55 territory,
# G ~ 10^4..10^7). Sorted mode is scatter-free end to end:
#
#   1. ONE lax.sort of the key words (+ row ids) -- 30-90 ms at 6M rows
#      on v5e, amortized over every aggregate
#   2. segment boundaries by adjacent-word inequality; dense group ids
#      are positions in sorted order; per-group [start, end) row ranges
#      come from searchsorted over the (nondecreasing) segment ids
#   3. every accumulator is a segmented reduction in sorted order:
#      sums/counts via padded-cumsum gather-diffs (ints decompose into
#      13-bit limbs so int64 cumsums are exact); min/max/arbitrary via a
#      flag-reset segmented associative scan; bool_and/or via counts
#   4. count_distinct / approx_percentile piggyback on the SAME sort:
#      their value column's words append to the sort key, making equal
#      values adjacent within each group (distinct = first-occurrence
#      flags; percentile = direct index into the value-sorted segment)
#
# The dense output table gathers keys from each segment's first row.
# No scatter appears anywhere. This is the TPU answer to
# InMemoryHashAggregationBuilder: sort IS the hash table.

def _padded_cumsum(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.concatenate([jnp.zeros(1, dtype=x.dtype), jnp.cumsum(x)])


def _seg_total(x: jnp.ndarray, start: jnp.ndarray, end: jnp.ndarray):
    """Per-segment totals of x (sorted order) over [start, end) ranges."""
    p = _padded_cumsum(x)
    return p[end] - p[start]


def _seg_scan_extreme(new_seg: jnp.ndarray, val: jnp.ndarray,
                      minimize: bool) -> jnp.ndarray:
    """Flag-reset segmented running min/max (textbook segmented scan:
    combine((v1,f1),(v2,f2)) = (f2 ? v2 : op(v1,v2), f1|f2), associative
    for any grouping). Returns the running extreme; a segment's answer
    sits at its last row."""
    def comb(a, b):
        va, fa = a
        vb, fb = b
        m = jnp.minimum(va, vb) if minimize else jnp.maximum(va, vb)
        return jnp.where(fb, vb, m), fa | fb

    run, _ = jax.lax.associative_scan(comb, (val, new_seg))
    return run


def _seg_extreme_at(new_seg, val, start, end, ident, minimize):
    n = val.shape[0]
    run = _seg_scan_extreme(new_seg, val, minimize)
    res = run[jnp.clip(end - 1, 0, n - 1)]
    return jnp.where(end > start, res, ident)


_VALUE_ORDER_AGGS = ("count_distinct", "approx_percentile")


def _sorted_capable(batch: Batch, key_channels, aggs) -> bool:
    """Can this aggregation run in sorted mode? (Everything TPC-H/DS
    SQL produces can; exotic combinations fall back to the hash-slot
    kernel.)"""
    if not key_channels:
        return False
    # a masked value-order agg would miscount: the mask doesn't join the
    # sort, so a masked-off row can shadow a live duplicate's
    # first-occurrence flag. The hash path's dedicated kernel is exact.
    if any(s.mask_channel is not None and s.canonical in _VALUE_ORDER_AGGS
           for s in aggs):
        return False
    vo_chans = {s.input_channel for s in aggs
                if s.canonical in _VALUE_ORDER_AGGS}
    if len(vo_chans) > 1:
        return False  # only one column can piggyback on the sort order
    for s in aggs:
        c = s.canonical
        if c in ("min_by", "max_by"):
            return False
        if c in _PAIR_MOMENT_AGGS or c in ("geometric_mean", "checksum"):
            return False  # hash path carries these (6-moment states)
        if s.input_channel is None:
            continue
        col = batch.column(s.input_channel)
        if isinstance(col, DictionaryColumn):
            col = col.dictionary
        if isinstance(col, StringColumn) and c in ("min", "max"):
            return False
        if isinstance(col, Int128Column) and c in ("min", "max"):
            return False
    return True


def _sorted_states(spec: AggSpec, scol, live, start, end, new_seg,
                   s_active, pair_first, max_groups: int):
    """Sorted-order accumulator states for one aggregate; mirrors
    _acc_columns' state layout exactly (merge_spec/state_width parity)."""
    g = max_groups
    name = spec.canonical
    zeros_g = jnp.zeros(g, dtype=bool)
    if name == "count_star":
        if spec.mask_channel is not None:
            cnt = _seg_total(live.astype(jnp.int64), start, end)
        else:
            cnt = (end - start).astype(jnp.int64)
        return [("count", Column(cnt, zeros_g, T.BIGINT))]

    nn = _seg_total(live.astype(jnp.int64), start, end)
    no_input = nn == 0
    if name == "count":
        return [("count", Column(nn, zeros_g, T.BIGINT))]
    if name == "count_distinct":
        cnt = _seg_total((live & pair_first).astype(jnp.int64), start, end)
        return [("count", Column(cnt, zeros_g, T.BIGINT))]
    if name in ("approx_distinct", "hll_merge"):
        # the HLL scatter kernels are sort-order-agnostic: rebuild the
        # per-row segment ids from the boundary flags and reuse them
        seg_ids = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
        seg_ids = jnp.clip(seg_ids, 0, max(g - 1, 0))
        if name == "approx_distinct":
            regs = _hll_registers_from_values(scol, live, seg_ids, g)
        else:
            regs = _hll_registers_merge(scol, live, seg_ids, g)
        return [("hll", _hll_state_column(regs))]
    if name == "approx_percentile":
        assert spec.parameter is not None, "approx_percentile needs fraction"
        n = live.shape[0]
        # value-sorted segment, nulls last: live values sit at
        # [start, start+nn); answer at start + floor((nn-1)*p)
        target = start + jnp.floor(
            jnp.maximum(nn - 1, 0).astype(jnp.float64)
            * float(spec.parameter)).astype(jnp.int64)
        idx = jnp.clip(target, 0, max(n - 1, 0))
        got = _gather_block(scol, idx, ~no_input)
        return [("percentile", got)]

    if name == "arbitrary":
        n = live.shape[0]
        pos = jnp.where(live, jnp.arange(n, dtype=jnp.int64), n)
        first = _seg_extreme_at(new_seg, pos, start, end,
                                jnp.int64(n), minimize=True)
        valid = first < n
        got = _gather_block(scol, jnp.clip(first, 0, max(n - 1, 0)), valid)
        return [(name, got)]

    if name in ("sum", "avg") and (isinstance(scol, Int128Column)
                                   or scol.type.is_decimal):
        from ..int128 import (combine_limb_totals_128, limbs13_of_128,
                              limbs13_of_i64)
        sum_ty = spec.output_type if name == "sum" else _sum_type(scol.type)
        if isinstance(scol, Int128Column):
            limbs = limbs13_of_128(scol.hi, scol.lo)
        else:
            # lane-width-proven limb count (see _nlimbs13): narrowed
            # lanes pay 2-3 cumsums here instead of int64's 5
            limbs = limbs13_of_i64(scol.values, _nlimbs13(scol.values))
        totals = [_seg_total(jnp.where(live, l, 0), start, end)
                  for l in limbs]
        hi, lo = combine_limb_totals_128(jnp.stack(totals, axis=-1))
        out = [("sum", Int128Column(hi, lo, no_input, sum_ty))]
        if name == "avg":
            out.append(("count", Column(nn, zeros_g, T.BIGINT)))
        return out

    v = scol.values
    if name in ("sum", "avg"):
        sv = v.astype(_sum_dtype(scol.type))
        if sv.dtype == jnp.int64:
            # 13-bit limb cumsums keep every intermediate exact; the
            # limb count follows the lane's proven width (_nlimbs13)
            from ..int128 import limbs13_of_i64
            limbs = limbs13_of_i64(sv, _nlimbs13(v))
            tot = jnp.zeros(g, dtype=jnp.int64)
            for li, l in enumerate(limbs):
                tot = tot + (_seg_total(jnp.where(live, l, 0), start, end)
                             << (13 * li))
            s = tot
        else:
            s = _seg_total(jnp.where(live, sv, sv.dtype.type(0)), start, end)
        out = [("sum", Column(s, no_input, spec.output_type if name == "sum"
                              else _sum_type(scol.type)))]
        if name == "avg":
            out.append(("count", Column(nn, zeros_g, T.BIGINT)))
        return out
    if name in ("min", "max"):
        minimize = name == "min"
        ident = _max_ident(v.dtype) if minimize else _min_ident(v.dtype)
        val = jnp.where(live, v, ident)
        m = _seg_extreme_at(new_seg, val, start, end, ident, minimize)
        return [(name, Column(m, no_input, spec.output_type))]
    if name in ("bool_and", "bool_or"):
        if name == "bool_and":
            bad = _seg_total((live & ~v).astype(jnp.int64), start, end)
            out_v = bad == 0
        else:
            good = _seg_total((live & v).astype(jnp.int64), start, end)
            out_v = good > 0
        return [(name, Column(out_v, no_input, T.BOOLEAN))]
    if name in ("var_samp", "var_pop", "stddev_samp", "stddev_pop"):
        f = v.astype(jnp.float64)
        if scol.type.is_decimal:
            from ..expr.functions import _POW10
            f = f / _POW10[scol.type.scale]
        s = _seg_total(jnp.where(live, f, 0.0), start, end)
        s2 = _seg_total(jnp.where(live, f * f, 0.0), start, end)
        return [("count", Column(nn, zeros_g, T.BIGINT)),
                ("sum", Column(s, no_input, T.DOUBLE)),
                ("sumsq", Column(s2, no_input, T.DOUBLE))]
    raise NotImplementedError(f"sorted-mode aggregate {spec.name!r}")


@jax.named_scope("_group_by_sorted")
def _group_by_sorted(batch: Batch, key_channels, aggs, max_groups: int
                     ) -> "GroupByResult":
    """Sorted-mode group_by (see block comment above)."""
    n = batch.capacity
    keys = [batch.column(c) for c in key_channels]
    words, _ = key_words(keys)
    lead = jnp.where(batch.active, np.uint64(0), np.uint64(1))
    ops = [lead, *words]
    nkw = len(words)
    # value-order piggyback: count_distinct / approx_percentile columns
    # sort WITHIN each group (nulls last so live values are a prefix)
    vo_chans = [s.input_channel for s in aggs
                if s.canonical in _VALUE_ORDER_AGGS]
    n_pair_words = 0
    if vo_chans:
        vo_col = batch.column(vo_chans[0])
        vwords, _ = key_words([vo_col], nulls_last=True)
        ops.extend(vwords)
        n_pair_words = len(vwords)
    ops.append(jnp.arange(n, dtype=jnp.int32))
    out = lex_sort(ops, num_keys=len(ops) - 1)
    s_lead = out[0]
    s_words = out[1:1 + nkw]
    s_pair_words = out[1 + nkw:1 + nkw + n_pair_words]
    perm = out[-1]
    s_active = s_lead == 0

    diffs = jnp.zeros(n, dtype=bool)
    for w in s_words:
        diffs = diffs | (w != jnp.concatenate([w[:1], w[:-1]]))
    diffs = diffs.at[0].set(False)
    seg = jnp.cumsum(diffs.astype(jnp.int32))
    new_seg = diffs.at[0].set(True)
    # distinct-value first-occurrence flags (pair = keys ++ value words)
    pair_first = diffs
    for w in s_pair_words:
        pair_first = pair_first | (w != jnp.concatenate([w[:1], w[:-1]]))
    pair_first = pair_first.at[0].set(True)

    n_act = jnp.sum(s_active.astype(jnp.int32))
    num_groups = jnp.where(n_act > 0,
                           seg[jnp.clip(n_act - 1, 0, max(n - 1, 0))] + 1, 0)
    overflow = num_groups > max_groups

    # per-slot [start, end) ranges; inactive rows get a sentinel segment
    seg_search = jnp.where(s_active, seg, jnp.int32(0x7FFFFFFF))
    gids = jnp.arange(max_groups, dtype=jnp.int32)
    start = jnp.searchsorted(seg_search, gids, side="left")
    end = jnp.searchsorted(seg_search, gids, side="right")
    slot_active = gids < jnp.minimum(num_groups, max_groups)

    perm_first = perm[jnp.clip(start, 0, max(n - 1, 0))]
    out_cols: List[Block] = [
        _gather_block(k, perm_first, slot_active) for k in keys]

    sorted_cols: dict = {}

    def sorted_col(ch: int):
        if ch not in sorted_cols:
            c = batch.column(ch)
            if isinstance(c, DictionaryColumn):
                c = c.decode()
            sorted_cols[ch] = _gather_block(c, perm)
        return sorted_cols[ch]

    for spec in aggs:
        act = s_active
        if spec.mask_channel is not None:
            m = sorted_col(spec.mask_channel)
            act = act & m.values.astype(bool) & ~m.nulls
        if spec.input_channel is None:
            scol, live = None, act
        else:
            scol = sorted_col(spec.input_channel)
            live = act & ~scol.nulls
        for _, state in _sorted_states(spec, scol, live, start, end,
                                       new_seg, s_active, pair_first,
                                       max_groups):
            out_cols.append(state)
    return GroupByResult(Batch(tuple(out_cols), slot_active),
                         num_groups, overflow)


def _hll_registers_from_values(col: Block, live, ids, g: int) -> jnp.ndarray:
    """(g, m) int8 register matrix: scatter-max of leading-zero ranks.
    Works for every key-able Block kind (hash via ops.keys words)."""
    vwords, _ = key_words([col])
    h = _hash_words(vwords[1:])  # value words only; nulls excluded by live
    reg = (h >> np.uint64(64 - _HLL_P)).astype(jnp.int64)
    w = (h << np.uint64(_HLL_P)).astype(jnp.uint64)
    rank = jnp.where(w == 0, 64 - _HLL_P + 1,
                     jax.lax.clz(w) + 1).astype(jnp.int8)
    flat = jnp.where(live, ids.astype(jnp.int64) * _HLL_M + reg,
                     g * _HLL_M)
    regs = jnp.zeros(g * _HLL_M + 1, dtype=jnp.int8).at[flat].max(
        jnp.where(live, rank, jnp.int8(0)))
    return regs[:g * _HLL_M].reshape(g, _HLL_M)


def _hll_registers_merge(col, live, ids, g: int) -> jnp.ndarray:
    """Merge partial register vectors (ArrayColumn rows) per group:
    elementwise max -- the HLL union, exact over merges."""
    from ..block import ArrayColumn
    assert isinstance(col, ArrayColumn), type(col)
    elems = col.elements.astype(jnp.int8)
    contrib = jnp.where(live[:, None], elems, jnp.int8(0))
    safe = jnp.where(live, ids, g).astype(jnp.int32)
    regs = jnp.zeros((g + 1, _HLL_M), dtype=jnp.int8).at[safe].max(contrib)
    return regs[:g]


def _hll_state_column(regs: jnp.ndarray) -> "Block":
    from ..block import ArrayColumn
    g = regs.shape[0]
    return ArrayColumn(regs, jnp.zeros_like(regs, dtype=bool),
                       jnp.full(g, _HLL_M, dtype=jnp.int32),
                       jnp.zeros(g, dtype=bool), hll_state_type())


def hll_estimate(regs: jnp.ndarray) -> jnp.ndarray:
    """Registers (g, m) -> int64 cardinality estimates (the standard
    HLL estimator + linear counting in the small range)."""
    m = float(_HLL_M)
    r = regs.astype(jnp.float64)
    z = jnp.sum(jnp.exp2(-r), axis=1)
    alpha = 0.7213 / (1 + 1.079 / m)
    e = alpha * m * m / z
    v = jnp.sum(regs == 0, axis=1)
    lin = m * jnp.log(m / jnp.maximum(v, 1).astype(jnp.float64))
    est = jnp.where((e <= 2.5 * m) & (v > 0), lin, e)
    return jnp.round(est).astype(jnp.int64)


def _masked_active(batch: Batch, spec: AggSpec) -> jnp.ndarray:
    """Rows this aggregate consumes: batch.active further restricted by
    the spec's BOOLEAN mask column (NULL mask = excluded)."""
    if spec.mask_channel is None:
        return batch.active
    mc = batch.column(spec.mask_channel)
    if isinstance(mc, DictionaryColumn):
        mc = mc.decode()
    return batch.active & mc.values.astype(bool) & ~mc.nulls


def _sum_dtype(ty: T.Type):
    if ty.is_floating:
        return jnp.float64
    return jnp.int64


def _lane_bits(values) -> int:
    """Proven bit width of a value lane: the PHYSICAL dtype's width.
    Narrow-width execution stages range-proven columns at int8/16/32
    lanes (plan/widths.py), so the staged dtype is itself a proof of
    the value range -- the exact-sum limb decompositions need only
    cover it (int16 lanes: 2 13-bit limbs, not int64's 5), shrinking
    the one-hot matmul / scatter / cumsum work per aggregate."""
    dt = jnp.dtype(values.dtype) if hasattr(values, "dtype") else None
    if dt is not None and dt.kind in "iu":
        return dt.itemsize * 8
    if dt is not None and dt.kind == "b":
        return 1
    return 64


def _nlimbs13(values) -> int:
    """13-bit limbs covering a lane's proven width (signed top limb:
    ceil(bits/13) limbs span bits+ (13-bits%13) with the sign riding
    the arithmetic-shift remainder -- int64's historical 5)."""
    return max(-(-_lane_bits(values) // 13), 1)


def _acc_columns(spec: AggSpec, col: Optional[Block], ids, active, max_groups: int,
                 batch: Optional[Batch] = None,
                 overflow_out: Optional[list] = None) -> List[Tuple[str, Column]]:
    """Compute accumulator state tables for one aggregate. Returns a list
    of named state columns (avg and the variance family need several).
    Aggregates that run their own group-id kernel (count_distinct)
    append that kernel's overflow flag to `overflow_out`."""
    g = max_groups
    name = spec.canonical
    if name == "count_star":
        cnt = _seg_count(ids, active, g)
        return [("count", Column(cnt, jnp.zeros(g, dtype=bool), T.BIGINT))]

    assert col is not None
    if isinstance(col, DictionaryColumn):
        col = col.decode()
    live = active & ~col.nulls
    nn = _seg_count(ids, live, g)
    no_input = nn == 0

    if name == "count":
        return [("count", Column(nn, jnp.zeros(g, dtype=bool), T.BIGINT))]

    if name == "count_distinct":
        assert batch is not None
        # exact: mark first occurrence of each (group, value) pair --
        # works for any key-able type incl. strings. Pair count is
        # bounded by the row count, so a row-count-sized table cannot
        # exceed capacity; probe-budget exhaustion still flags overflow
        # (the hash kernel's rerun contract) via overflow_out.
        from .misc import mark_distinct
        sub = Batch((Column(ids, jnp.zeros_like(live), T.INTEGER), col),
                    live)
        first, ovf = mark_distinct(sub, [0, 1], max_groups=len(col))
        if overflow_out is not None:
            overflow_out.append(ovf)
        cnt = _seg_count(ids, first & live, g)
        return [("count", Column(cnt, jnp.zeros(g, dtype=bool), T.BIGINT))]

    if name == "approx_distinct":
        regs = _hll_registers_from_values(col, live, ids, g)
        return [("hll", _hll_state_column(regs))]
    if name == "hll_merge":
        regs = _hll_registers_merge(col, live, ids, g)
        return [("hll", _hll_state_column(regs))]

    if name == "checksum":
        # order-independent 64-bit checksum: wrapping int64 sum of
        # per-row value hashes (hash64_block handles string/int128/
        # fixed-width blocks alike); NULL rows contribute a constant
        from ..expr.functions import hash64_block
        h = hash64_block(col).astype(jnp.int64)
        # the golden-ratio constant as SIGNED int64 (wrapping sum domain)
        h = jnp.where(col.nulls & active,
                      jnp.int64(-7046029254386353131),
                      jnp.where(live, h, 0))
        cnt_all = _seg_count(ids, active, g)
        return [("checksum", Column(_seg_add(ids, h, g), cnt_all == 0,
                                    T.BIGINT))]

    if isinstance(col, StringColumn):
        if name in ("min", "max"):
            return _minmax_string(col, ids, live, g, spec)
        raise NotImplementedError(f"{spec.name} over strings")

    if isinstance(col, Int128Column) or (
            name in ("sum", "avg") and col.type.is_decimal):
        # decimal sums always produce decimal(38, s) -- a LONG decimal --
        # so they accumulate exactly in 128 bits: per-limb totals (exact
        # int64 everywhere) recombine into (hi, lo) once per group.
        # Int128-lane inputs take the same path for min/max via argbest.
        if name in ("sum", "avg"):
            sum_ty = spec.output_type if name == "sum" \
                else _sum_type(col.type)
            hi, lo = _sum128(ids, col, live, g)
            out = [("sum", Int128Column(hi, lo, no_input, sum_ty))]
            if name == "avg":
                out.append(("count",
                            Column(nn, jnp.zeros(g, dtype=bool), T.BIGINT)))
            return out
        if isinstance(col, Int128Column):
            if name in ("min", "max"):
                from .keys import _SIGN
                words = [col.hi.astype(jnp.uint64) ^ _SIGN, col.lo]
                row_best = _argbest(words, ids, live, g,
                                    minimize=(name == "min"))
                n = len(col)
                valid = row_best < n
                idx = jnp.clip(row_best, 0, n - 1)
                return [(name, Int128Column(col.hi[idx], col.lo[idx],
                                            ~valid | col.nulls[idx],
                                            spec.output_type))]
            raise NotImplementedError(f"{spec.name} over long decimals")

    v = col.values
    if name == "sum" or name == "avg":
        sv = v.astype(_sum_dtype(col.type))
        s = _seg_add(ids, jnp.where(live, sv, sv.dtype.type(0)), g,
                     value_bits=_lane_bits(v))
        out = [("sum", Column(s, no_input, spec.output_type if name == "sum"
                              else _sum_type(col.type)))]
        if name == "avg":
            out.append(("count", Column(nn, jnp.zeros(g, dtype=bool), T.BIGINT)))
        return out
    if name == "min":
        ident = _max_ident(v.dtype)
        m = _seg_min(ids, jnp.where(live, v, ident), g, ident)
        return [("min", Column(m, no_input, spec.output_type))]
    if name == "max":
        ident = _min_ident(v.dtype)
        m = _seg_max(ids, jnp.where(live, v, ident), g, ident)
        return [("max", Column(m, no_input, spec.output_type))]
    if name in ("bool_and", "bool_or"):
        bv = v.astype(jnp.int32)
        if name == "bool_and":
            m = _seg_min(ids, jnp.where(live, bv, 1), g, 1)
        else:
            m = _seg_max(ids, jnp.where(live, bv, 0), g, 0)
        return [(name, Column(m.astype(bool), no_input, T.BOOLEAN))]
    if name in ("var_samp", "var_pop", "stddev_samp", "stddev_pop"):
        # (count, sum, sum of squares) in float64; finalization happens in
        # finalize_variance (exec layer / merge side)
        f = v.astype(jnp.float64)
        if col.type.is_decimal:
            from ..expr.functions import _POW10
            f = f / _POW10[col.type.scale]
        s = _seg_add(ids, jnp.where(live, f, 0.0), g)
        s2 = _seg_add(ids, jnp.where(live, f * f, 0.0), g)
        return [("count", Column(nn, jnp.zeros(g, dtype=bool), T.BIGINT)),
                ("sum", Column(s, no_input, T.DOUBLE)),
                ("sumsq", Column(s2, no_input, T.DOUBLE))]
    if name in _PAIR_MOMENT_AGGS:
        # six moments over rows where BOTH inputs are non-null
        assert batch is not None and spec.second_channel is not None
        ycol = col
        xcol = batch.column(spec.second_channel)
        if isinstance(xcol, DictionaryColumn):
            xcol = xcol.decode()
        pair_live = active & ~ycol.nulls & ~xcol.nulls
        from ..expr.functions import decimal_to_f64
        y = decimal_to_f64(ycol)
        x = decimal_to_f64(xcol)
        npair = _seg_count(ids, pair_live, g)
        z = jnp.float64(0.0)
        states = [
            ("count", Column(npair, jnp.zeros(g, dtype=bool), T.BIGINT)),
            ("sy", Column(_seg_add(ids, jnp.where(pair_live, y, z), g),
                          npair == 0, T.DOUBLE)),
            ("sx", Column(_seg_add(ids, jnp.where(pair_live, x, z), g),
                          npair == 0, T.DOUBLE)),
            ("syy", Column(_seg_add(ids, jnp.where(pair_live, y * y, z), g),
                           npair == 0, T.DOUBLE)),
            ("sxx", Column(_seg_add(ids, jnp.where(pair_live, x * x, z), g),
                           npair == 0, T.DOUBLE)),
            ("sxy", Column(_seg_add(ids, jnp.where(pair_live, y * x, z), g),
                           npair == 0, T.DOUBLE)),
        ]
        return states
    if name == "geometric_mean":
        # (count, sum of ln x); nonpositive inputs poison the group to
        # NaN exactly like ln() would (reference behavior)
        from ..expr.functions import decimal_to_f64
        logs = jnp.log(jnp.where(live, decimal_to_f64(col), 1.0))
        return [("count", Column(nn, jnp.zeros(g, dtype=bool), T.BIGINT)),
                ("slog", Column(_seg_add(ids, jnp.where(live, logs, 0.0), g),
                                no_input, T.DOUBLE))]
    if name == "arbitrary":
        row_best = _argbest([jnp.zeros(len(col), dtype=jnp.uint64)], ids,
                            live, g, minimize=True)
        n = len(col)
        valid = row_best < n
        idx = jnp.clip(row_best, 0, n - 1)
        return [(name, Column(v[idx], ~valid, spec.output_type))]
    if name in ("min_by", "max_by"):
        assert batch is not None
        order_col = batch.column(spec.second_channel)
        if isinstance(order_col, DictionaryColumn):
            order_col = order_col.decode()
        # Presto semantics: the winner is the row with the extreme ORDER
        # value among non-null-order rows; a NULL value at that row is
        # returned as NULL (so do NOT exclude value-nulls here)
        live = active & ~order_col.nulls
        order_words, _ = key_words([order_col])
        order_words = order_words[1:]  # drop the null word (masked above)
        row_best = _argbest(order_words, ids, live, g,
                            minimize=(name == "min_by"))
        n = len(col)
        valid = row_best < n
        idx = jnp.clip(row_best, 0, n - 1)
        # state = (winning value, winning order value) -- the order value
        # makes partial states mergeable (merge re-runs min_by on states)
        oty = spec.second_type or order_col.type
        return [(name, Column(v[idx], ~valid | col.nulls[idx],
                              spec.output_type)),
                ("order", Column(order_col.values[idx], ~valid, oty))]
    if name == "approx_percentile":
        # computed EXACTLY via sort (the reference uses KLL/tdigest
        # sketches for mergeable states -- those land with the sketch
        # library; exact is within any epsilon): rows sort by (group id,
        # value); each group's answer sits at start + floor((n-1)*p).
        assert spec.parameter is not None, "approx_percentile needs fraction"
        p = float(spec.parameter)
        n = len(col)
        vwords, _ = key_words([col])
        vwords = vwords[1:]  # drop null word; dead rows masked via lead
        lead = jnp.where(live, np.uint64(0), np.uint64(1))
        ops_ = [lead, ids.astype(jnp.uint64), *vwords,
                jnp.arange(n, dtype=jnp.int32)]
        perm = lex_sort(ops_, num_keys=len(ops_) - 1)[-1]
        pos = jnp.arange(n, dtype=jnp.int64)
        sorted_ids = jnp.where(live[perm], ids[perm], g)
        start = _seg_min(jnp.clip(sorted_ids, 0, g - 1),
                         jnp.where(sorted_ids < g, pos, n), g, n)
        target = start + jnp.floor((nn - 1).astype(jnp.float64) * p).astype(jnp.int64)
        target = jnp.clip(target, 0, n - 1)
        rows_sel = perm[target]
        vals = v[rows_sel]
        return [("percentile", Column(vals, no_input, spec.output_type))]
    raise NotImplementedError(f"aggregate function {spec.name!r}")


def _argbest(order_words: List[jnp.ndarray], ids, live, g, minimize: bool):
    """Row index of the min (or max) order-key per group; ties -> lowest
    row. Returns g-length int array; n (out of range) when group empty."""
    n = live.shape[0]
    if g <= _SMALL_G and _scatter_free():
        # per-group masked lexicographic reduction (no scatters)
        full = jnp.uint64(0xFFFFFFFFFFFFFFFF)
        rows = jnp.arange(n, dtype=jnp.int64)
        out = []
        for k in range(g):
            rem = live & (ids == k)
            for wk in order_words:
                sel = jnp.where(rem, wk, full if minimize else jnp.uint64(0))
                best = jnp.min(sel) if minimize else jnp.max(sel)
                rem = rem & (wk == best)
            out.append(jnp.min(jnp.where(rem, rows, n)))
        return jnp.stack(out)
    remaining = live
    w_prev = None
    best_prev = None
    for wk in order_words:
        if w_prev is not None:
            remaining = remaining & (w_prev == best_prev[ids])
        if minimize:
            sel = jnp.where(remaining, wk, jnp.uint64(0xFFFFFFFFFFFFFFFF))
            bk = jnp.full(g, np.uint64(0xFFFFFFFFFFFFFFFF),
                          dtype=jnp.uint64).at[ids].min(sel)
        else:
            sel = jnp.where(remaining, wk, jnp.uint64(0))
            bk = jnp.zeros(g, dtype=jnp.uint64).at[ids].max(sel)
        w_prev, best_prev = wk, bk
    winners = remaining & (w_prev == best_prev[ids])
    row_sel = jnp.where(winners, jnp.arange(n, dtype=jnp.int64), n)
    return jnp.full(g, n, dtype=jnp.int64).at[ids].min(row_sel)


def _sum_type(in_ty: T.Type) -> T.Type:
    if in_ty.is_decimal:
        return T.decimal(38, in_ty.scale)
    if in_ty.is_floating:
        return T.DOUBLE
    return T.BIGINT


def _max_ident(dt):
    return jnp.inf if dt in (jnp.float32, jnp.float64) else jnp.iinfo(dt).max


def _min_ident(dt):
    return -jnp.inf if dt in (jnp.float32, jnp.float64) else jnp.iinfo(dt).min


def _minmax_string(col: StringColumn, ids, live, g, spec):
    """min/max over strings: per-group lexicographic argbest over the
    packed big-endian key words, then gather the winning row's chars
    (small tables reduce per group, large tables scatter-min/max with
    iterative tie refinement -- both inside _argbest)."""
    from .keys import _string_words
    words = _string_words(col)
    n = col.chars.shape[0]
    best_row = _argbest(words, ids, live, g,
                        minimize=(spec.name == "min"))
    valid = best_row < n
    idx = jnp.clip(best_row, 0, n - 1)
    return [(spec.name,
             StringColumn(col.chars[idx], jnp.where(valid, col.lengths[idx], 0),
                          ~valid, spec.output_type))]


import os as _os

# A/B override for the large-table kernel: "sort" (default; scatter-free
# segmented reductions) or "hash" (the scatter-based hash-slot kernel)
_LARGE_G_MODE = _os.environ.get("PRESTO_TPU_GROUPBY", "sort")


def group_by(batch: Batch, key_channels: Sequence[int], aggs: Sequence[AggSpec],
             max_groups: int) -> GroupByResult:
    """Grouped aggregation over one batch -> dense group table.

    Global aggregation (no keys) always yields exactly one group, even
    over zero input rows -- SQL's `SELECT count(*) ... -> 0` contract."""
    if not key_channels:
        # global aggregation: exactly one group, ever. A wider declared
        # capacity (the planner's generic max_groups default) would pad
        # EVERY accumulator table and scatter/einsum to it -- q6's
        # whole aggregate state is one row, not 2^16
        max_groups = 1
    if max_groups > _SMALL_G and _LARGE_G_MODE == "sort" \
            and _sorted_capable(batch, key_channels, aggs):
        return _group_by_sorted(batch, key_channels, aggs, max_groups)
    keys = [batch.column(c) for c in key_channels]
    ids, perm_first, num_groups, overflow = _group_ids(keys, batch.active, max_groups)
    if not key_channels:
        num_groups = jnp.maximum(num_groups, 1)
    slot = jnp.arange(max_groups, dtype=jnp.int32)
    slot_active = slot < jnp.minimum(num_groups, max_groups)
    out_cols: List[Block] = []
    sub_overflow: List = []
    for k in keys:
        out_cols.append(_gather_block(k, perm_first, slot_active))
    # fused single-pass accumulation (narrow-width execution): a collect
    # pass walks the spec list once to discover every integer seg-sum,
    # ONE one-hot matmul computes them all, then the real walk serves
    # the batched totals -- the columns and ids are read once for the
    # whole aggregate list instead of once per accumulator. The collect
    # pass's other outputs are discarded (XLA dead-code-eliminates
    # them); count_distinct is excluded because its mark-distinct
    # while-loop feeds a pooled contrib and would trace live twice.
    pool = None
    if (max_groups <= _SMALL_G and _scatter_free() and _narrow_kernels()
            and aggs and not any(s.canonical == "count_distinct"
                                 for s in aggs)):
        pool = _SegSumPool(ids, max_groups)
        with _pooled(pool):
            for spec in aggs:
                col = None if spec.input_channel is None \
                    else batch.column(spec.input_channel)
                _acc_columns(spec, col, ids, _masked_active(batch, spec),
                             max_groups, batch, overflow_out=None)
        pool.compute()
    with _pooled(pool):
        for spec in aggs:
            col = None if spec.input_channel is None \
                else batch.column(spec.input_channel)
            for _, state in _acc_columns(spec, col, ids,
                                         _masked_active(batch, spec),
                                         max_groups, batch,
                                         overflow_out=sub_overflow):
                out_cols.append(state)
    if pool is not None:
        pool.check_served()
    for f in sub_overflow:
        overflow = overflow | f
    out = Batch(tuple(out_cols), slot_active)
    return GroupByResult(out, num_groups, overflow, counted=False)


def grouped_aggregate(batch: Batch, key_channels: Sequence[int],
                      aggs: Sequence[AggSpec], max_groups: int) -> GroupByResult:
    """Alias with the reference's operator naming."""
    return group_by(batch, key_channels, aggs, max_groups)


def state_width(spec: AggSpec) -> int:
    c = spec.canonical
    if c == "avg":
        return 2
    if c in ("var_samp", "var_pop", "stddev_samp", "stddev_pop"):
        return 3
    if c in ("min_by", "max_by"):
        return 2
    if c in _PAIR_MOMENT_AGGS:
        return 6
    if c == "geometric_mean":
        return 2
    return 1


def merge_spec(spec: AggSpec, state_channel: int) -> List[AggSpec]:
    """The merge-side aggregates for a partial state at `state_channel`
    (final aggregation step: sum<-sum, count<-sum, min<-min, max<-max,
    avg <- (sum of sums, sum of counts), variance <- moment sums,
    min_by/max_by <- min_by over (value, order) states)."""
    c = spec.canonical
    if c == "sum":
        return [AggSpec("sum", state_channel, spec.output_type)]
    if c in ("count", "count_star"):
        return [AggSpec("sum", state_channel, T.BIGINT)]
    if c == "min":
        return [AggSpec("min", state_channel, spec.output_type)]
    if c == "max":
        return [AggSpec("max", state_channel, spec.output_type)]
    if c in ("bool_and", "bool_or"):
        return [AggSpec(c, state_channel, T.BOOLEAN)]
    if c == "avg":
        # the sum state keeps the avg's scale: downstream finalizers
        # (divide sum/count) read the block's type metadata for rescaling
        sum_ty = T.decimal(38, spec.output_type.scale) \
            if spec.output_type.is_decimal else T.DOUBLE
        return [AggSpec("sum", state_channel, sum_ty),
                AggSpec("sum", state_channel + 1, T.BIGINT)]
    if c in ("var_samp", "var_pop", "stddev_samp", "stddev_pop"):
        return [AggSpec("sum", state_channel, T.BIGINT),
                AggSpec("sum", state_channel + 1, T.DOUBLE),
                AggSpec("sum", state_channel + 2, T.DOUBLE)]
    if c in _PAIR_MOMENT_AGGS:
        return [AggSpec("sum", state_channel, T.BIGINT)] + \
            [AggSpec("sum", state_channel + i, T.DOUBLE)
             for i in range(1, 6)]
    if c == "geometric_mean":
        return [AggSpec("sum", state_channel, T.BIGINT),
                AggSpec("sum", state_channel + 1, T.DOUBLE)]
    if c == "checksum":
        return [AggSpec("sum", state_channel, T.BIGINT)]
    if c in ("min_by", "max_by"):
        # min_by over the (value, order) state re-emits BOTH columns
        # (value + winning order), keeping state_width stable at 2
        return [AggSpec(c, state_channel, spec.output_type,
                        second_channel=state_channel + 1,
                        second_type=spec.second_type)]
    if c == "arbitrary":
        return [AggSpec("arbitrary", state_channel, spec.output_type)]
    if c == "approx_distinct":
        # register vectors union by elementwise max -- exactly mergeable
        # across PARTIAL tables, workers, and the mesh
        return [AggSpec("hll_merge", state_channel, T.BIGINT)]
    if c in ("count_distinct", "approx_percentile"):
        raise NotImplementedError(
            f"{spec.name} states don't merge across partials; distributed "
            "plans must hash-exchange raw rows by the group keys first, "
            "then aggregate in one step (the standard mark_distinct plan "
            "shape; sketch states arrive with the KLL/HLL library)")
    raise NotImplementedError(spec.name)


def finalize_pair_moments(c: str, n, sy, sx, syy, sxx, sxy):
    """(n, sy, sx, syy, sxx, sxy) -> (value, nulls) for the two-input
    statistics family. Population co-moments: cxy = sxy - sx*sy/n."""
    nf = n.astype(jnp.float64)
    safe_n = jnp.maximum(nf, 1.0)
    cxy = sxy - sx * sy / safe_n
    cxx = jnp.maximum(sxx - sx * sx / safe_n, 0.0)
    cyy = jnp.maximum(syy - sy * sy / safe_n, 0.0)
    if c == "covar_pop":
        v = cxy / safe_n
        nulls = n < 1
    elif c == "covar_samp":
        v = cxy / jnp.maximum(nf - 1.0, 1.0)
        nulls = n < 2
    elif c == "corr":
        denom = jnp.sqrt(cxx * cyy)
        v = jnp.where(denom > 0, cxy / jnp.maximum(denom, 1e-300), 0.0)
        nulls = (n < 2) | (denom <= 0)
    elif c == "regr_slope":
        v = jnp.where(cxx > 0, cxy / jnp.maximum(cxx, 1e-300), 0.0)
        nulls = (n < 2) | (cxx <= 0)
    else:  # regr_intercept
        slope = jnp.where(cxx > 0, cxy / jnp.maximum(cxx, 1e-300), 0.0)
        v = (sy - slope * sx) / safe_n
        nulls = (n < 2) | (cxx <= 0)
    return v, nulls


def finalize_variance(spec: AggSpec, count: jnp.ndarray, s: jnp.ndarray,
                      s2: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(count, sum, sumsq) moments -> (value, nulls) for the variance
    family. var = (sumsq - sum^2/n) / (n - ddof)."""
    c = spec.canonical
    ddof = 1 if c in ("var_samp", "stddev_samp") else 0
    n = count.astype(jnp.float64)
    denom = jnp.maximum(n - ddof, 1.0)
    var = (s2 - s * s / jnp.maximum(n, 1.0)) / denom
    var = jnp.maximum(var, 0.0)  # numeric floor
    if c.startswith("stddev"):
        var = jnp.sqrt(var)
    nulls = count < (2 if ddof else 1)
    return var, nulls


def finalize_states(table: Batch, num_keys: int, aggs: Sequence[AggSpec]
                    ) -> Batch:
    """State table (keys..., states...) -> finalized output: exactly ONE
    column per aggregate, in spec order.

    This is the evaluateFinal step of the reference's accumulators
    (operator/aggregation/GroupedAccumulator, InMemoryHashAggregationBuilder):
    SINGLE and FINAL aggregation steps emit finalized values; only
    PARTIAL/INTERMEDIATE steps ship raw states. avg divides sum by count
    (exact int128 half-away rounding for decimals via the registered
    `divide` kernel); the variance family folds its (count, sum, sumsq)
    moments; min_by/max_by drop the bookkeeping order column."""
    cols: List[Block] = list(table.columns[:num_keys])
    ch = num_keys
    for spec in aggs:
        w = state_width(spec)
        states = table.columns[ch:ch + w]
        ch += w
        c = spec.canonical
        if c == "avg":
            from ..expr.functions import lookup
            cols.append(lookup("divide").fn(spec.output_type,
                                            states[0], states[1]))
        elif c in ("var_samp", "var_pop", "stddev_samp", "stddev_pop"):
            cnt, s, s2 = states
            v, nulls = finalize_variance(spec, cnt.values, s.values, s2.values)
            cols.append(Column(v, nulls, T.DOUBLE))
        elif c in _PAIR_MOMENT_AGGS:
            cnt, sy, sx, syy, sxx, sxy = states
            v, nulls = finalize_pair_moments(
                c, cnt.values, sy.values, sx.values, syy.values,
                sxx.values, sxy.values)
            cols.append(Column(v, nulls, T.DOUBLE))
        elif c == "geometric_mean":
            cnt, slog = states
            n = jnp.maximum(cnt.values.astype(jnp.float64), 1.0)
            cols.append(Column(jnp.exp(slog.values / n),
                               cnt.values == 0, T.DOUBLE))
        elif c == "approx_distinct":
            est = hll_estimate(states[0].elements)
            cols.append(Column(est, jnp.zeros(len(est), dtype=bool),
                               T.BIGINT))
        else:
            # single-state aggregates pass through; min_by/max_by keep
            # only the value column (states[0])
            cols.append(states[0])
    return Batch(tuple(cols), table.active)


def merge_partials(partials: Batch, num_keys: int, aggs: Sequence[AggSpec],
                   max_groups: int) -> GroupByResult:
    """Final aggregation over concatenated partial tables (the
    INTERMEDIATE/FINAL step of the reference's two-stage aggregation)."""
    specs: List[AggSpec] = []
    ch = num_keys
    for spec in aggs:
        specs.extend(merge_spec(spec, ch))
        ch += state_width(spec)
    return group_by(partials, list(range(num_keys)), specs, max_groups)
