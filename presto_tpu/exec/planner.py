"""Plan -> executable lowering: the LocalExecutionPlanner analog.

Reference surface: sql/planner/LocalExecutionPlanner.java:480+ (PlanNode
visitor emitting OperatorFactory chains: visitTableScan:1711,
visitAggregation:1459, visitJoin:2033, visitExchange:3224) and, on the
native side, PrestoToVeloxQueryPlan.cpp (PlanFragment -> Velox plan).

Here lowering emits ONE pure function over scan batches. Stage
boundaries (REMOTE exchanges) lower to mesh collectives, so a
multi-stage distributed plan becomes a single SPMD program under
shard_map -- XLA gang-schedules what SqlQueryScheduler orchestrates by
hand. Without a mesh the same tree lowers to a single-chip program and
REMOTE exchanges collapse to no-ops (single-worker cluster).

Blocking operators map as: aggregation -> dense-table group_by; join
build -> sorted build side inside hash_join; sort/topN -> lax.sort.
Dynamic result sizes surface as (active-mask, overflow-flag) pairs;
the runner owns the rerun-with-bigger-buckets policy (the memory/
spill feedback loop of the reference's Driver yield + revoke).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .. import types as T
from ..block import Batch
from ..expr.compile import compile_filter, compile_projections
from ..ops.aggregation import finalize_states, group_by, merge_partials
from ..ops.join import hash_join, semi_join_mask
from ..ops.misc import distinct as distinct_op
from ..ops.misc import limit as limit_op
from ..ops.sort import SortKey, sort_batch, top_n
from ..parallel.exchange import (RANGE_HEADROOM, SLOT_HEADROOM, ExchangeLog,
                                 broadcast_build, exchange_by_hash,
                                 exchange_by_range, gather_to_root,
                                 logging_exchanges, slot_for)
from ..parallel.mesh import WORKERS_AXIS
from ..plan import nodes as N
from ..plan.stats import capacities, is_counted, preorder, preorder_index

__all__ = ["compile_plan", "CompiledPlan", "split_flags"]

# the status word a program returns beside its batch: overflow flags in
# the low bits, the joins' binary-search trips in STEP_BITS from
# FLAG_BITS up (held to what the field takes: 127 lookups of 32 trips),
# then in COUNT_BITS each the joins whose probe was compacted and the
# lookups their directory answered alone (31 each at most)
FLAG_BITS = 8
STEP_BITS = 12
COUNT_BITS = 5


def split_flags(word):
    """The status word, taken apart on the host: (overflow flags: bit0
    hard, bit1 exchange slots; join_search_steps; join_probe_compacted;
    join_lookup_direct). `word` is an int, or the array of them a
    vmapped program returns."""
    count = (1 << COUNT_BITS) - 1
    at = FLAG_BITS + STEP_BITS
    return (word & ((1 << FLAG_BITS) - 1),
            (word >> FLAG_BITS) & ((1 << STEP_BITS) - 1),
            (word >> at) & count, (word >> (at + COUNT_BITS)) & count)


@dataclasses.dataclass
class CompiledPlan:
    """fn(scans: Dict[node_id, Batch]) -> (Batch, status): the status
    word, or the vector `split_status` takes apart.
    `scan_nodes` lists the TableScanNode/ValuesNode leaves in the order
    their batches must be supplied; distributed plans expect each scan
    batch shard-able along axis 0 by the mesh."""
    fn: Callable
    scan_nodes: List[N.PlanNode]
    output_types: List[T.Type]
    distributed: bool
    # the exact plan object this program was traced from; a cache hit
    # must route node-id-keyed side computations (dynamic filters,
    # output names) through THIS tree, not the structurally-equal twin
    # the caller handed in (ids differ across plannings)
    root: "N.PlanNode" = None
    # argument shapes -> the device memory XLA planned for the
    # executable those shapes compiled to (runner._program_hbm_bytes):
    # kept here so that it lives and dies with the cached executable
    hbm_bytes: Dict[tuple, int] = dataclasses.field(default_factory=dict)
    # gather trips a slot of the joins' expansions takes to find its row
    # (ops/join._slot_rows), summed over the program's joins, None for a
    # program without a join: a constant of the shapes, so it is noted
    # where `fn` is traced and never leaves the device.
    # `traced_expand_steps` is the latest trace's; `expand_steps_of`
    # keeps it by argument shapes, as `hbm_bytes`
    traced_expand_steps: Optional[int] = None
    expand_steps: Dict[tuple, Optional[int]] = dataclasses.field(
        default_factory=dict)

    # what the program's exchanges are (`ExchangeLog.counters`: how many
    # of each kind, the bytes one chip's collectives move), None for a
    # program without a mesh: constants of the shapes, kept like
    # `expand_steps`, so that a plan-cache hit reports them too
    traced_exchanges: Optional[Dict[str, int]] = None
    exchanges: Dict[tuple, Optional[Dict[str, int]]] = dataclasses.field(
        default_factory=dict)

    # pre-order index -> the capacity this program was built with, for
    # the nodes whose need it counts (`plan.stats.is_counted`: a join's
    # output rows, a keyed aggregation's groups); the status carries
    # one need a node, in the keys' order
    counted: Dict[int, int] = dataclasses.field(default_factory=dict)

    def split_status(self, status):
        """What `fn` returns beside its batch, on the host: (status
        word, exchange_row_bytes, {pre-order index: need}). A program
        with neither a mesh nor a counted node returns the word alone;
        else one int64 vector: the word, under a mesh the bytes of rows
        its exchanges routed, then the needs. A vmapped program returns
        a row of that per member: the word and the needs are then
        arrays along the members."""
        status = np.asarray(status)
        if not self.distributed and not self.counted:
            return status, 0, {}
        at = 2 if self.distributed else 1
        return (status[..., 0], status[..., 1] if self.distributed else 0,
                {k: status[..., at + i] for i, k in enumerate(self.counted)})

    def exchanges_of(self, batches) -> Optional[Dict[str, int]]:
        """The exchange counters of a dispatch of `fn` on `batches`
        that has just returned (as `expand_steps_of`)."""
        return self.exchanges.setdefault(shape_key(batches),
                                         self.traced_exchanges)

    def expand_steps_of(self, batches) -> Optional[int]:
        """The counter join_expand_steps of a dispatch of `fn` on
        `batches` that has just returned (under the plan's call lock):
        shapes met for the first time were traced by that call."""
        return self.expand_steps.setdefault(shape_key(batches),
                                            self.traced_expand_steps)


def shape_key(batches) -> tuple:
    """What jit keys a program of one function by, as far as scan
    batches differ: the shapes and types of their leaves."""
    return tuple((x.shape, str(x.dtype))
                 for x in jax.tree_util.tree_leaves(batches))


def _collect_scans(node: N.PlanNode, out: List[N.PlanNode], _seen=None):
    """Leaf collection, identity-deduped: a plan DAG (shared CTE
    subtree) stages each shared scan ONCE."""
    if _seen is None:
        _seen = set()
    if id(node) in _seen:
        return
    _seen.add(id(node))
    if isinstance(node, (N.TableScanNode, N.ValuesNode, N.RemoteSourceNode)):
        out.append(node)
    for s in node.sources:
        _collect_scans(s, out, _seen)


def compile_plan(root: N.PlanNode, mesh=None,
                 default_join_capacity: int = 1 << 16,
                 exchange_slot_scale: int = 1) -> CompiledPlan:
    """An exchange's per-destination slots are sized from the sender's
    own shard (`parallel/exchange.slot_for`: a little over an even
    split), so a receiver's capacity is a little over its sender's and
    the operators after an exchange run at the shard's size.
    `exchange_slot_scale` geometrically grows every slot (clamped at the
    sender's row capacity, where overflow is impossible): the runner's
    overflow->rerun policy passes 1, 2, 4, ... until the plan fits --
    the memory-feedback analog of the reference's reserve/revoke loop.

    Every device op the program lowers to is named by where it came
    from: one ``<NodeType>.<k>`` scope per plan node on the path down
    to the operator that emitted it (`k` the node's pre-order index),
    then the ``ops/`` function; both follow from the plan's
    structure, which the plan cache keys, so a hit names its ops as a
    miss would.
    Which region of which statement a program ran for is not in its
    names (a cached program serves many): the ``dispatch`` span around
    its call says so. Scopes are op metadata: the compiled program does
    not depend on them."""
    scans: List[N.PlanNode] = []
    _collect_scans(root, scans)
    order = preorder_index(root)  # stable across plannings: plan/stats.py
    axis = WORKERS_AXIS
    dist = mesh is not None

    n_workers = mesh.devices.size if dist else 1

    def _scaled_slot(given: Optional[int], sender_capacity: int,
                     headroom: float = SLOT_HEADROOM) -> int:
        # a sender never has more than `sender_capacity` rows for any
        # one destination, so slots beyond that cannot overflow
        base = given or slot_for(sender_capacity, n_workers, headroom)
        return min(base * exchange_slot_scale, max(sender_capacity, 1))

    def lower(node: N.PlanNode, inputs: Dict[str, Batch]) -> Batch:
        # identity memo: a shared subtree (CTE planned once -> plan DAG)
        # is traced once and its staged batch reused at every reference
        key = id(node)
        if key in _lower_memo:
            return _lower_memo[key]
        with jax.named_scope(f"{type(node).__name__}.{order[key]}"):
            out = _lower(node, inputs)
        _lower_memo[key] = out
        return out

    def _lower(node: N.PlanNode, inputs: Dict[str, Batch]) -> Batch:
        if isinstance(node, (N.TableScanNode, N.ValuesNode,
                             N.RemoteSourceNode)):
            return inputs[node.id]
        if isinstance(node, N.FilterNode):
            return compile_filter(node.predicate)(lower(node.source, inputs))
        if isinstance(node, N.ProjectNode):
            return compile_projections(node.expressions)(lower(node.source, inputs))
        if isinstance(node, N.AggregationNode):
            src = lower(node.source, inputs)
            if node.step in ("FINAL", "INTERMEDIATE"):
                # both consume state tables; INTERMEDIATE re-emits
                # merged states (no finalization) for a further merge
                r = merge_partials(src, len(node.group_channels),
                                   node.aggregates, node.max_groups)
            else:  # SINGLE and PARTIAL share the kernel
                r = group_by(src, node.group_channels, node.aggregates,
                             node.max_groups)
            _note_overflow(r.overflow)
            if is_counted(node):
                # a kernel whose count stops at its table says "four
                # times the capacity" when it overflows: the ladder's
                # step before it had counts
                needs[order[id(node)]] = r.num_groups if r.counted \
                    else jnp.where(r.overflow, jnp.maximum(
                        r.num_groups, 4 * node.max_groups), r.num_groups)
            out = r.batch
            if node.step in ("SINGLE", "FINAL"):
                out = finalize_states(out, len(node.group_channels),
                                      node.aggregates)
            if dist and not node.group_channels:
                gathered = (isinstance(node.source, N.ExchangeNode)
                            and node.source.kind == "GATHER"
                            and node.source.scope == "REMOTE")
                if node.step == "SINGLE" and not gathered:
                    raise ValueError(
                        "SINGLE global aggregation under a mesh would emit "
                        "per-shard partials; run AddExchanges "
                        "(plan.distribute) first -- run_query does this "
                        "automatically")
                if node.step == "FINAL" or gathered:
                    # after a GATHER the guaranteed single row belongs to
                    # worker 0 (where gathered rows are active); other
                    # workers would emit spurious empty-state rows
                    is_root = jax.lax.axis_index(axis) == 0
                    out = out.with_active(out.active & is_root)
            return out
        if isinstance(node, N.JoinNode):
            probe = lower(node.left, inputs)
            build = lower(node.right, inputs)
            right_replicated = (isinstance(node.right, N.ExchangeNode)
                                and node.right.kind == "REPLICATE"
                                and node.right.scope == "REMOTE")
            if dist and node.join_type in ("right", "full") \
                    and (node.distribution == "broadcast" or right_replicated):
                raise ValueError(
                    "RIGHT/FULL OUTER join under a mesh needs PARTITIONED "
                    "distribution (a replicated build side would emit its "
                    "unmatched rows once per worker); run AddExchanges "
                    "(plan.distribute) first -- run_query does this "
                    "automatically")
            if dist and node.distribution == "broadcast" \
                    and not right_replicated:  # exchange already gathered
                build = broadcast_build(build, axis)
            cap = node.out_capacity or default_join_capacity
            # a build side hash-exchanged here holds a share of its keys'
            # span: the lookup's directory is that many times wider
            exchanged = (dist and isinstance(node.right, N.ExchangeNode)
                         and node.right.kind == "REPARTITION"
                         and node.right.scope == "REMOTE")
            r = hash_join(probe, build, node.left_keys, node.right_keys,
                          cap, node.join_type, node.right_output_channels,
                          spread=n_workers if exchanged else 1)
            _note_overflow(r.overflow)
            needs[order[id(node)]] = r.num_rows
            search_steps.append(r.search_steps)
            direct.append(r.direct)
            expand_steps.append(r.expand_steps)
            compacted.append(r.compacted)
            return r.batch
        if isinstance(node, N.SemiJoinNode):
            src = lower(node.source, inputs)
            filt = lower(node.filtering_source, inputs)
            filt_replicated = (isinstance(node.filtering_source, N.ExchangeNode)
                               and node.filtering_source.kind == "REPLICATE"
                               and node.filtering_source.scope == "REMOTE")
            if dist and not filt_replicated:
                filt = broadcast_build(filt, axis)
            sk = node.source_key if isinstance(node.source_key, list) \
                else [node.source_key]
            fk = node.filtering_key if isinstance(node.filtering_key, list) \
                else [node.filtering_key]
            looked: List = []
            m, mnull = semi_join_mask(src, filt, sk, fk,
                                      node.null_keys_match,
                                      steps_out=looked)
            for steps, answered in looked:
                search_steps.append(steps)
                direct.append(answered.astype(jnp.int32))
            from ..block import Column
            return Batch(src.columns + (Column(m, mnull, T.BOOLEAN),),
                         src.active)
        if isinstance(node, N.SortNode):
            return sort_batch(lower(node.source, inputs),
                              [SortKey(*k) for k in node.keys])
        if isinstance(node, N.TopNNode):
            return top_n(lower(node.source, inputs),
                         [SortKey(*k) for k in node.keys], node.count)
        if isinstance(node, N.LimitNode):
            return limit_op(lower(node.source, inputs), node.count)
        if isinstance(node, N.DistinctNode):
            keys = node.key_channels
            if keys is None:
                keys = list(range(len(node.output_types())))
            out, ovf = distinct_op(lower(node.source, inputs), keys,
                                   node.max_groups)
            _note_overflow(ovf)
            return out
        if isinstance(node, N.UnionNode):
            from ..block import concat_batches
            parts = [lower(s, inputs) for s in node.inputs]
            return concat_batches(parts)
        if isinstance(node, N.SampleNode):
            src = lower(node.source, inputs)
            # deterministic Bernoulli: row-index hash vs threshold
            from ..expr.functions import _mix64
            h = _mix64(jnp.arange(src.capacity, dtype=jnp.uint64))
            thresh = jnp.uint64(int(node.ratio * float(2**64 - 1)))
            return src.with_active(src.active & (h <= thresh))
        if isinstance(node, N.AssignUniqueIdNode):
            from ..block import Column
            src = lower(node.source, inputs)
            rid = jnp.arange(src.capacity, dtype=jnp.int64)
            if dist:
                widx = jax.lax.axis_index(axis).astype(jnp.int64)
                rid = rid | (widx << 40)  # task-salted high bits
            col = Column(rid, jnp.zeros(src.capacity, dtype=bool), T.BIGINT)
            return Batch(src.columns + (col,), src.active)
        if isinstance(node, N.MarkDistinctNode):
            from ..block import Column
            from ..ops.misc import mark_distinct
            src = lower(node.source, inputs)
            m, ovf = mark_distinct(src, node.key_channels, node.max_groups)
            _note_overflow(ovf)
            col = Column(m, jnp.zeros(src.capacity, dtype=bool), T.BOOLEAN)
            return Batch(src.columns + (col,), src.active)
        if isinstance(node, N.WindowNode):
            from ..ops.sort import SortKey as SK
            from ..ops.window import WindowSpec, window
            src = lower(node.source, inputs)
            # the 5th tuple slot is the function's int parameter:
            # ntile's bucket count, lag/lead's offset, nth_value's n
            specs = [WindowSpec(name, ch,
                                T.parse_type(ty) if isinstance(ty, str) else ty,
                                frame,
                                ntile_buckets=(k or 0) if name == "ntile" else 0,
                                offset=((1 if k is None else k)
                                        if name in ("lag", "lead",
                                                    "nth_value") else 1))
                     for name, ch, ty, frame, k in node.functions]
            return window(src, node.partition_channels,
                          [SK(*o) for o in node.order_keys], specs)
        if isinstance(node, N.RowNumberNode):
            from ..ops.window import WindowSpec, window
            src = lower(node.source, inputs)
            out = window(src, node.partition_channels,
                         [SortKey(*k) for k in node.order_keys],
                         [WindowSpec("row_number")])
            if node.max_rows_per_partition is not None:
                rn = out.column(out.num_columns - 1)
                keep = out.active & (rn.values <= node.max_rows_per_partition)
                out = out.with_active(keep)
            return out
        if isinstance(node, N.UnnestNode):
            from ..ops.unnest import unnest as unnest_op
            src = lower(node.source, inputs)
            cap = node.out_capacity or src.capacity * 4
            out, ovf = unnest_op(src, node.array_channel, cap,
                                 node.with_ordinality)
            _note_overflow(ovf)
            return out
        if isinstance(node, N.GroupIdNode):
            from ..block import Column, concat_batches, null_like
            src = lower(node.source, inputs)
            keyset = set(node.key_channels)
            parts = []
            for gi, kept in enumerate(node.grouping_sets):
                cols = []
                for ci, c in enumerate(src.columns):
                    if ci in keyset and ci not in kept:
                        cols.append(null_like(c))
                    else:
                        cols.append(c)
                gid = Column(jnp.full(src.capacity, gi, dtype=jnp.int64),
                             jnp.zeros(src.capacity, dtype=bool), T.BIGINT)
                parts.append(Batch(tuple(cols) + (gid,), src.active))
            return concat_batches(parts)
        if isinstance(node, N.ExchangeNode):
            if node.kind == "MERGE" and dist and node.scope == "REMOTE":
                # MergeOperator analog on the mesh: sampled range
                # repartition + per-worker sort => globally sorted
                # DISTRIBUTED output (the full row set never lands on
                # one device). The local pre-sort below the exchange
                # (which the HTTP tier's producers need for the k-way
                # merge) is redundant here -- the post-exchange sort
                # orders everything -- so lowering skips it.
                src_node = node.source
                if isinstance(src_node, N.SortNode):
                    src_node = src_node.source
                inner = lower(src_node, inputs)
                slot = _scaled_slot(node.slot_capacity, inner.capacity,
                                    RANGE_HEADROOM)
                out, ovf = exchange_by_range(inner, node.sort_keys, axis,
                                             slot)
                _note_overflow(ovf, scalable=True)
                return sort_batch(out, [SortKey(*k) for k in node.sort_keys])
            src = lower(node.source, inputs)
            if node.scope == "LOCAL" or not dist:
                return src
            if node.kind == "REPARTITION":
                slot = _scaled_slot(node.slot_capacity, src.capacity)
                out, ovf = exchange_by_hash(src, node.partition_channels,
                                            axis, slot)
                _note_overflow(ovf, scalable=True)
                return out
            if node.kind == "REPLICATE":
                return broadcast_build(src, axis)
            if node.kind == "GATHER":
                # every worker receives all rows; only worker 0 keeps them
                # active so the global (concatenated) view has one copy
                g = gather_to_root(src, axis)
                is_root = jax.lax.axis_index(axis) == 0
                return g.with_active(g.active & is_root)
            raise ValueError(node.kind)
        if isinstance(node, N.OutputNode):
            return lower(node.source, inputs)
        raise TypeError(type(node))

    overflow_box: List = []
    search_steps: List = []  # one trip count per join lookup lowered
    direct: List = []  # one count per join: lookups its directories answered
    expand_steps: List[int] = []  # one per join expansion lowered
    compacted: List = []  # one 0/1 per join: its probe was compacted
    needs: Dict[int, jax.Array] = {}  # pre-order index -> rows needed
    _lower_memo: Dict[int, Batch] = {}

    def _note_overflow(flag, scalable: bool = False):
        """scalable=True marks exchange-slot overflow, which the runner
        can cure by recompiling with a bigger exchange_slot_scale;
        join/group overflow needs bigger declared capacities instead."""
        overflow_box.append((flag, scalable))

    def run(scan_batches: Sequence[Batch]):
        overflow_box.clear()
        search_steps.clear()
        direct.clear()
        expand_steps.clear()
        compacted.clear()
        needs.clear()
        _lower_memo.clear()
        inputs = {n.id: b for n, b in zip(scans, scan_batches)}
        log = ExchangeLog()
        with logging_exchanges(log):
            out = lower(root, inputs)
        plan.traced_exchanges = log.counters() if dist else None
        plan.traced_expand_steps = sum(expand_steps) if expand_steps \
            else None
        hard = jnp.zeros((), dtype=bool)   # join/group capacity
        slots = jnp.zeros((), dtype=bool)  # exchange slots (rescalable)
        for f, scalable in overflow_box:
            if scalable:
                slots = slots | f
            else:
                hard = hard | f
        steps = sum(search_steps, jnp.zeros((), dtype=jnp.int32))
        took = sum(compacted, jnp.zeros((), dtype=jnp.int32))
        answered = sum(direct, jnp.zeros((), dtype=jnp.int32))
        if dist:
            hard = jax.lax.psum(hard.astype(jnp.int32), axis) > 0
            slots = jax.lax.psum(slots.astype(jnp.int32), axis) > 0
            steps = jax.lax.pmax(steps, axis)  # the deepest shard's
            took = jax.lax.pmax(took, axis)  # each shard chooses for itself
            # what every shard's directory answered (a min, as a max:
            # the all-reduce the chip is known to lower in 32 bits)
            answered = -jax.lax.pmax(-answered, axis)
        # one word, one host read: bit0 = hard (non-scalable), bit1 =
        # exchange slots, then the joins' binary-search trips, the
        # joins that compacted their probe and the lookups the directory
        # answered (the counters join_search_steps, join_probe_compacted,
        # join_lookup_direct; `split_flags` takes it apart)
        steps = jnp.minimum(steps, (1 << STEP_BITS) - 1)
        count = (1 << COUNT_BITS) - 1
        at = FLAG_BITS + STEP_BITS
        word = (hard.astype(jnp.int32) + 2 * slots.astype(jnp.int32)
                + (steps << FLAG_BITS)
                + (jnp.minimum(took, count) << at)
                + (jnp.minimum(answered, count) << (at + COUNT_BITS)))
        # beside the word, still one host read (`split_status`): what
        # each counted node needed, its join's output rows or its
        # groups, which the ladder sizes the node from; a capacity is a
        # shard's shape, so under a mesh the largest shard's
        if not dist and not plan.counted:
            return out, word  # the parent's program, op for op
        status = [word.astype(jnp.int64)]
        counts = [needs[k].astype(jnp.int64) for k in plan.counted]
        if dist:
            # under a mesh one more scalar rides beside the word: the
            # bytes of rows the hash and range exchanges routed, a
            # chip's mean (the counter exchange_row_bytes)
            routed = sum(log.routed, jnp.zeros((), dtype=jnp.int64))
            status.append(jax.lax.psum(routed, axis) // n_workers)
            # XLA:TPU reduces 64-bit lanes by sum alone: the maximum is
            # taken in 32 bits, and a need that does not fit them is
            # far past every capacity's ceiling anyway
            counts = [jax.lax.pmax(jnp.minimum(c, (1 << 31) - 1).astype(
                jnp.int32), axis).astype(jnp.int64) for c in counts]
        return out, jnp.stack(status + counts)

    plan = CompiledPlan(run, scans, root.output_types(), dist, root)
    nodes = preorder(root)
    plan.counted = {
        k: c for k, c in capacities(root, default_join_capacity).items()
        if is_counted(nodes[k])}
    if dist:
        in_specs = tuple(P(WORKERS_AXIS) for _ in scans)
        plan.fn = jax.shard_map(run, mesh=mesh, in_specs=(in_specs,),
                                out_specs=(P(WORKERS_AXIS), P()),
                                check_vma=False)
    return plan
