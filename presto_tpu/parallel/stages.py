"""Distributed stage composition: gang-scheduled fragments over the mesh.

Reference surface: the two-stage aggregation plan the optimizer emits
(PushPartialAggregationThroughExchange + AddExchanges inserting a
FIXED_HASH_DISTRIBUTION remote exchange between PARTIAL and FINAL
AggregationNodes) and the partitioned-join stage wiring
(SqlQueryScheduler gang-running stages connected by exchanges).

Here a multi-stage plan is ONE SPMD program under shard_map: stage
boundaries are collectives (exchange.py), so XLA overlaps compute and
ICI traffic instead of a scheduler overlapping tasks and HTTP.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..block import Batch
from ..ops.aggregation import AggSpec, GroupByResult, group_by, merge_partials
from ..ops.join import JoinResult, hash_join
from .exchange import broadcast_build, exchange_by_hash, gather_to_root
from .mesh import WORKERS_AXIS

__all__ = ["distributed_group_by", "distributed_hash_join", "two_stage_group_by"]


def distributed_group_by(shard: Batch, key_channels: Sequence[int],
                         aggs: Sequence[AggSpec], max_groups: int,
                         axis_name: str = WORKERS_AXIS,
                         slot_capacity: Optional[int] = None
                         ) -> Tuple[GroupByResult, jnp.ndarray]:
    """PARTIAL agg -> hash exchange of partial states -> FINAL agg.
    Call inside shard_map. Each worker returns its disjoint slice of
    final groups; also returns a global overflow flag."""
    part = group_by(shard, key_channels, aggs, max_groups)
    nkeys = len(key_channels)
    if slot_capacity is None:
        slot_capacity = max_groups
    ex, ex_overflow = exchange_by_hash(part.batch, list(range(nkeys)),
                                       axis_name, slot_capacity)
    final = merge_partials(ex, nkeys, aggs, max_groups)
    overflow = part.overflow | ex_overflow | final.overflow
    overflow = jax.lax.psum(overflow.astype(jnp.int32), axis_name) > 0
    return final, overflow


def two_stage_group_by(shard: Batch, key_channels: Sequence[int],
                       aggs: Sequence[AggSpec], max_groups: int,
                       axis_name: str = WORKERS_AXIS
                       ) -> Tuple[GroupByResult, jnp.ndarray]:
    """Like distributed_group_by but gathers every final group to every
    worker (SINGLE_DISTRIBUTION output stage), so the result is
    replicated -- the coordinator-facing root stage shape."""
    final, overflow = distributed_group_by(shard, key_channels, aggs,
                                           max_groups, axis_name)
    gathered = gather_to_root(final.batch, axis_name)
    nkeys = len(key_channels)
    # merge the per-worker disjoint tables into one dense table (no key
    # collisions across workers; merge combinators are idempotent over
    # already-final states: sum<-sum, count<-sum, min/max pass through)
    merged = merge_partials(gathered, nkeys, aggs, max_groups)
    return merged, overflow | merged.overflow


def distributed_hash_join(probe_shard: Batch, build_shard: Batch,
                          probe_keys: Sequence[int], build_keys: Sequence[int],
                          out_capacity: int, axis_name: str = WORKERS_AXIS,
                          strategy: str = "partitioned",
                          slot_capacity: Optional[int] = None,
                          join_type: str = "inner",
                          build_output_channels: Optional[Sequence[int]] = None
                          ) -> Tuple[JoinResult, jnp.ndarray]:
    """Distributed join (call inside shard_map).

    strategy="partitioned": both sides all_to_all by key hash, then local
    join (DetermineJoinDistributionType PARTITIONED).
    strategy="broadcast": build side all_gathered to every worker, probe
    stays put (REPLICATED / broadcast join).
    """
    overflow = jnp.zeros((), dtype=bool)
    if strategy == "broadcast":
        build_all = broadcast_build(build_shard, axis_name)
        res = hash_join(probe_shard, build_all, probe_keys, build_keys,
                        out_capacity, join_type, build_output_channels)
    else:
        if slot_capacity is None:
            slot_capacity = probe_shard.capacity
        p_ex, p_ovf = exchange_by_hash(probe_shard, probe_keys, axis_name,
                                       slot_capacity)
        b_ex, b_ovf = exchange_by_hash(build_shard, build_keys, axis_name,
                                       slot_capacity)
        overflow = p_ovf | b_ovf
        res = hash_join(p_ex, b_ex, probe_keys, build_keys, out_capacity,
                        join_type, build_output_channels,
                        spread=jax.lax.axis_size(axis_name))
    overflow = jax.lax.psum((overflow | res.overflow).astype(jnp.int32),
                            axis_name) > 0
    return res, overflow
