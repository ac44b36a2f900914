"""Spillable aggregation and join build: host-DRAM offload when state
exceeds an HBM budget.

Reference surface: the revocable-memory spill stack --
operator/aggregation/builder/SpillableHashAggregationBuilder.java:46
(partial group tables spilled when memory is revoked),
operator/HashBuilderOperator.java:166-186 (join build spill states),
presto-main/.../execution/MemoryRevokingScheduler.java (revocation
trigger), spiller/GenericPartitioningSpiller (hash-partitioned spill
files re-read partition by partition).

TPU redesign: the spill tier is HOST DRAM (BASELINE config 5 targets
host-spill, not disk), and the unit of spilling is a GROUPED-EXECUTION
BUCKET rather than an arbitrary page run: inputs hash-partition on the
aggregation/join keys into B buckets whose states are disjoint, the
device processes one bucket at a time, and each completed bucket's
output is COMPACTED to live rows host-side and kept in host memory.
That makes spilling restart-free -- no re-merge of spilled runs is ever
needed, because bucket states never interleave (the property
GenericPartitioningSpiller's partitioned files approximate on disk).

B is sized from the budget: B = ceil(2 * planned_state_bytes / budget)
(two tables coexist during the running merge). Spill movement is
counted in RuntimeStats over COMPACTED row bytes (spilled_bytes /
spill_buckets -- EXPLAIN ANALYZE surfaces them, the reference's
spilledDataSize analog).
"""

from __future__ import annotations

import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import failpoints
from .. import types as T
from ..block import Batch, batch_from_numpy, to_numpy
from ..connectors import catalog
from ..ops.aggregation import finalize_states
from ..plan import nodes as N
from .planner import compile_plan
from .stats import RuntimeStats

__all__ = ["plan_state_bytes", "run_spilled_agg", "run_spilled_join",
           "spill_bucket_count"]


def _type_bytes(ty: T.Type) -> int:
    """Per-row device bytes of one output column (values + null mask)."""
    if ty.is_string:
        return 64 + 4 + 1  # char matrix row (typical width) + len + null
    if ty.is_decimal and not ty.is_short_decimal:
        return 16 + 1
    try:
        return np.dtype(ty.to_dtype()).itemsize + 1
    except Exception:  # noqa: BLE001 - exotic types: assume wide
        return 17


def plan_state_bytes(agg: N.AggregationNode) -> int:
    """Planned footprint of the aggregation's dense state table."""
    return agg.max_groups * sum(_type_bytes(t) for t in agg.output_types())


def spill_bucket_count(state_bytes: int, hbm_budget_bytes: int) -> int:
    """Buckets needed so ~two bucket tables fit the budget."""
    return max(1, math.ceil(2 * state_bytes / max(hbm_budget_bytes, 1)))


_CPU = None


def _cpu_device():
    global _CPU
    if _CPU is None:
        _CPU = jax.devices("cpu")[0]
    return _CPU


class _HostRows:
    """Compacted host staging: live rows only, as numpy arrays (the
    first spill medium). Appending pulls the batch's ACTIVE rows
    off-device; `to_batch` re-stages them as one padded Batch.

    Disk tier (FileSingleStreamSpiller / TempStorage analog): with a
    `disk_dir`, accumulated host chunks flush to .npz run files once
    they exceed `disk_threshold_bytes`, bounding host DRAM too; reads
    re-load the runs in order. Bucket states are disjoint (module
    docstring), so runs concatenate -- no merge pass."""

    def __init__(self, types: List[T.Type], disk_dir: Optional[str] = None,
                 disk_threshold_bytes: int = 256 << 20):
        self.types = types
        self._cols: List[List[np.ndarray]] = [[] for _ in types]
        self._nulls: List[List[np.ndarray]] = [[] for _ in types]
        self.rows = 0
        self.bytes = 0
        self._mem_bytes = 0
        self.disk_dir = disk_dir
        self.disk_threshold = disk_threshold_bytes
        self._runs: List[str] = []  # flushed .npz paths, in order
        self.disk_bytes = 0

    def append(self, batch: Batch, stats: Optional[RuntimeStats]):
        act = np.asarray(batch.active)
        sel = np.nonzero(act)[0]
        self.rows += len(sel)
        moved = 0
        for c in range(len(self.types)):
            v, nl = to_numpy(batch.column(c))
            v, nl = v[sel], nl[sel]
            self._cols[c].append(v)
            self._nulls[c].append(nl)
            moved += (v.nbytes if v.dtype != object else 32 * len(v)) \
                + nl.nbytes
        self.bytes += moved
        self._mem_bytes += moved
        if stats is not None:
            stats.add("spilled_bytes", moved)
        if self.disk_dir is not None and \
                self._mem_bytes >= self.disk_threshold:
            self._flush_run(stats)

    def _flush_run(self, stats: Optional[RuntimeStats]):
        import os
        import uuid as _uuid
        if self.rows == 0 or not self._cols[0]:
            return
        if failpoints.ARMED:
            # a full/broken spill disk at run-flush time
            failpoints.hit("spill.write")
        os.makedirs(self.disk_dir, exist_ok=True)
        path = os.path.join(self.disk_dir,
                            f"spill_{_uuid.uuid4().hex[:12]}.npz")
        payload = {}
        for c in range(len(self.types)):
            payload[f"v{c}"] = np.concatenate(self._cols[c]) \
                if self._cols[c] else np.array([], dtype=object)
            payload[f"n{c}"] = np.concatenate(self._nulls[c]) \
                if self._nulls[c] else np.array([], dtype=bool)
            self._cols[c] = []
            self._nulls[c] = []
        np.savez(path, **{k: v for k, v in payload.items()})
        self._runs.append(path)
        written = os.path.getsize(path)
        self.disk_bytes += written
        self._mem_bytes = 0
        if stats is not None:
            stats.add("spilled_to_disk_bytes", written)

    def columns(self) -> Tuple[List[np.ndarray], List[np.ndarray]]:
        cols_runs: List[List[np.ndarray]] = [[] for _ in self.types]
        nulls_runs: List[List[np.ndarray]] = [[] for _ in self.types]
        if failpoints.ARMED and self._runs:
            # a run file that rotted/vanished between write and re-read
            failpoints.hit("spill.read")
        for path in self._runs:
            with np.load(path, allow_pickle=True) as z:
                for c in range(len(self.types)):
                    cols_runs[c].append(z[f"v{c}"])
                    nulls_runs[c].append(z[f"n{c}"])
        for c in range(len(self.types)):
            cols_runs[c].extend(self._cols[c])
            nulls_runs[c].extend(self._nulls[c])
        cols = [np.concatenate(c) if c else np.array([], dtype=object)
                for c in cols_runs]
        nulls = [np.concatenate(n) if n else np.array([], dtype=bool)
                 for n in nulls_runs]
        return cols, nulls

    def close(self):
        import os
        for path in self._runs:
            try:
                os.unlink(path)
            except OSError:
                pass
        self._runs = []

    def to_batch(self, capacity: Optional[int] = None,
                 on_host: bool = False) -> Batch:
        cols, nulls = self.columns()
        cap = capacity or max(8, -(-self.rows // 8) * 8)
        if on_host:
            with jax.default_device(_cpu_device()):
                return batch_from_numpy(self.types, cols, nulls=nulls,
                                        capacity=cap)
        return batch_from_numpy(self.types, cols, nulls=nulls, capacity=cap)


def run_spilled_agg(root: N.PlanNode, sf: float, split_rows: int,
                    hbm_budget_bytes: int,
                    stats: Optional[RuntimeStats] = None,
                    spill_dir: Optional[str] = None,
                    spill_file_threshold: int = 256 << 20) -> Batch:
    """Streamable aggregation whose state table exceeds the HBM budget:
    grouped execution with per-bucket host offload. The bucket executor
    compiles ONCE (bucket id is a traced scalar); each finished
    bucket's FINALIZED, compacted rows move to host DRAM before the
    next lifespan starts. Returns the result as one host-resident
    Batch."""
    from .streaming import _make_agg_executor, streamable_agg_shape

    shape = streamable_agg_shape(root)
    assert shape is not None, "plan is not a streamable aggregation"
    agg, _scan = shape
    state_bytes = plan_state_bytes(agg)
    n_buckets = spill_bucket_count(state_bytes, hbm_budget_bytes)
    # per-bucket capacity: groups hash-partition about evenly; 2x slack
    # absorbs skew, and the overflow flag still guards correctness
    bucket_groups = max(64, -(-2 * agg.max_groups // n_buckets))
    import dataclasses as _dc
    agg_b = _dc.replace(agg, max_groups=bucket_groups)
    root_b = _rebuild_above(root, agg, agg_b)

    nkeys = len(agg.group_channels)
    runner = _make_agg_executor(root_b, sf, split_rows, n_buckets)
    staged: Optional[_HostRows] = None
    try:
        for b in range(n_buckets):
            r = runner(b)
            if bool(np.asarray(r.overflow)):
                raise RuntimeError(
                    f"spilled aggregation bucket {b} overflowed its "
                    f"{bucket_groups}-group table; raise max_groups")
            out = finalize_states(r.batch, nkeys, agg.aggregates)
            if staged is None:
                staged = _HostRows(
                    [c.type for c in out.columns], disk_dir=spill_dir,
                    disk_threshold_bytes=spill_file_threshold)
            staged.append(out, stats)
            if stats is not None:
                stats.add("spill_buckets", 1)
        return staged.to_batch(on_host=True)
    finally:
        # run files must not outlive the query, success OR failure (a
        # mid-loop overflow raise would otherwise leak every flushed run)
        if staged is not None:
            staged.close()


def _rebuild_above(root: N.PlanNode, old: N.PlanNode,
                   new: N.PlanNode) -> N.PlanNode:
    """Replace `old` (by identity) with `new` in a linear wrapper
    chain."""
    import dataclasses as _dc
    if root is old:
        return new
    assert len(root.sources) == 1, "expected a linear chain"
    return _dc.replace(root, source=_rebuild_above(root.source, old, new))


# ---------------------------------------------------------------------------
# Spillable join build (bucketed partitioned join)
# ---------------------------------------------------------------------------


def _linear_scan(node: N.PlanNode) -> N.TableScanNode:
    cur = node
    while isinstance(cur, (N.FilterNode, N.ProjectNode)):
        cur = cur.source
    assert isinstance(cur, N.TableScanNode), \
        "spilled join streams scan-rooted pipelines"
    return cur


def run_spilled_join(join: N.JoinNode, sf: float, split_rows: int,
                     hbm_budget_bytes: int,
                     stats: Optional[RuntimeStats] = None,
                     out_capacity_per_bucket: Optional[int] = None
                     ) -> Batch:
    """Join two scan-rooted pipelines under a capped HBM budget:

      1. stream BOTH sides split by split; each split's rows
         hash-partition on their join keys and append -- COMPACTED, as
         host numpy arrays -- to per-bucket host staging (the build-side
         spill: every row leaves HBM before the join runs;
         HashBuilderOperator's INPUT_SPILLED state)
      2. per bucket: restage ONLY that bucket's rows into HBM, join,
         and move the compacted result back to host
         (LOOKUP_SOURCE_UNSPILLED: bucket-at-a-time restore)

    Peak HBM = one split batch during partitioning, then one bucket
    pair + its join output. Bucket count is sized so a bucket pair
    fits the budget."""
    from ..ops.join import hash_join
    from ..parallel.exchange import _row_hash
    from functools import partial

    sides = []
    for node, keys in ((join.left, join.left_keys),
                       (join.right, join.right_keys)):
        scan = _linear_scan(node)
        pipeline = compile_plan(node)
        conn = catalog(scan.connector)
        total = conn.table_row_count(scan.table, sf)
        row_bytes = sum(_type_bytes(t) for t in node.output_types())
        sides.append((node, keys, scan, pipeline, conn, total, row_bytes))

    total_bytes = sum(t * rb for *_x, t, rb in sides)
    n_buckets = max(1, math.ceil(3 * total_bytes / max(hbm_budget_bytes, 1)))

    @partial(jax.jit, static_argnums=1)
    def _bucket_of(batch: Batch, key_channels: Tuple[int, ...]):
        h = _row_hash([batch.column(c) for c in key_channels])
        return (h % jnp.uint64(n_buckets)).astype(jnp.int32)

    # phase 1: partition both sides into compacted host bucket staging
    host_buckets: List[List[_HostRows]] = []
    for si, (node, keys, scan, pipeline, conn, total, _rb) in enumerate(sides):
        tys = node.output_types()
        buckets = [_HostRows(tys) for _ in range(n_buckets)]
        host_buckets.append(buckets)
        from .runner import stage_scan_split
        for start in range(0, max(total, 1), split_rows):
            count = min(split_rows, max(total - start, 0))
            # shared narrow-width staging (honors physical_dtypes)
            batch = stage_scan_split(conn, scan, sf, start, count,
                                     split_rows)
            out, _ovf = pipeline.fn((batch,))
            bid = _bucket_of(out, tuple(keys))
            for b in range(n_buckets):
                buckets[b].append(
                    out.with_active(out.active & (bid == b)), stats)
        if stats is not None:
            stats.add("spill_buckets", n_buckets)

    # phase 2: bucket-at-a-time join on device
    result: Optional[_HostRows] = None
    for b in range(n_buckets):
        probe = host_buckets[0][b].to_batch()   # restore into HBM
        build = host_buckets[1][b].to_batch()
        cap = out_capacity_per_bucket or \
            4 * max(probe.capacity, build.capacity)
        r = hash_join(probe, build, join.left_keys, join.right_keys,
                      cap, join.join_type, join.right_output_channels)
        if bool(np.asarray(r.overflow)):
            raise RuntimeError(
                f"spilled join bucket {b} overflowed out_capacity {cap}; "
                "raise out_capacity_per_bucket")
        if result is None:
            result = _HostRows([c.type for c in r.batch.columns])
        result.append(r.batch, stats)
        if stats is not None:
            stats.add("spill_buckets", 1)
    return result.to_batch(on_host=True)
