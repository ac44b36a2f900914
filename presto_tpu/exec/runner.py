"""Query runner: split generation, execution, result fetch.

Reference surface: the worker task path -- SqlTaskExecution creating
drivers per split (execution/SqlTaskExecution.java:144), the Driver
processing loop (operator/Driver.java:310), and the coordinator pulling
results from the root stage's output buffer.

Round-1 model: one batch per table scan (splits concatenated), one
jit'd program per plan, host-side result extraction. The driver-loop
streaming of bounded batches (double-buffered through HBM) and the
overflow->rerun policy (spill analog) land on top of compile_plan
without changing lowered kernels.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import dataclasses
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from .. import types as T
from ..block import Batch, batch_from_numpy, to_numpy
from ..plan import nodes as N
from .planner import compile_plan, shape_key, split_flags
from .resident import budget as resident_budget
from .resident import shard_key
from .resident import tier as resident_tier
from .stats import (QueryStats, RuntimeStats, StatsCollector, joining, note,
                    note_max, span, stage)

__all__ = ["run_query", "prepare_plan", "QueryResult"]


@dataclasses.dataclass
class QueryResult:
    columns: List[np.ndarray]
    nulls: List[np.ndarray]
    names: List[str]
    row_count: int
    stats: Dict[str, Dict[str, float]] = dataclasses.field(default_factory=dict)
    types: List[T.Type] = dataclasses.field(default_factory=list)
    # structured telemetry (stages/operators/counters with a merge law);
    # `stats` above stays the flat named-counter snapshot
    query_stats: Optional[QueryStats] = None

    def rows(self) -> List[tuple]:
        # M001: the caller asked for the FINAL RESULT as Python
        # rows -- output cardinality, already materialized above
        _BOUNDED_BY = {"out": "final result rows (caller-requested materialization)"}
        out = []
        for i in range(self.row_count):
            out.append(tuple(None if self.nulls[c][i] else self.columns[c][i]
                             for c in range(len(self.columns))))
        return out

    def canonical_rows(self, digits: int = 6) -> List[tuple]:
        """Order-independent, stringified rows for oracle comparison
        (floats rounded so summation order cannot flip a digit) -- the
        ONE canonicalization the fusion A/B surfaces share."""
        # M001: same output surface as rows() above
        _BOUNDED_BY = {"out": "final result rows (oracle canonicalization)"}
        out = []
        for i in range(self.row_count):
            row = []
            for c in range(len(self.columns)):
                v = None if self.nulls[c][i] else self.columns[c][i]
                if isinstance(v, (float, np.floating)):
                    v = round(float(v), digits)
                row.append(str(v))
            out.append(tuple(row))
        return sorted(out)


def _host_bytes(arrays, nulls=None) -> int:
    """Host-side byte count of generated columns (object lanes count
    pointer bytes -- consistent, if conservative, for strings)."""
    total = sum(getattr(a, "nbytes", 0) for a in arrays)
    if nulls:
        total += sum(getattr(n, "nbytes", 0) for n in nulls)
    return total


def _read_split(conn, node: "N.TableScanNode", sf: float, start: int,
                count: int, predicate=None):
    """One split's host columns and null masks (None where the
    connector has no nulls), in `node.columns` order. A stored connector
    that offers `read_columns` is asked once for both (a file is opened
    and decoded once, its pieces assembled on the host; it records its
    own ``connector_read`` and ``decode`` hops, and with a `predicate`
    may give fewer rows than `count`); the others' two calls are views
    or generated columns, timed here as ``connector_read``."""
    from .datapath import timed_hop
    one_call = getattr(conn, "read_columns", None)
    if one_call is not None:
        data, nmap = one_call(node.table, node.columns, start, count,
                              predicate)
        return ([data[c] for c in node.columns],
                [nmap[c] for c in node.columns])
    with timed_hop("connector_read") as t_read:
        data = conn.generate_columns(node.table, sf, node.columns,
                                     start, count)
        arrays = [data[c] for c in node.columns]
        nulls = None
        if hasattr(conn, "generate_nulls"):  # stored tables carry nulls
            nmap = conn.generate_nulls(node.table, node.columns, start,
                                       count)
            nulls = [nmap[c] for c in node.columns]
        t_read.bytes = _host_bytes(arrays, nulls)
    return arrays, nulls


def _plain_lanes(types) -> bool:
    """Every column stages as one `Column` (a lane and a mask)."""
    return all(ty.is_fixed_width
               and not (ty.is_decimal and not ty.is_short_decimal)
               for ty in types)


def _stage_pieces(scan, types, capacity: int) -> Optional[Batch]:
    """The device-side consumer of a lake scan's producer
    (connectors/parquet.PieceScan): each row group's lanes and masks,
    decoded at the batch's physical dtypes on the decode pool, are put
    and land in the batch's columns on the device while later groups are
    still being read and decoded. None where a group refused its
    narrowing (a value the range the plan trusted does not cover): the
    caller stages the scan whole, that column wide.

    The three hops overlap inside ``staging``: ``connector_read`` and
    ``decode`` run from their first group's entry to their last group's
    exit on the pool (`PieceScan.record`), ``device_put`` from the first
    group's put to the assembled batch being ready."""
    from ..block import BatchBuilder
    from .datapath import hop_interval
    from .memory import batch_bytes
    builder = BatchBuilder(types, scan.dtypes, capacity, scan.room)
    decoded, put_at = 0, None
    pieces = iter(scan)
    try:
        for piece in pieces:
            if piece.refused:
                return None
            put_at = put_at or time.time()
            with jax.profiler.TraceAnnotation("presto:device_put"):
                decoded += builder.put(
                    [piece.values[c] for c in scan.columns],
                    [piece.nulls[c] for c in scan.columns], piece.rows)
    finally:
        pieces.close()  # nothing of a scan given up stays on the pool
    b = jax.block_until_ready(builder.finish())
    done = time.time()
    hop_interval("device_put", batch_bytes(b), put_at or done, done)
    scan.record(decoded, pipelined=True)
    return b


def _checked_by_shard(phys, types, arrays, nulls, shards: int, per: int):
    """`checked_physical_dtypes` shard by shard, the shards side by side
    (numpy's reductions run outside the interpreter's lock): a lane
    keeps its narrowing where every shard that holds rows proves it."""
    from ..plan.widths import checked_physical_dtypes
    rows = len(arrays[0])
    cuts = [(k * per, min((k + 1) * per, rows)) for k in range(shards)
            if k * per < rows]

    if not cuts:
        return checked_physical_dtypes(phys, types, arrays, nulls=nulls)

    def prove(cut):
        lo, hi = cut
        return checked_physical_dtypes(
            phys, types, [a[lo:hi] for a in arrays],
            nulls=None if nulls is None else
            [None if m is None else m[lo:hi] for m in nulls])

    with concurrent.futures.ThreadPoolExecutor(len(cuts)) as pool:
        proofs = list(pool.map(prove, cuts))
    return tuple(dt if all(p[i] == dt for p in proofs) else None
                 for i, dt in enumerate(proofs[0]))


def stage_scan_split(conn, node: "N.TableScanNode", sf: float, start: int,
                     count: int, capacity: int, predicate=None,
                     sharding=None) -> Batch:
    """Stage one scan split honoring the node's narrow-width annotation
    (plan/widths.py) -- the shared staging path of the runner and the
    streaming executor. `predicate` is the scan's pushed-down range, for
    a connector that prunes by statistics. What the code observes picks
    the form:

    * a connector that offers a producer of decoded pieces
      (`scan_pieces`: a lake file's row groups) and a scan of plain
      fixed-width columns: staged piece by piece (`_stage_pieces`),
      read, decode-to-narrow-lanes and put running as one pipeline;
    * host columns otherwise (`read_columns` of a lake file with string
      columns or a refused narrowing, `generate_columns` of the rest):
      the staging-time range guard re-proves each narrowed lane against
      the actual values, and the batch stages at the narrowed physical
      dtypes in one `batch_from_numpy`;
    * the connector's own `generate_batch` where the node carries no
      width annotation (or the connector can't produce host columns)
      and the connector reads no files.

    With `sharding` (a statement over a mesh: rows over its devices)
    the host columns are proved, narrowed, padded and put shard by
    shard, each on its own chip and the chips side by side
    (`block._sharded_batch`): the hops keep their names and carry the
    shard count as the attribute `shards`. A connector that can only
    give a device batch has it laid out again by one `device_put`.

    Every path records its data-path hops (exec/datapath.py):
    connector_read (host column materialization), decode (a file's
    arrow arrays to lanes), narrow_cast (the staging-time range
    re-proof), device_put (host -> HBM staging, the bytes QueryStats'
    staging stage counts). A whole-table scan of a memory table does
    not come here where a budget is known: `_scan_batch` sends it
    through the resident tier (`_stage_resident`), which shares the
    host-column tail (`_stage_host_columns`) for what it misses."""
    from .datapath import timed_hop
    from .memory import batch_bytes
    phys = getattr(node, "physical_dtypes", None)
    if not hasattr(conn, "read_columns") and (
            not hasattr(conn, "generate_columns")
            or (sharding is None and (not phys or not any(phys)))):
        # the connector stages straight to a device batch: the whole
        # read+put attributes to connector_read (coarse by design --
        # connectors wanting finer hops expose generate_columns)
        with timed_hop("connector_read") as t_read:
            b = conn.generate_batch(node.table, sf, node.columns,
                                    start=start, count=count,
                                    capacity=capacity)
            if sharding is not None:
                b = jax.device_put(b, sharding)
            t_read.bytes = batch_bytes(b)
        return b
    offered = getattr(conn, "scan_pieces", None)
    if offered is not None and node.columns and sharding is None \
            and _plain_lanes(node.column_types):
        scan = offered(node.table, node.columns, start, count, predicate,
                       dtypes=[dt or ty.to_dtype() for dt, ty in zip(
                           phys or [None] * len(node.columns),
                           node.column_types)])
        b = scan and _stage_pieces(scan, node.column_types, capacity)
        if b is not None:
            return b
    arrays, nulls = _read_split(conn, node, sf, start, count, predicate)
    return _stage_host_columns(node.column_types, phys, arrays, nulls,
                               capacity, sharding)


def _stage_host_columns(types, phys, arrays, nulls, capacity: int,
                        sharding=None) -> Batch:
    """Host columns to a device batch at the narrowed dtypes `phys`
    asks for: the staging-time range guard re-proves each narrowed lane
    against the values (hop ``narrow_cast``; under a mesh shard by
    shard), then one `batch_from_numpy` (hop ``device_put``)."""
    from .datapath import timed_hop
    from .memory import batch_bytes
    shards = len(sharding.mesh.devices.flat) if sharding is not None else 1
    by_shard = {"shards": shards} if sharding is not None else None
    if phys and any(phys):
        from ..plan.widths import checked_physical_dtypes
        with timed_hop("narrow_cast", _host_bytes(arrays, nulls), by_shard):
            phys = checked_physical_dtypes(phys, types, arrays,
                                           nulls=nulls) \
                if sharding is None else _checked_by_shard(
                    phys, types, arrays, nulls, shards, capacity // shards)
    with timed_hop("device_put", attrs=by_shard) as t_put:
        b = batch_from_numpy(types, arrays, nulls=nulls, capacity=capacity,
                             physical_dtypes=phys or None,
                             sharding=sharding)
        # sync so the measured wall is the transfer, not the async
        # dispatch returning early (bench.py learned this on the chip).
        # Nothing overlaps on this path: host columns are whole before
        # the first byte is put (a lake scan's pieces overlap in
        # `_stage_pieces`).
        jax.block_until_ready(b)
        t_put.bytes = batch_bytes(b)
    return b


def _tier_room(node: N.PlanNode, scan_range, dyn_filters,
               hbm_budget) -> Optional[int]:
    """The room the resident tier gives a scan that takes it (the
    caller's budget, `resident_budget`): a whole-table scan (no row
    range, no dynamic filter) of a table its connector keeps versions
    of (`scan_snapshot`: the memory store). None for any other scan,
    and where no budget is known."""
    if not isinstance(node, N.TableScanNode) or scan_range is not None \
            or dyn_filters or not node.columns:
        return None
    from ..connectors import catalog
    if not hasattr(catalog(node.connector), "scan_snapshot"):
        return None
    return resident_budget(hbm_budget)


def _resident_place(node: "N.TableScanNode", version: int, rows: int,
                    capacity_hint, pad_multiple: int, sharding):
    """A tier scan's place (exec/resident.py) and the (column, dtype)
    pairs it wants."""
    cap = capacity_hint or max(-(-rows // pad_multiple) * pad_multiple,
                               pad_multiple)
    phys = list(getattr(node, "physical_dtypes", None)
                or [None] * len(node.columns))
    return ((node.connector, node.table, version, cap, shard_key(sharding)),
            list(zip(node.columns, phys)))


def _stage_resident(conn, node: "N.TableScanNode", capacity_hint,
                    pad_multiple: int, sharding, budget: int,
                    memory_pool=None, query_id: Optional[str] = None
                    ) -> Batch:
    """A whole-table scan of a table its connector keeps versions of
    (`scan_snapshot`: the memory store), through the resident tier
    (exec/resident.py). The version, the row count and the host columns
    are read as one snapshot (hop ``connector_read``, which holds the
    tier's lookup too); the columns the tier holds at that version,
    capacity and sharding are taken as they lie in HBM, and only the
    others are re-proved and put (`_stage_host_columns`) and kept (in
    `memory_pool`, moved out of `query_id`'s reservation). A scan the
    tier answers whole records no ``narrow_cast`` and no
    ``device_put``. Counters ``resident_hits`` and ``resident_misses``:
    the columns taken from the tier and those staged for it."""
    from .datapath import timed_hop
    resident = resident_tier()
    resident.watch(node.connector, conn)
    with timed_hop("connector_read") as t_read:
        version, rows, arrays, nulls = conn.scan_snapshot(node.table,
                                                          node.columns)
        place, wanted = _resident_place(node, version, rows, capacity_hint,
                                        pad_multiple, sharding)
        found, kept = resident.take(place, wanted)
        missing = [i for i, w in enumerate(wanted)
                   if w not in found and w not in wanted[:i]]
        t_read.bytes = _host_bytes([arrays[i] for i in missing],
                                   [nulls[i] for i in missing])
    note("resident_hits", len(found))
    note("resident_misses", len(missing))
    active = kept[0] if kept is not None else None
    if missing:
        part = _stage_host_columns(
            [node.column_types[i] for i in missing],
            [wanted[i][1] for i in missing], [arrays[i] for i in missing],
            [nulls[i] for i in missing], place[3], sharding)
        staged = {wanted[i]: col for i, col in zip(missing, part.columns)}
        resident.keep(place, staged, part.active, rows, budget,
                      memory_pool, query_id)
        found.update(staged)
        active = part.active if active is None else active
    return Batch(tuple(found[w] for w in wanted), active)


def _resident_pooled_bytes(node: N.PlanNode, capacity_hint,
                           pad_multiple: int, sharding, memory_pool) -> int:
    """Bytes of a tier scan (`_tier_room`) that the tier holds
    registered in `memory_pool` at the table's version: the
    statement's reservation leaves them out, as the tier's
    registration counts them."""
    from ..connectors import catalog
    conn = catalog(node.connector)
    place, wanted = _resident_place(
        node, conn.table_version(node.table),
        conn.table_row_count(node.table), capacity_hint, pad_multiple,
        sharding)
    return resident_tier().pooled_bytes(place, wanted, memory_pool)


def _scan_batch(node: N.PlanNode, sf: float, capacity_hint: Optional[int],
                pad_multiple: int,
                scan_range: Optional[Tuple[int, int]] = None,
                dyn_filters=None, stats=None, sharding=None,
                hbm_budget=None, memory_pool=None,
                query_id: Optional[str] = None, resident: bool = True
                ) -> Batch:
    """One scan leaf's staged batch; with `sharding` (a statement over
    a mesh) laid out over the mesh's devices, a table's rows shard by
    shard from the host. What the code observes picks the path:

    * a whole-table scan (no row range, no dynamic filter) of a table
      its connector keeps versions of (`scan_snapshot`), where a budget
      is known (`_tier_room`: the statement's `hbm_budget_bytes`
      capped at the device's limit, else that limit): through the
      resident tier (`_stage_resident`), `memory_pool` and `query_id`
      the statement's; `resident=False` stages it afresh (the dynamic
      filter's dimension side: exec/dynfilter.py);
    * a dynamic-filtered scan: read, pruned on the host, then staged;
    * every other scan: `stage_scan_split`."""
    if isinstance(node, N.ValuesNode):
        arrays = []
        null_masks = []
        for ci, ty in enumerate(node.types):
            col = [r[ci] for r in node.rows]
            nulls = np.array([v is None for v in col], dtype=bool)
            if ty.is_string or ty.base in ("array", "map", "row") or \
                    (ty.is_decimal and not ty.is_short_decimal):
                a = np.empty(len(col), dtype=object)
                for i, v in enumerate(col):
                    a[i] = v
                arrays.append(a)
            else:
                arrays.append(np.array([0 if v is None else v for v in col],
                                       dtype=ty.to_dtype()))
            null_masks.append(nulls)
        cap = capacity_hint or -(-len(node.rows) // pad_multiple) * pad_multiple
        if not node.types:
            # zero-column VALUES (FROM-less SELECT): rows are all mask
            import jax.numpy as jnp
            active = np.zeros(cap, dtype=bool)
            active[:len(node.rows)] = True
            return jax.device_put(Batch((), jnp.asarray(active)), sharding)
        return batch_from_numpy(node.types, arrays, nulls=null_masks,
                                capacity=cap, sharding=sharding)
    assert isinstance(node, N.TableScanNode)
    from ..connectors import catalog
    conn = catalog(node.connector)
    room = _tier_room(node, scan_range, dyn_filters, hbm_budget)
    if room and resident:
        return _stage_resident(conn, node, capacity_hint, pad_multiple,
                               sharding, room, memory_pool, query_id)
    if scan_range is not None:
        start, count = scan_range
    else:
        start, count = 0, conn.table_row_count(node.table, sf)
    if dyn_filters:
        # dynamic filtering: prune fact rows host-side BEFORE they are
        # staged into HBM (DynamicFilterSourceOperator pushdown; the
        # win here is smaller staged shapes)
        from .dynfilter import apply_dynamic_filters
        arrays, nulls = _read_split(conn, node, sf, start, count)
        # the filter's host side, between the read and the re-proof: the
        # keep mask over the key columns and every column gathered by it
        kept = {"rows_in": len(arrays[0]) if arrays else 0}
        with stage("prune", kept):
            keep, pruned = apply_dynamic_filters(
                dict(zip(node.columns, arrays)), node.columns, dyn_filters)
            kept["rows_kept"] = len(keep) - pruned
            if stats is not None:
                stats.add("dynamic_filter_rows_pruned", pruned)
                stats.add("dynamic_filter_rows_staged", kept["rows_kept"])
            if not pruned:
                keep = slice(None)  # every row stays: nothing to copy out
            arrays = [a[keep] for a in arrays]
            if nulls is not None:
                nulls = [n[keep] for n in nulls]
        nrows = len(arrays[0])
        cap = max(-(-nrows // pad_multiple) * pad_multiple, pad_multiple)
        return _stage_host_columns(node.column_types,
                                   getattr(node, "physical_dtypes", None),
                                   arrays, nulls, cap)
    cap = capacity_hint or max(-(-count // pad_multiple) * pad_multiple,
                               pad_multiple)
    # connector statistics pruning: a file scan skips the row groups the
    # pushed-down range provably excludes (the exact Filter still runs
    # above) and stages what is left like any other split
    predicate = tuple(node.pushdown) \
        if node.pushdown is not None and scan_range is None else None
    return stage_scan_split(conn, node, sf, start, count, cap, predicate,
                            sharding)


def _count_staged(scan_leaves, batches, collector: StatsCollector,
                  stats: RuntimeStats, prog, sf: float,
                  query_id: str) -> int:
    """Staging's own bookkeeping, the child span ``scan_count`` of
    ``staging``: each scan's rows, its bytes, its operator and accuracy
    records, what narrowing saved; the sums go to the ``staging``
    stage. A scan the resident tier holds the mask of
    (exec/resident.py) takes its rows from the tier's entry; any other
    is counted by reading its `active` mask back whole (attribute
    `bytes_read_back`: the masks read back). Where the statement went
    through the tier, counter ``resident_bytes``: what the tier holds
    after the staging, on its fullest chip. Returns the staged
    bytes."""
    from ..plan.widths import batch_narrowed_bytes_saved, note_narrowed
    from .accuracy import est_rows_of as _acc_est
    from .accuracy import record_node as _acc_record
    from .memory import batch_bytes
    resident = resident_tier()
    known = [resident.rows_of(b) for b in batches]
    staged_rows = staged_bytes = 0
    narrowed_cols = narrowed_saved = 0
    with stage("scan_count", {
            "scans": len(batches),
            "bytes_read_back": sum(int(b.active.nbytes)
                                   for b, n in zip(batches, known)
                                   if n is None)}):
        for si, (s, b) in enumerate(zip(scan_leaves, batches)):
            rows = int(np.asarray(b.active).sum()) if known[si] is None \
                else known[si]
            nbytes = batch_bytes(b)
            staged_rows += rows
            staged_bytes += nbytes
            stats.add("scan_rows", rows)
            collector.operator(_scan_key(si, s), output_rows=rows,
                               output_bytes=nbytes)
            # estimate-vs-actual (exec/accuracy.py): the scan leaf's
            # planner estimate against the rows it actually staged --
            # structural keys line up with the operator rows and across
            # workers running the same fragment
            _acc_record(_scan_key(si, s), _scan_label(s), unit="rows",
                        est=_acc_est(s, sf), actual=rows)
            if prog is not None:  # processed-input counters (monotonic)
                prog.advance(rows=rows, bytes=nbytes)
            if getattr(s, "physical_dtypes", None):
                nc, nb = batch_narrowed_bytes_saved(b)
                narrowed_cols += nc
                narrowed_saved += nb
        collector.bump_stage("staging", rows=staged_rows,
                             bytes=staged_bytes)
        if "resident_hits" in collector.stats.counters:
            collector.note_max("resident_bytes", resident.held_bytes())
        if narrowed_saved:
            # staged bytes saved vs logical lanes: the QueryStats
            # counter the acceptance criteria name, plus the
            # process-lifetime /v1/metrics totals
            # (server/metrics.narrowing_families)
            stats.add("narrowed_bytes_saved", narrowed_saved)
            collector.note("narrowed_bytes_saved", narrowed_saved)
            collector.note("narrowed_columns", narrowed_cols)
            note_narrowed(narrowed_cols, narrowed_saved)
            # narrow-width decisions are exactly the kind of silent
            # plan choice a post-mortem wants on the timeline (flight
            # recorder)
            from ..server.flight_recorder import record_event
            record_event("narrow_width", query_id=query_id,
                         columns=narrowed_cols, bytes_saved=narrowed_saved)
    return staged_bytes


def _process_chips() -> int:
    """Chips this process has (a test stands in for a one-chip host
    here)."""
    return len(jax.devices())


def placement_mesh(root: N.PlanNode, mesh=None):
    """The mesh a statement runs over: the caller's `mesh` where one was
    handed in; else the chips its tables are spread over, which is the
    widest placement among the tables it scans (a memory table's
    `workers`), held to the chips the process has; None, one chip and
    today's path line for line, where that is 1. Read from the plan's
    scans, prepared or not, so that `prepare_plan` and `run_query`, and
    with them the server, `sql()`, the dbapi and a worker, agree."""
    if mesh is not None:
        return mesh
    from ..connectors import catalog
    workers, seen, todo = 1, set(), [root]
    while todo:
        n = todo.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        if isinstance(n, N.TableScanNode):
            try:
                spread = getattr(catalog(n.connector), "table_workers", None)
            except KeyError:  # no such catalog: the plan checker's to say
                spread = None
            if spread is not None:
                workers = max(workers, spread(n.table))
        todo.extend(n.sources)
    if workers > 1:
        workers = min(workers, _process_chips())
    if workers <= 1:
        return None
    from ..parallel.mesh import make_mesh
    return make_mesh(workers)


def prepare_plan(root: N.PlanNode, sf: float = 0.01, mesh=None,
                 session=None) -> N.PlanNode:
    """The plan-shaping pipeline run_query applies before lowering:
    rule-based simplification + channel pruning, cost-based join
    reordering, connector predicate pushdown, NDV capacity refinement,
    AddExchanges (mesh), PlanChecker validation. Exposed so EXPLAIN
    ANALYZE can annotate exactly the tree that executes (pass the
    result back with ``prepared=True``). Write/DDL roots pass through
    untouched -- their inner SELECTs are shaped when the writer
    re-enters run_query."""
    from ..utils.config import session_flag, session_value

    inner_root = root.source if isinstance(root, N.OutputNode) else root
    if isinstance(inner_root, (N.DdlNode, N.TableFinishNode,
                               N.TableWriterNode, N.TableRewriteNode)):
        return root

    def _session_on(name: str) -> bool:
        return session_flag(session, name, True)

    _check_schemas(root, sf)
    mesh = placement_mesh(root, mesh)
    # rule-based simplification + channel pruning (IterativeOptimizer /
    # PruneUnreferencedOutputs analog): narrows intermediates before
    # stats and distribution decide capacities and exchange widths
    if _session_on("iterative_optimizer"):
        from ..plan.rules import optimize_plan
        root = optimize_plan(root)
    # cost-based join reordering (ReorderJoins analog): largest
    # relation stays the streaming probe, smallest builds join first.
    # Runs BEFORE channel pruning of the rebuilt chain would matter --
    # the trailing optimize_plan sweep re-prunes the widened
    # intermediates reorder introduces
    if session_value(session, "join_reordering_strategy",
                     "AUTOMATIC") != "NONE":
        from ..plan.reorder import reorder_joins
        rr = reorder_joins(root, sf)
        if rr is not root and _session_on("iterative_optimizer"):
            from ..plan.rules import optimize_plan
            rr = optimize_plan(rr)
        root = rr
    # connector predicate pushdown: range conjuncts above pushdown-
    # capable scans (parquet row-group statistics) annotate the scan
    if _session_on("scan_predicate_pushdown"):
        from ..plan.pushdown import push_scan_predicates
        root = push_scan_predicates(root)
    # capacity refinement (CBO stats): shrink group tables to the
    # connector-proven NDV bound so group-by rides the scatter-free
    # small-table kernels wherever statistics allow
    if _session_on("stats_capacity_refinement"):
        from ..plan.stats import refine_capacities
        root = refine_capacities(root, sf)
    # narrow-width execution (plan/widths.py): annotate every scan whose
    # column ranges the connector proves with the narrowest safe
    # physical lanes; staging honors them (halved host->HBM bytes for
    # narrowed columns), compute sites widen before arithmetic.
    # PRESTO_TPU_NARROW=0 / session narrow_width_execution=false = wide A/B
    from ..plan.widths import narrow_enabled
    if narrow_enabled(session):
        from ..plan.widths import annotate_widths
        root = annotate_widths(root, sf)
    if mesh is not None:
        # make the plan SPMD-correct: single-node operators get the
        # exchanges they need (AddExchanges; idempotent for plans that
        # already carry PARTIAL/FINAL + exchange structure). The session's
        # join_distribution_type picks broadcast vs partitioned joins
        # (DetermineJoinDistributionType); AUTOMATIC, upstream's default
        # and this engine's, decides per join from the build side's
        # estimated rows (plan/distribute._BROADCAST_ROW_LIMIT)
        from ..plan.distribute import add_exchanges
        jd = str(session_value(session, "join_distribution_type",
                               "AUTOMATIC")).upper()
        strategy = {"BROADCAST": "broadcast",
                    "PARTITIONED": "partitioned"}.get(jd, "automatic")
        root = add_exchanges(root, join_strategy=strategy, sf=sf)
    from ..plan.validator import validate_plan
    violations = validate_plan(root, distributed=mesh is not None)
    if violations:
        raise ValueError("plan not executable by the TPU engine "
                         f"(PlanChecker): {violations}")
    # estimate stamping (exec/accuracy.py): every prepared node carries
    # its planner row estimate, so EXPLAIN and the runtime's
    # estimate-vs-actual ledger read ONE provenance
    from .accuracy import stamp_estimates
    stamp_estimates(root, sf)
    return root


def _check_schemas(root: N.PlanNode, sf: float) -> None:
    """A scan that named a schema (`tpch.sf10.lineitem`) is held to the
    scale this run serves; the connector raises where they differ."""
    from ..connectors import catalog
    seen: set = set()
    todo = [root]
    while todo:
        n = todo.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        if isinstance(n, N.TableScanNode) and n.schema is not None:
            catalog(n.connector).check_schema(n.schema, sf)
        todo.extend(n.sources)


def run_query(root: N.PlanNode, sf: float = 0.01, mesh=None,
              capacity_hints: Optional[Dict[str, int]] = None,
              default_join_capacity: int = 1 << 16,
              split_rows: Optional[int] = None,
              scan_ranges: Optional[Dict[str, Tuple[int, int]]] = None,
              remote_sources: Optional[Dict[str, Batch]] = None,
              memory_pool=None, query_id: str = "query",
              session=None,
              hbm_budget_bytes: Optional[int] = None,
              prepared: bool = False,
              trace_id=None) -> QueryResult:
    """Plan -> results, end to end (DistributedQueryRunner analog for
    programmatic plans). With a mesh, scan batches are padded to a
    multiple of the mesh size and the plan runs SPMD. With `split_rows`,
    streamable aggregation plans execute split-by-split with bounded
    HBM (exec/streaming.py).

    Every invocation maintains a live-progress entry keyed by
    ``query_id`` (exec/progress.py): monotonic stage/splits/rows/bytes
    counters an in-flight status poll, ``GET /v1/cluster`` and the
    stuck-progress watchdog read while the query is still RUNNING.
    Nested invocations (write roots) share their outer scope's entry.

    A per-query datapath ledger (exec/datapath.py) is ambient for the
    whole invocation: every instrumented hop on this thread
    (connector read, decode, narrow cast, device put, kernel, serde)
    attributes to THIS query; nested invocations (write roots' inner
    SELECTs) shadow-and-restore like the progress entry."""
    from .accuracy import AccuracyLedger
    from .accuracy import recording as _acc_recording
    from .datapath import DatapathLedger
    from .datapath import recording as _dp_recording
    from .progress import begin as _progress_begin
    prog = _progress_begin(query_id)
    dp = DatapathLedger()
    # the per-query estimate-vs-actual ledger (exec/accuracy.py) is
    # ambient too: measured boundaries (scan outputs, region outputs,
    # K005 footprint audits) attribute to THIS query's plan nodes
    acc = AccuracyLedger()
    try:
        with joining(query_id, trace_id) as collector, \
                _dp_recording(dp), _acc_recording(acc):
            res = _run_query_inner(
                root, sf=sf, mesh=mesh, capacity_hints=capacity_hints,
                default_join_capacity=default_join_capacity,
                split_rows=split_rows, scan_ranges=scan_ranges,
                remote_sources=remote_sources, memory_pool=memory_pool,
                query_id=query_id, session=session,
                hbm_budget_bytes=hbm_budget_bytes, prepared=prepared,
                trace_id=trace_id, prog=prog, dp=dp, acc=acc,
                collector=collector)
    except BaseException:
        prog.release(state="FAILED")
        raise
    prog.release(state="FINISHED")
    return res


def _run_query_inner(root: N.PlanNode, sf: float = 0.01, mesh=None,
                     capacity_hints: Optional[Dict[str, int]] = None,
                     default_join_capacity: int = 1 << 16,
                     split_rows: Optional[int] = None,
                     scan_ranges: Optional[Dict[str,
                                                Tuple[int, int]]] = None,
                     remote_sources: Optional[Dict[str, Batch]] = None,
                     memory_pool=None, query_id: str = "query",
                     session=None,
                     hbm_budget_bytes: Optional[int] = None,
                     prepared: bool = False,
                     trace_id=None, prog=None, dp=None,
                     acc=None,
                     collector: Optional[StatsCollector] = None
                     ) -> QueryResult:
    # write/DDL roots execute their source on device, then write
    # host-side (TableWriterOperator.java:76 analog -- the sink is a
    # host effect, fed by one DMA-out of the computed rows)
    inner_root = root.source if isinstance(root, N.OutputNode) else root
    if isinstance(inner_root, (N.DdlNode, N.TableFinishNode,
                               N.TableWriterNode, N.TableRewriteNode)):
        from ..server.access import get_access_control
        acl = get_access_control()
        if acl is not None:
            acl.check_plan(root, (session or {}).get("user", ""))
        res = _run_write_root(
            inner_root, sf=sf, mesh=mesh, capacity_hints=capacity_hints,
            default_join_capacity=default_join_capacity,
            split_rows=split_rows, scan_ranges=scan_ranges,
            remote_sources=remote_sources, memory_pool=memory_pool,
            query_id=query_id, session=session,
            hbm_budget_bytes=hbm_budget_bytes, trace_id=trace_id)
        # the inner SELECT joined this statement's collector: its
        # stages and the sink's `write` are one document
        res.query_stats = collector.stats
        return res
    t_query0 = time.time()
    with stage("plan"):
        mesh = placement_mesh(root, mesh)
        if not prepared:
            with stage("plan.prepare"):
                root = prepare_plan(root, sf=sf, mesh=mesh, session=session)
        if prog is not None:
            prog.advance(stage="plan")
        # access control: the analysis-time boundary (AccessControlManager
        # checkCanSelectFromColumns / write checks) -- enforced on the
        # plan before anything touches data
        from ..server.access import get_access_control
        acl = get_access_control()
        if acl is not None:
            acl.check_plan(root, (session or {}).get("user", ""))
    from ..utils.config import session_flag, session_value
    refine = session_flag(session, "stats_capacity_refinement", True)
    stats = RuntimeStats()
    hbm_budget = hbm_budget_bytes
    if hbm_budget is None and session is not None:
        hbm_budget = session.get("hbm_budget_bytes")
    if split_rows is not None and mesh is None:
        from .streaming import run_streaming_agg, streamable_agg_shape
        shape = streamable_agg_shape(root)
        if shape is not None:
            agg_node, _ = shape
            if prog is not None:
                prog.advance(stage="execute")
            if hbm_budget:  # 0 / None = uncapped (the config default)
                from .spill import plan_state_bytes, run_spilled_agg
                spill_dir = session_value(session, "spill_path") or None
                spill_thresh = int(session_value(
                    session, "spill_file_threshold_bytes", 256 << 20))
                if 2 * plan_state_bytes(agg_node) > hbm_budget:
                    # the full state table cannot fit the budget: grouped
                    # execution with per-bucket host offload (the
                    # SpillableHashAggregationBuilder path)
                    with stage("execute"):
                        out_b = run_spilled_agg(
                            root, sf, split_rows, hbm_budget, stats,
                            spill_dir=spill_dir,
                            spill_file_threshold=spill_thresh)
                    res = _batch_to_result(out_b, root)
                    res.stats = stats.snapshot()
                    _finalize_query_stats(collector, res, t_query0, 0,
                                          root, dp=dp, acc=acc, sf=sf)
                    return res
            with stage("execute"):
                r = run_streaming_agg(root, sf, split_rows)
            if bool(np.asarray(r.overflow)):
                raise RuntimeError("streaming aggregation overflowed "
                                   "max_groups; raise AggregationNode.max_groups")
            # the streaming executor accumulates raw states; SINGLE-step
            # plans still owe the evaluateFinal step
            from ..ops.aggregation import finalize_states
            out_b = finalize_states(r.batch, len(agg_node.group_channels),
                                    agg_node.aggregates)
            res = _batch_to_result(out_b, root)
            res.stats = stats.snapshot()
            _finalize_query_stats(collector, res, t_query0, 0, root,
                                  dp=dp, acc=acc, sf=sf)
            return res
    pad = (mesh.devices.size if mesh is not None else 1) * 8
    sharding = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        from ..parallel.mesh import WORKERS_AXIS
        sharding = NamedSharding(mesh, PartitionSpec(WORKERS_AXIS))
    hints = capacity_hints or {}
    scan_ranges = scan_ranges or {}
    remote_sources = remote_sources or {}
    # Compiled-plan cache (exec/plan_cache.py): repeat submissions of a
    # structurally identical plan reuse the jitted executable instead of
    # re-tracing + re-compiling. Remote sources refer to THIS plan
    # object's ids, which a cached plan does not share -- those callers
    # (the fragment tier) compile fresh. Capacity hints and scan ranges
    # name scans only, and a scan is the same leaf, by position, in
    # every plan of one fingerprint: they follow the cached plan below.
    use_cache = not remote_sources
    # Pipeline-region partition (exec/regions.py): the prepared plan
    # becomes 1..N regions, each staged as ONE XLA program. With fusion
    # on and nothing refused/demoted this is a single region -- the
    # fused whole-fragment program, compiled and cached exactly as
    # before. Materialized boundaries (fusion off, footprint refusal,
    # demotion) run the general region executor below.
    from .plan_cache import plan_fingerprint
    from .regions import fusion_memory, partition_regions
    from .. import failpoints
    with stage("plan"):
        rplan = partition_regions(root, session=session, sf=sf, mesh=mesh)
        if failpoints.ARMED and rplan.fused and mesh is None \
                and len(rplan.regions) == 1 and rplan.regions[0].ops > 1:
            try:
                failpoints.hit("fusion.demote")
            except Exception as e:  # noqa: BLE001 - any injected class
                # forced demotion mid-query (chaos/bisection): the fused
                # span demotes and THIS query already runs materialized
                fusion_memory().demote(
                    plan_fingerprint(rplan.regions[0].root),
                    f"failpoint ({type(e).__name__})")
                # the shared demotion counter (both paths) + the forced-
                # path discriminator, correlated by the flight event reason
                stats.add("fusion_forced_demotions", 1)
                collector.note("fusion_demotions")
                from ..server.flight_recorder import record_event
                record_event("fusion_demotion", query_id=query_id,
                             reason="failpoint")
                rplan = partition_regions(root, session=session, sf=sf,
                                          mesh=mesh)
        multi_region = len(rplan.regions) > 1
        if multi_region:
            stats.add("fusion_regions", len(rplan.regions))
            collector.note("fusion_regions", len(rplan.regions))
            plan = jfn = call_lock = None
            fp = None
            scan_leaves: List[N.PlanNode] = []
            from .planner import _collect_scans
            _collect_scans(root, scan_leaves)
        elif use_cache:
            named: List[N.PlanNode] = []
            if hints or scan_ranges:
                from .planner import _collect_scans
                _collect_scans(root, named)
            plan, jfn, call_lock = _compile_any(
                root, mesh, default_join_capacity, 1, True)
            # canonical tree: node ids match plan.scan_nodes
            root = plan.root
            fp = plan_fingerprint(root)
            scan_leaves = plan.scan_nodes
            ids = {mine.id: kept.id for mine, kept in zip(named, scan_leaves)}
            hints = {ids[k]: v for k, v in hints.items() if k in ids}
            scan_ranges = {ids[k]: v for k, v in scan_ranges.items()
                           if k in ids}
        else:
            plan, jfn, call_lock = _compile_any(
                root, mesh, default_join_capacity, 1, False)
            fp = None
            scan_leaves = plan.scan_nodes
    adaptive_off = False
    if session is not None:
        try:
            v = session.get("adaptive_capacity")
        except (KeyError, TypeError):
            v = None
        adaptive_off = v is not None and not v
    # dynamic filtering (local tier): dimension build sides run first
    # and their key domains prune fact scans at staging time
    dyn_filters = {}
    if session is None:
        dyn_on = True
    else:
        try:
            v = session.get("dynamic_filtering")
        except (KeyError, TypeError):  # plain dicts / older sessions
            v = None
        dyn_on = True if v is None else bool(v)
    if dyn_on and mesh is None:
        from .dynfilter import collect_dynamic_filters
        with stage("dynfilter"):
            dyn_filters = collect_dynamic_filters(root, sf)
        if dyn_filters:
            stats.add("dynamic_filters", sum(len(v)
                                             for v in dyn_filters.values()))
    reserved = 0
    if memory_pool is not None:
        # admission accounting (MemoryPool.reserve analog): PLANNED scan
        # footprints are charged before any device allocation, so a
        # reservation failure surfaces before the scan stage can OOM;
        # what the resident tier has registered of a scan is left out
        # (its registration counts those bytes)
        reserved = sum(max(
            _planned_scan_bytes(s, sf, hints.get(s.id), pad,
                                scan_ranges.get(s.id), remote_sources)
            - (_resident_pooled_bytes(s, hints.get(s.id), pad, sharding,
                                      memory_pool)
               if _tier_room(s, scan_ranges.get(s.id),
                             dyn_filters.get(s.id), hbm_budget) else 0),
            0) for s in scan_leaves)
        memory_pool.reserve(query_id, reserved)
        if prog is not None:
            prog.note_memory(reserved)
    try:
        if prog is not None:
            prog.set_planned(len(scan_leaves))
            prog.advance(stage="staging")
        with stage("staging"):
            batches = []
            for si, s in enumerate(scan_leaves):
                t_scan0 = time.time()
                if isinstance(s, N.RemoteSourceNode):
                    assert s.id in remote_sources, \
                        f"no remote source batch supplied for node {s.id}"
                    batches.append(remote_sources[s.id])
                else:
                    batches.append(_scan_batch(
                        s, sf, hints.get(s.id), pad,
                        scan_ranges.get(s.id),
                        dyn_filters=dyn_filters.get(s.id),
                        stats=stats, sharding=sharding,
                        hbm_budget=hbm_budget, memory_pool=memory_pool,
                        query_id=query_id))
                collector.operator(
                    _scan_key(si, s), _scan_label(s),
                    wall_us=int((time.time() - t_scan0) * 1e6))
                if prog is not None:  # one split staged = one heartbeat
                    prog.advance(splits=1)
            staged_bytes = _count_staged(scan_leaves, batches, collector,
                                         stats, prog, sf, query_id)
    except Exception:
        if memory_pool is not None:
            memory_pool.free(query_id, reserved)
            memory_pool.query_peak_bytes(query_id, pop=True)
        raise
    from .accuracy import record_node as _acc_record
    # staging-time kernel audit (audit/staged.py): with the
    # kernel_audit session property (env PRESTO_TPU_KERNEL_AUDIT) on,
    # trace the fused program once more over the staged batches and run
    # the IR passes -- findings land in QueryStats counters, the
    # process /v1/metrics totals, and one flight-recorder event; the
    # K005 footprint estimate feeds the memory pool. Memoized per
    # (plan fingerprint, mesh, kernel mode, shapes); never fails the
    # query.
    from ..audit.staged import audit_staged_query, kernel_audit_enabled
    if kernel_audit_enabled(session) and not multi_region:
        audit_report = audit_staged_query(
            plan, batches, mesh=mesh, query_id=query_id, session=session,
            collector=collector, stats=stats, memory_pool=memory_pool,
            plan_fp=fp)
        if audit_report and audit_report.get("peak_bytes_estimate"):
            # ... and the estimate side of the footprint accuracy
            # record (actual fills in at finalize from the pool's
            # measured per-query peak)
            _acc_record("footprint", "MemoryPool", unit="bytes",
                        est=float(audit_report["peak_bytes_estimate"]))
            # the K005 footprint estimate feeds the fusion cost model:
            # a fused span whose measured peak exceeds
            # kernel_audit_budget_bytes is REFUSED on its next
            # submission (exec/regions.py footprint feedback)
            if rplan.fused and mesh is None and rplan.regions[0].ops > 1:
                fusion_memory().note_footprint(
                    fp or plan_fingerprint(root),
                    audit_report["peak_bytes_estimate"])
    device_s = 0.0           # summed dispatch+sync wall (all reruns)
    compile_us: Optional[int] = None
    res = None
    if prog is not None:
        prog.advance(stage="execute")
    try:
        with stage("execute"):
            if multi_region:
                # region executor: each pipeline region dispatches as
                # its own program; boundaries are HBM-resident Batch
                # handoffs (no host round trip), reruns re-dispatch
                # only the overflowing region
                out, device_s, compile_us = _execute_regions(
                    rplan, scan_leaves, batches, default_join_capacity,
                    use_cache, stats, session, adaptive_off, refine,
                    prog, collector, query_id,
                    memory_pool, plan_fp_root=plan_fingerprint(root),
                    sf=sf, hbm_budget=hbm_budget)
            else:
                (out, device_s, dispatch_fn, call_lock, ran_caps,
                 scale, plan) = _dispatch_ladder(
                    root, plan, jfn, call_lock, batches, mesh,
                    default_join_capacity, use_cache, fp, stats,
                    adaptive_off, refine, prog, rplan.regions[0].tag,
                    hbm_budget)
        # XLA compile cost (compile-time captured via jax.monitoring; a
        # plan-cache hit naturally reports zero) + the program's
        # FLOPs / bytes-accessed from cost_analysis, memoized per plan.
        # Clamped to the execute wall that contains it (nested-jit
        # lowering events can overlap), anchored at execute start so
        # traces render the compile where it happened. The
        # region executor drains compile incrementally per region; any
        # remainder is folded in here.
        compile_us = (compile_us or 0) + collector.take_compile_us()
        exec_stage = collector.stats.stages.get("execute")
        if exec_stage is not None and exec_stage.wall_us:
            compile_us = min(compile_us, exec_stage.wall_us)
        if compile_us:
            anchor = collector.stage_span_start("execute") or t_query0
            collector.record_stage(
                "compile", anchor, anchor + compile_us / 1e6,
                compile_us=compile_us)
            stats.add("compile_s", compile_us / 1e6)
        if session_flag(session, "query_cost_analysis", False) \
                and not multi_region:
            fp_cost = fp if fp is not None else plan_fingerprint(root)
            # the capacities tell a rerun's or a refit's program from
            # the plan's own (same fingerprint + shapes otherwise)
            cost = _stage_cost(dispatch_fn, batches,
                               (fp_cost, ran_caps, scale), call_lock)
            if cost:
                collector.bump_stage("compile", **cost)
        if rplan.fused and mesh is None and not multi_region \
                and rplan.regions[0].ops > 1:
            # fused-side sample for the demotion comparator: device
            # occupancy of the fused span, compile excluded. When these
            # samples show the fused form regressing beyond
            # the perfgate band vs the materialized baseline, the span
            # demotes and the NEXT submission runs materialized.
            mem = fusion_memory()
            span_fp = fp if fp is not None else plan_fingerprint(root)
            mem.note_fused(span_fp,
                           max(int(device_s * 1e6) - compile_us, 0))
            verdict = mem.maybe_demote(span_fp)
            if verdict is not None:
                collector.note("fusion_demotions")
                from ..server.flight_recorder import record_event
                record_event("fusion_demotion", query_id=query_id,
                             reason="profiler",
                             ratio=verdict.get("ratio"))
        # kernel hop (exec/datapath.py): the compiled program's dispatch
        # wall over the bytes it read -- the data-path waterfall's
        # device-side rung, bounded by the device_put ceiling proxy.
        # XLA compile is SUBTRACTED (same correction the fusion
        # comparator applies above): a cold dispatch's 1-2s
        # compile would otherwise read as <1% utilization and misname
        # 'kernel' as the bottleneck on every fresh query. Bytes scale
        # with the DISPATCH count (device_s sums every overflow
        # rerun's wall, and each rerun re-reads the staged inputs) so
        # a capacity-rescaled query's achieved rate stays honest.
        from .datapath import record_hop as _dp_record
        _snap = stats.snapshot()
        _dispatches = 1 + \
            int(_snap.get("capacity_reruns", {}).get("total", 0)) + \
            int(_snap.get("exchange_slot_reruns", {}).get("total", 0))
        _dp_record("kernel", staged_bytes * _dispatches,
                   max(device_s - (compile_us or 0) / 1e6, 0.0))
        if prog is not None:
            prog.advance(stage="fetch")
        with stage("fetch"):
            res = _batch_to_result(out, root)
    finally:
        # the close-out, from `fetch`'s exit to the return into the
        # caller, on the success AND failure paths: the pool's map must
        # stay bounded by in-flight queries, so the per-query peak is
        # always drained; the statement's device buffers (its staged
        # batches, its output) are let go here, inside the span, and not
        # at the frame's exit (the runtime frees them when the next
        # transfer is issued: PERF.md section 6, PR 36); a statement
        # that answered folds its ledgers into its stats
        with stage("finish"):
            peak_reserved = 0
            if memory_pool is not None:
                memory_pool.free(query_id, reserved)
                peak_reserved = memory_pool.query_peak_bytes(query_id,
                                                             pop=True)
            batches = out = None
            if res is not None:
                stats.add("output_rows", res.row_count)
                res.stats = stats.snapshot()
                _finalize_query_stats(collector, res, t_query0,
                                      peak_reserved, root, dp=dp, acc=acc,
                                      sf=sf)
    return res


# adaptive-capacity feedback (HBO-lite, HistoryBasedPlanStatistics
# analog, kept per plan node as upstream's is): plan fingerprint ->
# {pre-order index: the capacity the node last fitted at}, and under a
# mesh the exchange-slot scale beside it. A counted node's capacity
# comes from the count its program reported (`plan/stats.py`).
# Bounded process-local memory; structurally identical future
# submissions start at the known-good sizes instead of re-laddering.
_CAPACITY_FEEDBACK: Dict[str, Dict[int, int]] = {}
_SLOT_FEEDBACK: Dict[str, int] = {}
# a ladder that has not fitted after this many reruns gives up (every
# rerun sizes at least one node from its count or grows all four times)
_MAX_CAPACITY_RERUNS = 12
# a plan that fitted is compiled and run again at its fitted capacities
# within the statement only where that frees this share of the capacity
# rows that ran: a program is not built again for less
_REFIT_SHARE = 8
_MAX_CAPACITY_SCALE = 1 << 10  # the mesh ladder's start stops here
# Over a mesh the join and group ladder starts where the capacities
# reach this share of the largest scan's rows a chip, not at the
# default: every rung is one more SPMD program to compile (minutes each
# at tens of millions of rows a chip: PERF.md, PR 34), while a capacity
# of a sixteenth of the largest scan holds, a lane, a sixteenth of what
# that scan already does.
_MESH_LADDER_SHARE = 16


def _mesh_ladder_start(batches, mesh, default_join_capacity: int) -> int:
    """The capacity scale a meshed program's ladder starts from: the
    power of four (the ladder's step) at which `default_join_capacity`
    reaches 1 / `_MESH_LADDER_SHARE` of the largest staged scan's rows a
    chip; 1 for small tables."""
    per_chip = max((b.capacity for b in batches), default=0) \
        // mesh.devices.size
    scale = 1
    while default_join_capacity * scale * _MESH_LADDER_SHARE < per_chip \
            and scale < _MAX_CAPACITY_SCALE:
        scale *= 4
    return scale


def _read_status(status, plan, expand_steps: Optional[int]
                 ) -> Tuple[int, int, Dict[int, int]]:
    """The one host read of what a program returns beside its batch
    (`CompiledPlan.split_status`): its overflow flags come back, the
    trips its joins' lookups took go to the statement's counters, and
    with them `expand_steps`, the trips of its joins' expansions
    (`CompiledPlan.expand_steps_of`: None for a program without a
    join), how many of its joins compacted their probe, and how many of
    its lookups their directory answered alone. With the flags come the
    bytes of rows a meshed program's exchanges routed (a chip's mean)
    and what each counted node needed, by pre-order index."""
    word, routed, needs = plan.split_status(status)
    flags, steps, compacted, direct = split_flags(int(word))
    if steps or direct or expand_steps is not None:  # a lookup ran
        note("join_search_steps", steps)  # 0 where directories answered
        note("join_lookup_direct", direct)
    if expand_steps is not None:  # 0 too: a join whose table is its
        note("join_expand_steps", expand_steps)  # own directory
        note("join_probe_compacted", compacted)
    return flags, int(routed), {k: int(v) for k, v in needs.items()}


def _program_hbm_bytes(plan, dispatch_fn, batches) -> int:
    """Device memory of the program `dispatch_fn` is about to run on
    `batches`: its arguments, outputs and temporaries as XLA's memory
    analysis of the executable gives them, aliased bytes counted once.
    Called under the plan's call lock before the dispatch: lowering
    traces and compiles a shape met for the first time, and the call
    that follows finds the executable jit keeps for these shapes (no
    second trace, no second compile); the answer stays with the
    compiled plan. A program over a mesh plans that much on each of its
    chips: the analysis of an SPMD executable is one device's. Where the
    executable gives no analysis (one read from a compile cache may
    not), the allocator's peak: then the process's peak so far, not
    this program's."""
    key = shape_key(batches)
    if key not in plan.hbm_bytes:
        found = 0
        try:
            ma = dispatch_fn.lower(tuple(batches)).compile() \
                .memory_analysis()
            found = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        except Exception:  # noqa: BLE001 - a backend without the analysis
            pass
        if found <= 0:
            found = int((jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use", 0))
        plan.hbm_bytes[key] = found
    return plan.hbm_bytes[key]


def _worth_refit(ran: Dict[int, int], fitted: Dict[int, int]) -> bool:
    """Whether a plan that fitted at `ran` is built again at `fitted`:
    where that frees 1 / `_REFIT_SHARE` of the capacity rows or more."""
    total = sum(ran.values())
    return (total - sum(fitted.values())) * _REFIT_SHARE >= total > 0


def _dispatch_ladder(root: N.PlanNode, plan, jfn, call_lock, batches,
                     mesh, default_join_capacity: int, use_cache: bool,
                     fp: Optional[str], stats, adaptive_off: bool,
                     refine: bool, prog, tag: str, hbm_budget=None):
    """The overflow->rerun dispatch loop for ONE compiled program (a
    whole fused plan or a single pipeline region).

    Exchange-slot overflow (flag bit1) -> rerun with geometrically
    larger slots; slots clamp at the sender capacity, where overflow is
    impossible, so this converges. Join/group overflow (bit0) reruns
    with larger capacities: a node whose count passed its capacity is
    sized from the count, the nodes above it and those that report a
    flag alone grow four times (`plan/stats.grown_capacities`), up to
    the ceilings. This is the memory-feedback loop the reference runs
    as reserve/revoke -- here it recompiles with bigger static buckets
    instead. Under the region executor only the overflowing REGION
    re-dispatches; upstream regions' materialized outputs are reused.

    A dispatch that fitted has exact counts: where the ladder had
    taken the plan above its own capacities and it ran with much more
    room than its nodes needed (`_worth_refit`: the fourfold steps, the
    start under a mesh), the plan is built once more at the fitted
    capacities and run within the same statement (counter
    ``capacity_refits``), so that the fingerprint's next statement
    finds the fitted program compiled; the answer is the same rows
    either way. A plan that fitted as planned is left as planned: a
    statement that never overflowed pays for no second program. The
    capacities that fitted are remembered per node
    (`_CAPACITY_FEEDBACK`); should the data outgrow them, the flags
    rerun as ever. ``adaptive_capacity=false`` runs the plan as
    planned: no feedback, no rerun, no refit.

    Each turn is two stages of the ambient collector, children of
    ``execute`` with the region's `tag`: ``dispatch`` (the call of the
    jitted program until it returns: flatten, jit-cache lookup,
    trace/lower/compile or cache read on a miss, enqueue) and
    ``device_wait`` (block_until_ready plus the status read). Before
    each dispatch, the program's planned bytes (`_program_hbm_bytes`)
    are noted and the resident tier trimmed to the room the
    statement's `hbm_budget` leaves beside them (exec/resident.py).

    Returns (out, device_s, dispatch_fn, call_lock, the capacities that
    ran as a hashable, scale, plan)."""
    from ..plan.stats import (capacities, fitted_capacities,
                              grown_capacities, scaled_capacities,
                              with_capacities)
    device_s = 0.0
    scale = _SLOT_FEEDBACK.get(fp, 1) if fp and mesh is not None else 1
    adapt = bool(fp) and not adaptive_off
    base = capacities(root, default_join_capacity)
    caps = _CAPACITY_FEEDBACK.get(fp) if adapt else None
    if caps is None:
        caps = base
        if mesh is not None:
            caps = scaled_capacities(root, base, _mesh_ladder_start(
                batches, mesh, default_join_capacity))

    def build():
        # HBO-lite: the capacities a structurally identical plan fitted
        # at, a rung's, or the fitted ones: explicit on every node
        return _compile_any(
            with_capacities(root, caps) if caps != base else root, mesh,
            default_join_capacity, scale, use_cache)

    if caps != base or scale > 1:
        plan, jfn, call_lock = build()
    from .datapath import now_us as _now_us
    region = {"region": tag}
    note("capacity_reruns", 0)  # in every statement's counters, 0 too
    reruns, refitted = 0, False
    while True:
        t_disp0 = _now_us()
        with stage("dispatch", region):
            dispatch_fn = jax.jit(plan.fn) if jfn is None else jfn
            lock = call_lock if jfn is not None \
                else contextlib.nullcontext()
            with lock:  # serialize trace-time closure state
                planned = _program_hbm_bytes(plan, dispatch_fn, batches)
                expand_steps = plan.expand_steps_of(batches)
                exchanges = plan.exchanges_of(batches)
            note_max("program_hbm_bytes", planned)
            # the resident tier makes room before the program runs
            resident_tier().note_program(planned, resident_budget(hbm_budget))
            with lock:
                out, overflow = dispatch_fn(tuple(batches))
        with stage("device_wait", region):
            jax.block_until_ready(out)
            # host-observed device occupancy of this dispatch: the
            # block_until_ready delta around the existing sync point is
            # the only per-kernel timing one fused program exposes -- on
            # the monotonic now_us clock
            device_s += (_now_us() - t_disp0) / 1e6
            flags, routed, needs = _read_status(overflow, plan,
                                                expand_steps)
        if prog is not None:  # each landed dispatch advances
            prog.advance()
        if flags == 0:
            fitted = fitted_capacities(base, caps, needs)
            if adapt and not refitted and caps != base \
                    and _worth_refit(caps, fitted):
                refitted = True
                caps = fitted
                stats.add("capacity_refits", 1)
                note("capacity_refits")
                plan, jfn, call_lock = build()
                continue
            if adapt and caps != base:
                _CAPACITY_FEEDBACK[fp] = caps
            if scale > 1 and fp:
                _SLOT_FEEDBACK[fp] = scale
            if needs:
                # how full the program that answered ran its counted
                # nodes: the capacities it was built with, what they held
                note("capacity_rows", sum(plan.counted.values()))
                note("capacity_live_rows", sum(needs.values()))
            if mesh is not None:
                # the program that answered, a plan-cache hit or not:
                # its chips, its exchanges (constants kept with the
                # compiled plan) and the bytes of rows they routed
                note_max("mesh_chips", mesh.devices.size)
                for name, value in (exchanges or {}).items():
                    note(name, value)
                note("exchange_row_bytes", routed)
            break
        if flags & 1:
            # hard (join/group/unnest) overflow: adaptive rerun with
            # larger capacities (the memory-feedback loop that replaces
            # per-query hand hints; reserve/revoke analog)
            grown = caps if adaptive_off or reruns >= _MAX_CAPACITY_RERUNS \
                else grown_capacities(root, caps, needs)
            if grown == caps:
                hint = (" (note: connector NDV statistics shrank "
                        "group capacities this run; set session "
                        "stats_capacity_refinement=false if a "
                        "hand-set max_groups must stand)"
                        if refine else "")
                raise RuntimeError(
                    "plan execution overflowed a static bucket "
                    "(join/group capacity) beyond the adaptive "
                    "rerun ceiling; rerun with larger capacity "
                    "hints (max_groups / join_capacity)" + hint)
            caps = grown
            reruns += 1
            stats.add("capacity_reruns", 1)
            note("capacity_reruns")
            scale = 1
            plan, jfn, call_lock = build()
            continue
        if mesh is None or scale >= 1 << 20:  # unreachable: clamp
            raise RuntimeError(
                "exchange slot overflow did not converge")
        scale *= 2
        stats.add("exchange_slot_reruns", 1)
        note("capacity_reruns")  # a slot is a capacity too
        plan, jfn, call_lock = build()
    return (out, device_s, dispatch_fn, call_lock,
            tuple(sorted(caps.items())), scale, plan)


def _execute_regions(rplan, scan_leaves, batches, default_join_capacity,
                     use_cache, stats, session, adaptive_off, refine,
                     prog, collector, query_id,
                     memory_pool, plan_fp_root: str, sf: float = 0.01,
                     hbm_budget=None):
    """Materialized region executor (exec/regions.py partition): run
    each pipeline region as its own compiled-and-cached program in
    producer order. Region outputs stay DEVICE-resident Batches handed
    to downstream regions' programs -- a materialized block boundary in
    HBM, never a host round trip. Per-region: the plan cache keys on
    the region fingerprint, the kernel auditor (when armed) audits the
    region's program and feeds its K005 peak into the fusion cost
    model; the `dispatch` / `device_wait` spans around each region's
    call carry the region's tag (attribute `region`).

    Returns (final output Batch, total device seconds, total compile
    micros drained so far)."""
    import contextlib

    from ..audit.staged import audit_staged_query, kernel_audit_enabled
    from ..server.flight_recorder import record_event
    from ..utils.config import session_flag
    from .accuracy import est_rows_of as _acc_est
    from .accuracy import record_node as _acc_record
    from .donation import (donation_enabled, note_donation,
                           note_fallback, overflow_incapable,
                           prepare_donation)
    from .memory import batch_bytes
    from .plan_cache import plan_fingerprint
    from .regions import fusion_memory
    staged_by_id = {id(n): b for n, b in zip(scan_leaves, batches)}
    outputs: Dict[int, Batch] = {}
    # consumer refcounts: a materialized intermediate is dropped after
    # its LAST consumer dispatches, so peak HBM in per-op mode is the
    # max live set, not the sum of every boundary in the chain
    consumers: Dict[int, int] = {}
    for reg in rplan.regions:
        for i in reg.inputs:
            if i.kind == "region":
                consumers[i.region] = consumers.get(i.region, 0) + 1
    total_device_s = 0.0
    total_compile_us = 0
    audit_on = kernel_audit_enabled(session)
    cost_on = session_flag(session, "query_cost_analysis", False)
    donate_on = donation_enabled(session)
    # region-boundary intermediates are real HBM the fused path never
    # materializes: account them against the pool as OBSERVED usage
    # (note_usage, not admission) so the per-query peak reflects the
    # live set -- and shrinks by the donated bytes when donation
    # aliases a dead input into the region's output. The finally
    # balances whatever is still accounted (the caller's bulk free
    # only covers staged scans).
    inter_bytes: Dict[int, int] = {}
    try:
        for reg in rplan.regions:
            rbatches = [staged_by_id[id(i.node)] if i.kind == "scan"
                        else outputs[i.region] for i in reg.inputs]
            plan, jfn, call_lock = _compile_any(reg.root, None,
                                                default_join_capacity, 1,
                                                use_cache)
            rfp = plan_fingerprint(reg.root)
            if audit_on:
                report = audit_staged_query(
                    plan, rbatches, mesh=None, query_id=query_id,
                    session=session, collector=collector, stats=stats,
                    memory_pool=memory_pool, plan_fp=rfp)
                if report and report.get("peak_bytes_estimate"):
                    fusion_memory().note_footprint(
                        rfp, report["peak_bytes_estimate"])
                    # per-region K005 estimate: region estimates fold by
                    # max into ONE query-level footprint record (the pool
                    # measures one per-query peak, and intermediates drop
                    # past their last consumer, so max is the honest
                    # planned-peak bound)
                    _acc_record("footprint", "MemoryPool", unit="bytes",
                                est=float(report["peak_bytes_estimate"]))
            # -- proven-safe buffer donation (exec/donation.py) ----------
            # engine half of the K006 proof: candidates are region-kind
            # inputs whose LAST consumer is this region, fed exactly once,
            # under an overflow-incapable root (the rerun ladder re-reads
            # inputs after overflow -- donated buffers would be freed)
            prep = None
            donated_nbytes = 0
            if donate_on and overflow_incapable(reg.root):
                region_uses: Dict[int, int] = {}
                for i in reg.inputs:
                    if i.kind == "region":
                        region_uses[i.region] = \
                            region_uses.get(i.region, 0) + 1
                dead_idx: list = []
                pos = 0
                for i, b in zip(reg.inputs, rbatches):
                    nleaves = len(jax.tree_util.tree_leaves(b))
                    if (i.kind == "region" and consumers[i.region] == 1
                            and region_uses[i.region] == 1):
                        dead_idx.extend(range(pos, pos + nleaves))
                    pos += nleaves
                if dead_idx:
                    try:
                        with (call_lock if call_lock is not None
                              else contextlib.nullcontext()):
                            prep = prepare_donation(rfp, plan.fn,
                                                    rbatches, dead_idx)
                    except Exception as e:
                        # fallback, never failure: nothing was consumed
                        # yet, the undonated dispatch below is untouched
                        prep = None
                        note_fallback()
                        stats.add("donation_fallbacks", 1)
                        if collector is not None:
                            collector.note("donation_fallbacks", 1)
                        record_event("donation_fallback",
                                     query_id=query_id, region=reg.tag,
                                     reason=str(e)[:200])
            if prep is not None:
                from .datapath import now_us as _now_us
                region = {"region": reg.tag}
                t_don0 = _now_us()
                with stage("dispatch", region):
                    with (call_lock if call_lock is not None
                          else contextlib.nullcontext()):
                        out, overflow = prep.dispatch(rbatches)
                with stage("device_wait", region):
                    jax.block_until_ready(out)
                    dev_s = (_now_us() - t_don0) / 1e6
                    # no join is overflow-incapable: nothing expands
                    oflags, _, _ = _read_status(overflow, plan, None)
                if prog is not None:
                    prog.advance()
                if oflags:  # unreachable: whitelist admits no overflow op
                    raise RuntimeError(
                        f"donated region {reg.tag} set overflow flags "
                        f"{oflags}; the overflow-incapable whitelist is "
                        f"wrong -- this is a bug, not a capacity problem")
                donated_nbytes = prep.donated_bytes
                note_donation(donated_nbytes, len(prep.donate_idx))
                stats.add("donations", 1)
                stats.add("donated_bytes", donated_nbytes)
                if collector is not None:
                    collector.note("donations", 1)
                    collector.note("donated_bytes", donated_nbytes)
                record_event("buffer_donation", query_id=query_id,
                             region=reg.tag, bytes=donated_nbytes,
                             leaves=len(prep.donate_idx))
                dispatch_fn = None
            else:
                out, dev_s, dispatch_fn, dlock, ran_caps, scale, _ = \
                    _dispatch_ladder(
                        reg.root, plan, jfn, call_lock, rbatches, None,
                        default_join_capacity, use_cache, rfp, stats,
                        adaptive_off, refine, prog, reg.tag, hbm_budget)
            if cost_on and collector is not None and dispatch_fn is not None:
                # per-region XLA cost analysis: the fused path's FLOPs /
                # bytes-accessed split, summed region by region so EXPLAIN
                # ANALYZE keeps its compile-stage roofline inputs under
                # fusion=0 / refusal / demotion
                cost = _stage_cost(dispatch_fn, rbatches,
                                   (rfp, ran_caps, scale), dlock)
                if cost:
                    collector.bump_stage("compile", **cost)
            outputs[reg.index] = out
            if memory_pool is not None and consumers.get(reg.index, 0) > 0:
                # intermediate output: new HBM is its footprint minus the
                # donated bytes its program aliased in place
                held = max(batch_bytes(out) - donated_nbytes, 0)
                if held:
                    memory_pool.note_usage(query_id, held)
                    inter_bytes[reg.index] = held
            # region-boundary estimate-vs-actual: the region root's planner
            # estimate against the rows its program actually emitted (join
            # build sides that partition into their own region are
            # attributed here; the dispatch already synced, so reading the
            # active mask costs one small host transfer, not a block)
            _acc_record(f"region[{reg.tag}]:{type(reg.root).__name__}",
                        type(reg.root).__name__, unit="rows",
                        est=_acc_est(reg.root, sf),
                        actual=int(np.asarray(out.active).sum()))
            for i in reg.inputs:  # drop intermediates past their last use
                if i.kind == "region":
                    consumers[i.region] -= 1
                    if consumers[i.region] == 0:
                        outputs.pop(i.region, None)
                        freed = inter_bytes.pop(i.region, 0)
                        if memory_pool is not None and freed:
                            memory_pool.free(query_id, freed)
            total_device_s += dev_s
            # incremental compile drain: what accumulated since the last
            # region dispatched is this region's trace+compile share
            cu = collector.take_compile_us() if collector is not None else 0
            total_compile_us += cu
            dev_us = max(int(dev_s * 1e6) - cu, 0)
            stats.add(f"fusion_region_{reg.tag}_device_us", dev_us)
    finally:
        # no residue may leak into the pool's per-query ledger: the
        # caller's finally frees exactly the staged-scan reservation
        if memory_pool is not None:
            leftover = sum(inter_bytes.values())
            if leftover:
                memory_pool.free(query_id, leftover)
    # materialized-baseline sample for the demotion comparator: the
    # whole span just ran with materialized boundaries, so its total
    # device time is the unfused side of the span's fused-vs-unfused
    # comparison (keyed by the fingerprint the span fuses to)
    fusion_memory().note_unfused(
        plan_fp_root,
        max(int(total_device_s * 1e6) - total_compile_us, 0))
    return (outputs[rplan.regions[-1].index], total_device_s,
            total_compile_us)


def _scan_key(index: int, node: N.PlanNode) -> str:
    """Structural operator key for the index-th scan leaf (DFS order).
    Structural (not node-id) keys survive plan-cache canonicalization
    AND line up across workers running the same fragment, so per-node
    rows merge cross-worker by plain key equality. The label is part of
    the key so a leaf fragment's TableScan and a consumer fragment's
    RemoteSource at the same index never fold together."""
    return f"scan[{index}]:{_scan_label(node)}"


def _scan_label(node: N.PlanNode) -> str:
    if isinstance(node, N.TableScanNode):
        return f"TableScan[{node.connector}.{node.table}]"
    if isinstance(node, N.RemoteSourceNode):
        return "RemoteSource"
    return type(node).__name__


# cost_analysis memo: (plan fingerprint+scales, batch shapes) ->
# {flops, bytes_accessed}. lower() re-traces the program, so the
# analysis is paid once per distinct (program, shape) and amortized
# across repeats; LRU-evicted so a long-lived server keeps caching.
_COST_MEMO: "collections.OrderedDict[tuple, Optional[dict]]" = \
    collections.OrderedDict()
_COST_MEMO_MAX = 256
_COST_MEMO_LOCK = threading.Lock()


def _stage_cost(dispatch_fn, batches, fingerprint,
                call_lock=None) -> Optional[dict]:
    import contextlib
    key = (fingerprint,
           tuple((b.capacity, b.num_columns) for b in batches))
    with _COST_MEMO_LOCK:
        if key in _COST_MEMO:
            _COST_MEMO.move_to_end(key)
            return _COST_MEMO[key]
    try:
        # lower() re-traces: hold the cached entry's dispatch lock so a
        # concurrent first dispatch's trace-time closure state can't tear
        with call_lock or contextlib.nullcontext():
            lowered = dispatch_fn.lower(tuple(batches))
        analysis = lowered.cost_analysis()
        cost = {"flops": max(float(analysis.get("flops", 0.0)), 0.0),
                "bytes_accessed":
                    max(float(analysis.get("bytes accessed", 0.0)), 0.0)}
    except Exception:  # noqa: BLE001 - cost analysis is best-effort
        cost = None
    with _COST_MEMO_LOCK:
        _COST_MEMO[key] = cost
        while len(_COST_MEMO) > _COST_MEMO_MAX:
            _COST_MEMO.popitem(last=False)
    return cost


def _result_bytes(res: "QueryResult") -> int:
    total = 0
    for vals, nulls in zip(res.columns, res.nulls):
        total += getattr(vals, "nbytes", 0) + getattr(nulls, "nbytes", 0)
    return total


def _finalize_query_stats(collector: StatsCollector, res: "QueryResult",
                          t0: float, peak_reserved_bytes: int,
                          root: Optional[N.PlanNode],
                          dp=None, acc=None, sf: float = 0.01) -> None:
    """Close out the structured stats for one run_query invocation (its
    spans go to the tracer when the collector's owner is done with it:
    ``stats.joining``). `peak_reserved_bytes` is
    the pool high-water mark the caller already drained. `dp` is the
    invocation's datapath ledger: its hop map rides QueryStats.datapath
    (stitching worker slices through the task-status path) and the
    bounded per-query registry flight dumps embed from."""
    qs = collector.stats
    if dp is not None:
        from .datapath import merge_hop_maps, note_query
        hops = dp.snapshot_hops()
        if hops:
            qs.datapath = merge_hop_maps(qs.datapath, hops)
            note_query(collector.query_id, hops)
    # drain any compile time not yet attributed (the streaming/spill
    # early-return paths compile inside their execute stage and never
    # reach the main path's drain); same clamp + anchor as there
    leftover_us = collector.take_compile_us()
    exec_stage = qs.stages.get("execute")
    if exec_stage is not None and exec_stage.wall_us:
        leftover_us = min(leftover_us, exec_stage.wall_us)
    if leftover_us:
        anchor = collector.stage_span_start("execute") or t0
        collector.record_stage("compile", anchor,
                               anchor + leftover_us / 1e6,
                               compile_us=leftover_us)
    qs.wall_us = int((time.time() - t0) * 1e6)
    qs.output_rows = res.row_count
    qs.output_bytes = _result_bytes(res)
    staging = qs.stages.get("staging")
    peak = max(staging.bytes if staging else 0, peak_reserved_bytes)
    qs.peak_memory_bytes = max(qs.peak_memory_bytes, peak)
    if root is not None:
        collector.operator("output", type(root).__name__,
                           output_rows=res.row_count,
                           output_bytes=qs.output_bytes,
                           wall_us=qs.stage_us("fetch"))
    # estimate-vs-actual close-out (exec/accuracy.py): the root's
    # cardinality record, the footprint record's measured side (the
    # pool peak the caller drained), then the whole ledger rides
    # QueryStats.accuracy (stitching worker slices through the
    # task-status path) and folds into the process registry +
    # q-error histogram -- complete records only, at this one seam
    if acc is not None:
        from .accuracy import est_rows_of as _est_of
        from .accuracy import finalize_query as _acc_finalize
        from .accuracy import merge_record_maps as _acc_merge
        if root is not None:
            acc.record("output", node_type=type(root).__name__,
                       unit="rows", est=_est_of(root, sf),
                       actual=float(res.row_count))
        recs = acc.snapshot_records()
        if "footprint" in recs and qs.peak_memory_bytes:
            acc.record("footprint", node_type="MemoryPool",
                       unit="bytes",
                       actual=float(qs.peak_memory_bytes))
            recs = acc.snapshot_records()
        if recs:
            qs.accuracy = _acc_merge(qs.accuracy, recs)
            _acc_finalize(collector.query_id, recs)
    res.query_stats = qs


def _compile_any(root: N.PlanNode, mesh, default_join_capacity: int,
                 slot_scale: int, use_cache: bool):
    """(CompiledPlan, jitted-fn-or-None, lock-or-None) via the
    compiled-plan cache when node-id-keyed kwargs aren't in play."""
    if use_cache:
        from .plan_cache import cached_compile
        return cached_compile(root, mesh, default_join_capacity,
                              exchange_slot_scale=slot_scale)
    return (compile_plan(root, mesh, default_join_capacity,
                         exchange_slot_scale=slot_scale), None, None)


def _count_result(rows: int, name: str = "rows") -> QueryResult:
    return QueryResult([np.array([rows], dtype=np.int64)],
                       [np.array([False])], [name], 1,
                       types=[T.BIGINT])


def _run_write_root(node: N.PlanNode, **kw) -> QueryResult:
    """Execute a DdlNode / TableFinishNode / TableWriterNode root. The
    host-side sink, from the inner SELECT's result in hand to the table
    published, is the statement's ``write`` stage; the inner SELECT's
    own stages are its siblings, not its children.

    Local + mesh tiers run the whole write under one TableFinish
    (staged handle, atomic publish). On the HTTP tier the fragmenter
    splits writer and finish: each worker task's TableWriterNode
    publishes its own chunk (the presto-memory per-node append
    semantics) and the finish fragment just sums counts."""
    from ..connectors import catalog

    if isinstance(node, N.DdlNode):
        assert node.op == "drop_table", node.op
        catalog(node.connector).drop_table(node.table,
                                           if_exists=node.if_exists)
        res = QueryResult([np.array([True])], [np.array([False])],
                          ["result"], 1, types=[T.BOOLEAN])
        return res

    if isinstance(node, N.TableRewriteNode):
        # DELETE/UPDATE: compute new contents + `changed` flags on
        # device, swap the table host-side, report affected rows. The
        # whole read-compute-swap holds the table's writer lock so a
        # concurrent committed INSERT cannot vanish under the swap.
        mod = catalog(node.connector)
        with mod.write_lock(node.table):
            res = run_query(N.OutputNode(node.source, []), **kw)
            ncols = len(res.columns) - 1
            changed = np.asarray(res.columns[-1]).astype(bool) & \
                ~np.asarray(res.nulls[-1], dtype=bool)
            affected = int(changed.sum())
            with stage("write"):
                if node.kind == "delete":
                    keep = ~changed
                    cols = [c[keep] for c in res.columns[:ncols]]
                    nulls = [n[keep] for n in res.nulls[:ncols]]
                else:
                    cols = list(res.columns[:ncols])
                    nulls = list(res.nulls[:ncols])
                mod.replace_table(node.table, cols, nulls)
        return _count_result(affected)

    if isinstance(node, N.TableWriterNode):
        mod = catalog(node.connector)
        h = mod.begin_insert(node.table)
        try:
            return _count_result(_write_pages(mod, h, node, kw))
        except BaseException:
            mod.abort_insert(h)
            raise

    finish: N.TableFinishNode = node
    mod = catalog(finish.connector)
    src = finish.source
    # single-process execution collapses the writer/finish exchange seam
    while isinstance(src, N.ExchangeNode):
        src = src.source
    if isinstance(src, N.TableWriterNode):
        # single-process (local/mesh) write: stage + atomic publish
        created = {}
        if finish.create:
            created = {"create_columns": finish.create_columns,
                       "create_types": finish.create_types}
            if finish.create_properties:  # only a catalog that has any
                created["properties"] = finish.create_properties
        h = mod.begin_insert(finish.table, **created)
        try:
            return _count_result(_write_pages(mod, h, src, kw))
        except BaseException:
            mod.abort_insert(h)
            raise
    # distributed finish: the source plan delivers per-task counts
    res = run_query(N.OutputNode(finish.source, ["rows"]), **kw)
    total = int(sum(int(v) for v, nl in zip(res.columns[0], res.nulls[0])
                    if not nl))
    return _count_result(total)


# A page of a paged write may plan this share of the device's memory:
# the program holds the page as its input and again as its output, XLA
# its temporaries, and the next page is staged before the last is freed.
_WRITE_PAGE_SHARE = 8


def _write_page_ranges(select: N.OutputNode, kw) -> List[Optional[dict]]:
    """The pages a writer's SELECT is run in, each the `scan_ranges` and
    `capacity_hints` of one `run_query`: `[None]`, one page that is the
    whole SELECT, wherever its source fits beside the program or cannot
    be cut; else row ranges of its one scan, all of one capacity so that
    every page runs the same program. What can be cut is a scan under
    projections and filters: a row's output depends on that row alone.
    What fits is read from what the engine can observe: the scan's
    planned bytes against `hbm_budget_bytes` where the caller or the
    session set one, else against the device's own limit."""
    scan = select.source
    while isinstance(scan, (N.ProjectNode, N.FilterNode)):
        scan = scan.source
    session = kw.get("session")
    budget = kw.get("hbm_budget_bytes") or \
        (session.get("hbm_budget_bytes") if session is not None else None)
    if not budget:
        budget = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    if not isinstance(scan, N.TableScanNode) or not budget \
            or placement_mesh(select, kw.get("mesh")) is not None \
            or kw.get("scan_ranges") \
            or kw.get("split_rows") is not None:
        return [None]
    from ..connectors import catalog
    rows = catalog(scan.connector).table_row_count(scan.table, kw["sf"])
    planned = _planned_scan_bytes(scan, kw["sf"], None, 8, None, {})
    page_bytes = int(budget) // _WRITE_PAGE_SHARE
    if planned <= page_bytes or rows <= 8:
        return [None]
    per_row = -(-planned // max(rows, 1))
    page_rows = max(page_bytes // per_row // 8 * 8, 8)
    return [{"scan_ranges": {scan.id: (at, min(page_rows, rows - at))},
             "capacity_hints": {scan.id: page_rows}}
            for at in range(0, rows, page_rows)]


def _write_pages(mod, handle: str, writer: N.TableWriterNode, kw) -> int:
    """The one write path: the writer's SELECT, page by page, into the
    sink's staged handle, then the publish. A table that fits is the
    case of one page. Each page's host side is a ``write`` stage with
    a ``write.page`` span under it, the last one's with the publish
    (``write.publish``) too; the SELECT's own stages are their siblings.
    A page is let go before the next is computed: a sink that writes
    as it goes (a lake file) leaves the host one page at a time.
    Nothing is visible to a reader before ``finish_insert``; the
    caller aborts the handle if any page raises."""
    select = N.OutputNode(writer.source, writer.column_names)
    rows = nbytes = 0
    pages = _write_page_ranges(select, kw)
    for k, page in enumerate(pages):
        res = run_query(select, **{**kw, **(page or {})})
        size = _result_bytes(res)
        with stage("write"):
            with span("write.page", {"page": k, "rows": res.row_count,
                                     "bytes": size}):
                mod.append(handle, res.columns, res.nulls)
            if k == len(pages) - 1:
                with span("write.publish"):
                    published = mod.finish_insert(handle)
        rows += res.row_count
        nbytes += size
        del res
    note("write_pages", len(pages))
    note("write_rows", rows)
    # what the sink holds of the table where it can say (a lake file's
    # size), else the bytes of the pages handed to it
    stored = getattr(mod, "stored_bytes", None)
    note("write_bytes", stored(writer.table) if stored else nbytes)
    return published


def _planned_scan_bytes(node: N.PlanNode, sf: float,
                        capacity_hint: Optional[int], pad_multiple: int,
                        scan_range: Optional[Tuple[int, int]],
                        remote_sources: Dict[str, Batch]) -> int:
    """Planned HBM footprint of a scan input WITHOUT materializing it."""
    if isinstance(node, N.RemoteSourceNode):
        b = remote_sources.get(node.id)
        if b is None:
            return 0
        from .memory import batch_bytes
        return batch_bytes(b)
    if isinstance(node, N.ValuesNode):
        rows = len(node.rows)
        types = node.types
    else:
        from ..connectors import catalog
        conn = catalog(node.connector)
        rows = scan_range[1] if scan_range is not None else \
            conn.table_row_count(node.table, sf)
        types = node.column_types
    cap = capacity_hint or max(-(-rows // pad_multiple) * pad_multiple,
                               pad_multiple)
    per_row = 1  # active mask
    for ty in types:
        if ty.is_string:
            per_row += ty.max_length if ty.max_length < 1 << 20 else 64
            per_row += 5  # lengths + nulls
        else:
            per_row += ty.to_dtype().itemsize + 1
    return cap * per_row


def _batch_to_result(out: Batch, root: N.PlanNode) -> QueryResult:
    act = np.asarray(out.active)
    idx = np.nonzero(act)[0]
    live = len(idx)
    if live and idx[-1] == live - 1:
        # the live rows lead (a scan's page, a sorted or compacted
        # result): a slice, where picking them would copy each column
        idx = slice(0, live)
    cols, nulls, types = [], [], []
    for c in range(out.num_columns):
        v, n = to_numpy(out.column(c))
        ty = out.column(c).type
        v = v[idx]
        if v.dtype != object and v.dtype.kind in "iu" and ty.is_fixed_width:
            # narrow-width lanes widen back to the logical dtype at the
            # result boundary (device->host already moved narrow bytes;
            # clients/serde see the declared type's width)
            ld = np.dtype(ty.to_dtype())
            if ld.kind in "iu" and v.dtype != ld:
                v = v.astype(ld)
        cols.append(v)
        nulls.append(n[idx])
        types.append(ty)
    names = root.names if isinstance(root, N.OutputNode) else \
        [f"col{i}" for i in range(out.num_columns)]
    return QueryResult(cols, nulls, names, live, types=types)
