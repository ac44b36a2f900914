"""Plan nodes: the worker-visible plan vocabulary.

Reference surface: presto-spi/.../spi/plan/ (67 public plan-node files --
TableScanNode, FilterNode, ProjectNode, AggregationNode, JoinNode,
SemiJoinNode, SortNode, TopNNode, LimitNode, DistinctLimitNode,
ExchangeNode, ValuesNode, OutputNode...) which every worker deserializes
from PlanFragment JSON (the C++ worker mirrors them in generated
presto_protocol_core structs).

Differences from the reference, by design:
  * Symbols are already resolved to channel indices (the reference ships
    VariableReferenceExpressions + layout maps; resolving them is
    coordinator-side bookkeeping that a worker redoes -- here the
    protocol adapter will do it once at ingest).
  * Aggregations carry explicit step (PARTIAL/FINAL/SINGLE) like the
    reference's AggregationNode.Step.
  * TableScanNode names a connector table + column list; the split is
    supplied at execution time (ConnectorSplit analog).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple, Union

from .. import types as T
from ..expr import ir as E
from ..ops.aggregation import AggSpec

__all__ = ["PlanNode", "TableScanNode", "ValuesNode", "FilterNode",
           "ProjectNode", "AggregationNode", "JoinNode", "SemiJoinNode",
           "SortNode", "TopNNode", "LimitNode", "DistinctNode",
           "ExchangeNode", "OutputNode", "TableWriterNode",
           "TableFinishNode", "TableRewriteNode", "DdlNode",
           "to_json", "from_json"]


_next_id = [0]


def _nid() -> str:
    _next_id[0] += 1
    return str(_next_id[0])


@dataclasses.dataclass
class PlanNode:
    id: str = dataclasses.field(default_factory=_nid, kw_only=True)

    @property
    def sources(self) -> Tuple["PlanNode", ...]:
        return ()

    def output_types(self) -> List[T.Type]:
        raise NotImplementedError


@dataclasses.dataclass
class TableScanNode(PlanNode):
    connector: str
    table: str
    columns: List[str]
    column_types: List[T.Type]
    # connector predicate pushdown (PushdownSubfields / the selective
    # ORC/parquet reader seam): (column, lo, hi) range the connector may
    # use to prune row groups/pages. PRUNING ONLY -- the Filter above
    # still applies exactly; None bound = unbounded on that side
    pushdown: object = None
    # narrow-width execution (plan/widths.py): per-column physical lane
    # dtype names ("int16", ...; None = logical width), proven safe by
    # connector range statistics. Staging honors these; every compute
    # site widens before arithmetic, so results stay bit-exact
    physical_dtypes: object = None
    # the schema the statement named (`tpch.sf10.lineitem`), where it
    # named one: checked against the scale the server serves when the
    # plan is prepared (connectors/tpch.check_schema)
    schema: Optional[str] = None

    def output_types(self):
        return list(self.column_types)


@dataclasses.dataclass
class RemoteSourceNode(PlanNode):
    """Input fed from upstream fragments' output buffers
    (RemoteSourceNode analog): within a slice the exec layer wires it to
    collectives; across workers the task body names upstream (worker,
    task) pairs and the batch arrives via the HTTP SerializedPage pull
    (server/http_exchange.py)."""
    types: List[T.Type]
    fragment_id: int = -1

    def output_types(self):
        return list(self.types)


@dataclasses.dataclass
class ValuesNode(PlanNode):
    types: List[T.Type]
    rows: List[List[object]]

    def output_types(self):
        return list(self.types)


@dataclasses.dataclass
class FilterNode(PlanNode):
    source: PlanNode
    predicate: E.RowExpression

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return self.source.output_types()


@dataclasses.dataclass
class ProjectNode(PlanNode):
    source: PlanNode
    expressions: List[E.RowExpression]

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return [e.type for e in self.expressions]


@dataclasses.dataclass
class AggregationNode(PlanNode):
    source: PlanNode
    group_channels: List[int]
    aggregates: List[AggSpec]
    step: str = "SINGLE"  # SINGLE | PARTIAL | FINAL
    max_groups: int = 1 << 16

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        src = self.source.output_types()
        if self.step == "INTERMEDIATE":
            # merge of state tables re-emits the SAME state layout (the
            # source is already keys + states; input_channel indexes the
            # raw-row world and must not be consulted here)
            return list(src)
        out = [src[c] for c in self.group_channels]
        if self.step in ("SINGLE", "FINAL"):
            # finalized steps emit exactly one column per aggregate
            # (the reference's evaluateFinal contract); only PARTIAL
            # ships raw state columns over exchanges
            out.extend(a.output_type for a in self.aggregates)
            return out
        from ..ops.aggregation import (_PAIR_MOMENT_AGGS, _sum_type,
                                       hll_state_type)
        for a in self.aggregates:
            c = a.canonical
            if c == "approx_distinct":
                out.append(hll_state_type())
            elif c == "avg":  # (sum, count) state pair
                out.extend([_sum_type(src[a.input_channel]), T.BIGINT])
            elif c in ("var_samp", "var_pop", "stddev_samp", "stddev_pop"):
                # raw (count, sum, sumsq) moments
                out.extend([T.BIGINT, T.DOUBLE, T.DOUBLE])
            elif c in _PAIR_MOMENT_AGGS:
                # (n, sy, sx, syy, sxx, sxy) moments
                out.extend([T.BIGINT] + [T.DOUBLE] * 5)
            elif c == "geometric_mean":
                out.extend([T.BIGINT, T.DOUBLE])
            elif c in ("min_by", "max_by"):
                out.extend([a.output_type, a.second_type or T.BIGINT])
            else:
                out.append(a.output_type)
        return out


@dataclasses.dataclass
class JoinNode(PlanNode):
    left: PlanNode
    right: PlanNode
    left_keys: List[int]
    right_keys: List[int]
    join_type: str = "inner"          # inner | left | right | full
    distribution: str = "partitioned"  # partitioned | broadcast (REPLICATED)
    right_output_channels: Optional[List[int]] = None
    out_capacity: Optional[int] = None

    @property
    def sources(self):
        return (self.left, self.right)

    def output_types(self):
        lt = self.left.output_types()
        rt = self.right.output_types()
        chans = self.right_output_channels
        if chans is None:
            chans = list(range(len(rt)))
        return lt + [rt[c] for c in chans]


@dataclasses.dataclass
class SemiJoinNode(PlanNode):
    source: PlanNode
    filtering_source: PlanNode
    source_key: Union[int, List[int]]
    filtering_key: Union[int, List[int]]
    negate: bool = False  # True => anti join semantics when filtered on
    null_keys_match: bool = False  # True: NULL==NULL (set-op semantics)

    @property
    def sources(self):
        return (self.source, self.filtering_source)

    def output_types(self):
        return self.source.output_types() + [T.BOOLEAN]


@dataclasses.dataclass
class SortNode(PlanNode):
    source: PlanNode
    keys: List[Tuple[int, bool, bool]]  # (channel, descending, nulls_last)

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return self.source.output_types()


@dataclasses.dataclass
class TopNNode(PlanNode):
    source: PlanNode
    keys: List[Tuple[int, bool, bool]]
    count: int

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return self.source.output_types()


@dataclasses.dataclass
class LimitNode(PlanNode):
    source: PlanNode
    count: int

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return self.source.output_types()


@dataclasses.dataclass
class DistinctNode(PlanNode):
    """DISTINCT over all channels (MarkDistinct/DistinctLimit analog)."""
    source: PlanNode
    key_channels: Optional[List[int]] = None
    max_groups: int = 1 << 16

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return self.source.output_types()


@dataclasses.dataclass
class UnionNode(PlanNode):
    """UNION ALL (UnionNode analog; set-distinct UNION is Union+Distinct,
    exactly how the reference plans it via SetFlatteningOptimizer)."""
    inputs: List[PlanNode] = dataclasses.field(default_factory=list)

    @property
    def sources(self):
        return tuple(self.inputs)

    def output_types(self):
        return self.inputs[0].output_types()


@dataclasses.dataclass
class SampleNode(PlanNode):
    """BERNOULLI sampling (SampleNode analog): keep each row with
    probability `ratio`, decided by a deterministic per-row hash (the
    reference samples with a per-split RNG; hashing keeps splits
    reproducible)."""
    source: PlanNode
    ratio: float = 1.0

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return self.source.output_types()


@dataclasses.dataclass
class AssignUniqueIdNode(PlanNode):
    """Append a unique BIGINT per row (AssignUniqueId analog; the
    reference salts with the task id -- here the worker index salts the
    high bits under shard_map)."""
    source: PlanNode

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return self.source.output_types() + [T.BIGINT]


@dataclasses.dataclass
class MarkDistinctNode(PlanNode):
    """Append a BOOLEAN 'is first occurrence of these keys' column
    (MarkDistinctOperator analog, the basis of mixed distinct/non-
    distinct aggregations)."""
    source: PlanNode
    key_channels: List[int] = dataclasses.field(default_factory=list)
    max_groups: int = 1 << 16

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return self.source.output_types() + [T.BOOLEAN]


@dataclasses.dataclass
class WindowNode(PlanNode):
    """Window functions over partitions (WindowNode/WindowOperator
    analog). `functions` entries: (name, input_channel|None, type_sig,
    frame, ntile_buckets)."""
    source: PlanNode
    partition_channels: List[int] = dataclasses.field(default_factory=list)
    order_keys: List[Tuple[int, bool, bool]] = dataclasses.field(default_factory=list)
    functions: List[Tuple] = dataclasses.field(default_factory=list)

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        out = list(self.source.output_types())
        for name, _ch, ty, _frame, _k in self.functions:
            out.append(T.parse_type(ty) if isinstance(ty, str) else ty)
        return out


@dataclasses.dataclass
class RowNumberNode(PlanNode):
    """Append row_number() over partitions, optionally keeping only the
    first max_rows per partition (RowNumberOperator /
    TopNRowNumberOperator analog). `max_partitions` is accepted for
    protocol parity with the reference's hash-table sizing hint; the
    sort-based implementation needs no partition cap and ignores it."""
    source: PlanNode
    partition_channels: List[int] = dataclasses.field(default_factory=list)
    order_keys: List[Tuple[int, bool, bool]] = dataclasses.field(default_factory=list)
    max_rows_per_partition: Optional[int] = None
    max_partitions: int = 1 << 16

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return self.source.output_types() + [T.BIGINT]


@dataclasses.dataclass
class UnnestNode(PlanNode):
    """UNNEST(array) [WITH ORDINALITY] (operator/unnest/ analog). Output:
    non-array source columns, then the element column (+ ordinality)."""
    source: PlanNode
    array_channel: int
    out_capacity: Optional[int] = None
    with_ordinality: bool = False

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        src = self.source.output_types()
        arr = src[self.array_channel]
        out = [t for i, t in enumerate(src) if i != self.array_channel]
        if arr.base == "map":
            out.extend([arr.key_type, arr.value_type])
        else:
            out.append(arr.element_type)
        if self.with_ordinality:
            out.append(T.BIGINT)
        return out


@dataclasses.dataclass
class GroupIdNode(PlanNode):
    """Grouping-set row expansion (spi/plan/GroupIdNode.java analog):
    each input row is emitted once per grouping set; key channels NOT in
    that set are replaced with typed NULLs, and a BIGINT group-id column
    is appended (the set's index). A single downstream aggregation over
    (key channels ++ group id) then computes every grouping set in ONE
    pass -- replacing the k+1-pass UNION rewrite. Output capacity is
    source capacity x len(grouping_sets) (static, XLA-friendly concat)."""
    source: PlanNode
    grouping_sets: List[List[int]] = dataclasses.field(default_factory=list)

    @property
    def sources(self):
        return (self.source,)

    @property
    def key_channels(self) -> List[int]:
        seen: List[int] = []
        for s in self.grouping_sets:
            for c in s:
                if c not in seen:
                    seen.append(c)
        return seen

    def output_types(self):
        return self.source.output_types() + [T.BIGINT]


@dataclasses.dataclass
class DdlNode(PlanNode):
    """Coordinator-side data definition (the DataDefinitionTask family,
    execution/CreateTableTask etc.): executes host-side against
    connector metadata, no device work. `op`: drop_table (more arrive
    with the DDL surface)."""
    op: str
    connector: str
    table: str
    if_exists: bool = False

    def output_types(self):
        return [T.BOOLEAN]


@dataclasses.dataclass
class TableRewriteNode(PlanNode):
    """DELETE/UPDATE as a table rewrite (spi/plan DeleteNode/UpdateNode
    analog for in-memory storage): `source` yields the table's columns
    plus a trailing BOOLEAN `changed` column; delete drops changed rows,
    update keeps every row (with changed rows already projected to their
    new values). Executes host-side like the other write roots; output
    is one BIGINT -- affected rows."""
    source: PlanNode
    connector: str
    table: str
    kind: str = "delete"  # delete | update

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return [T.BIGINT]


@dataclasses.dataclass
class TableWriterNode(PlanNode):
    """Write source rows into a connector table
    (spi/plan/TableWriterNode + operator/TableWriterOperator.java:76
    analog). Executes host-side AFTER the source program runs on
    device (writes are a host effect; the device computes, one DMA-out
    feeds the sink). Output: one BIGINT row -- rows this task wrote."""
    source: PlanNode
    connector: str
    table: str
    column_names: List[str] = dataclasses.field(default_factory=list)
    insert_handle: Optional[str] = None  # runtime: shared staging handle

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return [T.BIGINT]


@dataclasses.dataclass
class TableFinishNode(PlanNode):
    """Commit point (spi/plan/TableFinishNode analog): sums the
    per-task written-row counts and atomically publishes the staged
    insert (ConnectorMetadata.finishInsert / finishCreateTable).
    `create_*` carry CTAS table metadata (`create_properties` the
    WITH (...) of the statement as its catalog checked them)."""
    source: PlanNode
    connector: str
    table: str
    create: bool = False
    create_columns: List[str] = dataclasses.field(default_factory=list)
    create_types: List[T.Type] = dataclasses.field(default_factory=list)
    create_properties: Dict[str, str] = dataclasses.field(
        default_factory=dict)

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return [T.BIGINT]


@dataclasses.dataclass
class ExchangeNode(PlanNode):
    """scope REMOTE => stage boundary (collective over the mesh);
    scope LOCAL => no-op in this engine (XLA fuses local pipelines).
    kind: REPARTITION (hash by partition_channels), REPLICATE
    (broadcast), GATHER (to single/replicated), MERGE (order-preserving
    exchange of locally sorted inputs by `sort_keys` -- the
    MergeOperator.java:45 analog; on the mesh it lowers to a sampled
    range repartition + local sort so the globally sorted result stays
    DISTRIBUTED, on the HTTP tier consumers k-way merge sorted upstream
    streams)."""
    source: PlanNode
    kind: str = "REPARTITION"
    scope: str = "REMOTE"
    partition_channels: List[int] = dataclasses.field(default_factory=list)
    slot_capacity: Optional[int] = None
    # (channel, descending, nulls_last) triples when kind == "MERGE"
    sort_keys: Optional[List[Tuple[int, bool, bool]]] = None

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return self.source.output_types()


@dataclasses.dataclass
class OutputNode(PlanNode):
    source: PlanNode
    names: List[str]

    @property
    def sources(self):
        return (self.source,)

    def output_types(self):
        return self.source.output_types()


# ---------------------------------------------------------------------------
# JSON (PlanFragment wire shape analog)
# ---------------------------------------------------------------------------

def _agg_to_json(a: AggSpec) -> dict:
    out = {"name": a.name, "input": a.input_channel, "type": str(a.output_type)}
    if a.second_channel is not None:
        out["secondChannel"] = a.second_channel
        out["secondType"] = str(a.second_type) if a.second_type else None
    return out


def _agg_from_json(j: dict) -> AggSpec:
    st = j.get("secondType")
    return AggSpec(j["name"], j["input"], T.parse_type(j["type"]),
                   second_channel=j.get("secondChannel"),
                   second_type=T.parse_type(st) if st else None)


def to_json(n: PlanNode) -> dict:
    base = {"id": n.id}
    if isinstance(n, TableScanNode):
        j = {**base, "@type": "tablescan", "connector": n.connector,
             "table": n.table, "columns": n.columns,
             "columnTypes": [str(t) for t in n.column_types]}
        if n.pushdown is not None:
            j["pushdown"] = list(n.pushdown)
        if n.physical_dtypes is not None:
            j["physicalDtypes"] = list(n.physical_dtypes)
        if n.schema is not None:
            j["schema"] = n.schema
        return j
    if isinstance(n, RemoteSourceNode):
        return {**base, "@type": "remotesource",
                "types": [str(t) for t in n.types],
                "fragmentId": n.fragment_id}
    if isinstance(n, ValuesNode):
        return {**base, "@type": "values", "types": [str(t) for t in n.types],
                "rows": n.rows}
    if isinstance(n, FilterNode):
        return {**base, "@type": "filter", "source": to_json(n.source),
                "predicate": E.to_json(n.predicate)}
    if isinstance(n, ProjectNode):
        return {**base, "@type": "project", "source": to_json(n.source),
                "expressions": [E.to_json(e) for e in n.expressions]}
    if isinstance(n, AggregationNode):
        return {**base, "@type": "aggregation", "source": to_json(n.source),
                "groupChannels": n.group_channels,
                "aggregates": [_agg_to_json(a) for a in n.aggregates],
                "step": n.step, "maxGroups": n.max_groups}
    if isinstance(n, JoinNode):
        return {**base, "@type": "join", "left": to_json(n.left),
                "right": to_json(n.right), "leftKeys": n.left_keys,
                "rightKeys": n.right_keys, "joinType": n.join_type,
                "distribution": n.distribution,
                "rightOutputChannels": n.right_output_channels,
                "outCapacity": n.out_capacity}
    if isinstance(n, SemiJoinNode):
        return {**base, "@type": "semijoin", "source": to_json(n.source),
                "filteringSource": to_json(n.filtering_source),
                "sourceKey": n.source_key, "filteringKey": n.filtering_key,
                "negate": n.negate, "nullKeysMatch": n.null_keys_match}
    if isinstance(n, SortNode):
        return {**base, "@type": "sort", "source": to_json(n.source),
                "keys": [list(k) for k in n.keys]}
    if isinstance(n, TopNNode):
        return {**base, "@type": "topn", "source": to_json(n.source),
                "keys": [list(k) for k in n.keys], "count": n.count}
    if isinstance(n, LimitNode):
        return {**base, "@type": "limit", "source": to_json(n.source),
                "count": n.count}
    if isinstance(n, DistinctNode):
        return {**base, "@type": "distinct", "source": to_json(n.source),
                "keyChannels": n.key_channels, "maxGroups": n.max_groups}
    if isinstance(n, UnionNode):
        return {**base, "@type": "union",
                "inputs": [to_json(s) for s in n.inputs]}
    if isinstance(n, SampleNode):
        return {**base, "@type": "sample", "source": to_json(n.source),
                "ratio": n.ratio}
    if isinstance(n, AssignUniqueIdNode):
        return {**base, "@type": "assignuniqueid", "source": to_json(n.source)}
    if isinstance(n, MarkDistinctNode):
        return {**base, "@type": "markdistinct", "source": to_json(n.source),
                "keyChannels": n.key_channels, "maxGroups": n.max_groups}
    if isinstance(n, WindowNode):
        return {**base, "@type": "window", "source": to_json(n.source),
                "partitionChannels": n.partition_channels,
                "orderKeys": [list(k) for k in n.order_keys],
                "functions": [[f[0], f[1], str(f[2]), f[3], f[4]]
                              for f in n.functions]}
    if isinstance(n, RowNumberNode):
        return {**base, "@type": "rownumber", "source": to_json(n.source),
                "partitionChannels": n.partition_channels,
                "orderKeys": [list(k) for k in n.order_keys],
                "maxRowsPerPartition": n.max_rows_per_partition,
                "maxPartitions": n.max_partitions}
    if isinstance(n, UnnestNode):
        return {**base, "@type": "unnest", "source": to_json(n.source),
                "arrayChannel": n.array_channel,
                "outCapacity": n.out_capacity,
                "withOrdinality": n.with_ordinality}
    if isinstance(n, GroupIdNode):
        return {**base, "@type": "groupid", "source": to_json(n.source),
                "groupingSets": [list(s) for s in n.grouping_sets]}
    if isinstance(n, ExchangeNode):
        return {**base, "@type": "exchange", "source": to_json(n.source),
                "kind": n.kind, "scope": n.scope,
                "partitionChannels": n.partition_channels,
                "slotCapacity": n.slot_capacity,
                "sortKeys": [list(k) for k in n.sort_keys]
                if n.sort_keys is not None else None}
    if isinstance(n, DdlNode):
        return {**base, "@type": "ddl", "op": n.op,
                "connector": n.connector, "table": n.table,
                "ifExists": n.if_exists}
    if isinstance(n, TableRewriteNode):
        return {**base, "@type": "tablerewrite", "source": to_json(n.source),
                "connector": n.connector, "table": n.table, "kind": n.kind}
    if isinstance(n, TableWriterNode):
        return {**base, "@type": "tablewriter", "source": to_json(n.source),
                "connector": n.connector, "table": n.table,
                "columnNames": n.column_names,
                "insertHandle": n.insert_handle}
    if isinstance(n, TableFinishNode):
        return {**base, "@type": "tablefinish", "source": to_json(n.source),
                "connector": n.connector, "table": n.table,
                "create": n.create, "createColumns": n.create_columns,
                "createTypes": [str(t) for t in n.create_types],
                "createProperties": n.create_properties}
    if isinstance(n, OutputNode):
        return {**base, "@type": "output", "source": to_json(n.source),
                "names": n.names}
    raise TypeError(type(n))


def from_json(j: dict) -> PlanNode:
    t = j["@type"]
    nid = j.get("id", None)
    kw = {"id": nid} if nid else {}
    if t == "tablescan":
        pd = j.get("pushdown")
        phys = j.get("physicalDtypes")
        return TableScanNode(j["connector"], j["table"], j["columns"],
                             [T.parse_type(s) for s in j["columnTypes"]],
                             pushdown=tuple(pd) if pd else None,
                             physical_dtypes=tuple(phys) if phys else None,
                             schema=j.get("schema"), **kw)
    if t == "remotesource":
        return RemoteSourceNode([T.parse_type(s) for s in j["types"]],
                                j["fragmentId"], **kw)
    if t == "values":
        return ValuesNode([T.parse_type(s) for s in j["types"]], j["rows"], **kw)
    if t == "filter":
        return FilterNode(from_json(j["source"]), E.from_json(j["predicate"]), **kw)
    if t == "project":
        return ProjectNode(from_json(j["source"]),
                           [E.from_json(e) for e in j["expressions"]], **kw)
    if t == "aggregation":
        return AggregationNode(from_json(j["source"]), j["groupChannels"],
                               [_agg_from_json(a) for a in j["aggregates"]],
                               j["step"], j["maxGroups"], **kw)
    if t == "join":
        return JoinNode(from_json(j["left"]), from_json(j["right"]),
                        j["leftKeys"], j["rightKeys"], j["joinType"],
                        j["distribution"], j["rightOutputChannels"],
                        j["outCapacity"], **kw)
    if t == "semijoin":
        return SemiJoinNode(from_json(j["source"]), from_json(j["filteringSource"]),
                            j["sourceKey"], j["filteringKey"], j["negate"],
                            j.get("nullKeysMatch", False), **kw)
    if t == "sort":
        return SortNode(from_json(j["source"]),
                        [tuple(k) for k in j["keys"]], **kw)
    if t == "topn":
        return TopNNode(from_json(j["source"]), [tuple(k) for k in j["keys"]],
                        j["count"], **kw)
    if t == "limit":
        return LimitNode(from_json(j["source"]), j["count"], **kw)
    if t == "distinct":
        return DistinctNode(from_json(j["source"]), j["keyChannels"],
                            j["maxGroups"], **kw)
    if t == "union":
        return UnionNode([from_json(s) for s in j["inputs"]], **kw)
    if t == "sample":
        return SampleNode(from_json(j["source"]), j["ratio"], **kw)
    if t == "assignuniqueid":
        return AssignUniqueIdNode(from_json(j["source"]), **kw)
    if t == "markdistinct":
        return MarkDistinctNode(from_json(j["source"]), j["keyChannels"],
                                j["maxGroups"], **kw)
    if t == "window":
        return WindowNode(from_json(j["source"]), j["partitionChannels"],
                          [tuple(k) for k in j["orderKeys"]],
                          [(f[0], f[1], T.parse_type(f[2]), f[3], f[4])
                           for f in j["functions"]], **kw)
    if t == "rownumber":
        return RowNumberNode(from_json(j["source"]),
                             j["partitionChannels"],
                             [tuple(k) for k in j["orderKeys"]],
                             j["maxRowsPerPartition"], j["maxPartitions"], **kw)
    if t == "unnest":
        return UnnestNode(from_json(j["source"]), j["arrayChannel"],
                          j["outCapacity"], j["withOrdinality"], **kw)
    if t == "groupid":
        return GroupIdNode(from_json(j["source"]),
                           [list(s) for s in j["groupingSets"]], **kw)
    if t == "exchange":
        return ExchangeNode(from_json(j["source"]), j["kind"], j["scope"],
                            j["partitionChannels"], j["slotCapacity"],
                            sort_keys=[tuple(k) for k in j["sortKeys"]]
                            if j.get("sortKeys") is not None else None, **kw)
    if t == "ddl":
        return DdlNode(j["op"], j["connector"], j["table"],
                       j.get("ifExists", False), **kw)
    if t == "tablerewrite":
        return TableRewriteNode(from_json(j["source"]), j["connector"],
                                j["table"], j["kind"], **kw)
    if t == "tablewriter":
        return TableWriterNode(from_json(j["source"]), j["connector"],
                               j["table"], j["columnNames"],
                               j.get("insertHandle"), **kw)
    if t == "tablefinish":
        return TableFinishNode(from_json(j["source"]), j["connector"],
                               j["table"], j["create"],
                               j["createColumns"],
                               [T.parse_type(s) for s in j["createTypes"]],
                               j.get("createProperties") or {}, **kw)
    if t == "output":
        return OutputNode(from_json(j["source"]), j["names"], **kw)
    raise ValueError(f"unknown plan node {t!r}")
