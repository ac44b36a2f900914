#!/usr/bin/env python
"""Generate the round-4 protocol fixtures (joins/windows/unnest/...).

The reference ships captured wire documents only for the round-3 slice
(scan/filter/values/exchange shapes -- presto_protocol/tests/data/);
there are NO in-repo captures of Join/Window/Unnest fragments. These
fixtures are therefore SYNTHESIZED, field-for-field, from the wire
vocabulary the coordinator serializes: the @JsonCreator constructors of
presto-spi/src/main/java/com/facebook/presto/spi/plan/{JoinNode,
SemiJoinNode,WindowNode,UnnestNode,MarkDistinctNode,DistinctLimitNode,
TopNRowNumberNode}.java and presto-main-base/.../sql/planner/plan/
{GroupIdNode,RowNumberNode}.java, with constants encoded in the
SerializedPage block format (serialized-page.rst) exactly as
ConstantExpression.valueBlock ships them.

Run from the repo root to (re)generate:  python tests/fixtures/protocol/gen_round4.py
"""

import base64
import json
import os
import struct
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", ".."))
sys.path.insert(0, os.path.join(HERE, "..", "..", "..", "scripts"))
import _cpu  # noqa: E402,F401  (force the CPU)

import numpy as np  # noqa: E402

from presto_tpu import types as T  # noqa: E402
from presto_tpu.serde.pages import (_serialize_array,  # noqa: E402
                                    _serialize_fixed)


def v(name, ty):
    return {"@type": "variable", "name": name, "type": ty}


def const_bigint(x, ty="bigint"):
    blk = _serialize_fixed(np.array([x], dtype=np.int64),
                           np.array([False]))
    return {"@type": "constant", "type": ty,
            "valueBlock": base64.b64encode(blk).decode()}


def const_array_bigint(values):
    arr = np.empty(1, dtype=object)
    arr[0] = list(values)
    blk = _serialize_array(arr, np.array([False]),
                           T.array_of(T.BIGINT))
    return {"@type": "constant", "type": "array(bigint)",
            "valueBlock": base64.b64encode(blk).decode()}


def call(op, rty, *args, name=None):
    return {"@type": "call", "displayName": name or op,
            "functionHandle": {"@type": "$static", "signature": {
                "name": f"presto.default.{op}", "kind": "SCALAR",
                "returnType": rty,
                "argumentTypes": [a.get("type", a.get("returnType", ""))
                                  for a in args]}},
            "returnType": rty, "arguments": list(args)}


def agg_handle(op, rty, arg_types):
    return {"@type": "$static", "signature": {
        "name": f"presto.default.{op}", "kind": "AGGREGATE",
        "returnType": rty, "argumentTypes": arg_types}}


def scan(table, cols, node_id="1"):
    """tpch TableScanNode; cols = [(prefixed_name, type)]."""
    return {
        "@type": ".TableScanNode", "id": node_id,
        "table": {"connectorId": "tpch",
                  "connectorHandle": {"@type": "tpch", "tableName": table,
                                      "scaleFactor": 0.01}},
        "outputVariables": [v(n, t) for n, t in cols],
        "assignments": {f"{n}<{t}>": {"@type": "tpch", "columnName": n,
                                      "type": t} for n, t in cols},
    }


ORDERS = scan("orders", [("o_orderkey", "bigint"), ("o_custkey", "bigint"),
                         ("o_totalprice", "decimal(12,2)")], "1")
CUSTOMER = scan("customer", [("c_custkey", "bigint"),
                             ("c_acctbal", "decimal(12,2)")], "2")


def write(name, doc):
    with open(os.path.join(HERE, name), "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print("wrote", name)


# -- JoinNode: INNER equi-join, outputVariables reordered/subset --------
write("JoinNode.json", {
    "@type": ".JoinNode", "id": "3", "type": "INNER",
    "left": ORDERS, "right": CUSTOMER,
    "criteria": [{"left": v("o_custkey", "bigint"),
                  "right": v("c_custkey", "bigint")}],
    "outputVariables": [v("o_totalprice", "decimal(12,2)"),
                        v("c_acctbal", "decimal(12,2)"),
                        v("o_orderkey", "bigint")],
    "filter": None, "leftHashVariable": None, "rightHashVariable": None,
    "distributionType": "PARTITIONED", "dynamicFilters": {},
})

# -- JoinNode: LEFT outer, broadcast build ------------------------------
write("JoinNodeLeft.json", {
    "@type": ".JoinNode", "id": "3", "type": "LEFT",
    "left": ORDERS, "right": CUSTOMER,
    "criteria": [{"left": v("o_custkey", "bigint"),
                  "right": v("c_custkey", "bigint")}],
    "outputVariables": [v("o_orderkey", "bigint"),
                        v("c_acctbal", "decimal(12,2)")],
    "filter": None, "leftHashVariable": None, "rightHashVariable": None,
    "distributionType": "REPLICATED", "dynamicFilters": {},
})

# -- JoinNode: INNER with residual (non-equi) filter --------------------
write("JoinNodeResidualFilter.json", {
    "@type": ".JoinNode", "id": "3", "type": "INNER",
    "left": ORDERS, "right": CUSTOMER,
    "criteria": [{"left": v("o_custkey", "bigint"),
                  "right": v("c_custkey", "bigint")}],
    "outputVariables": [v("o_orderkey", "bigint")],
    "filter": call("$operator$greater_than", "boolean",
                   v("o_totalprice", "decimal(12,2)"),
                   v("c_acctbal", "decimal(12,2)"), name="GREATER_THAN"),
    "leftHashVariable": None, "rightHashVariable": None,
    "distributionType": "PARTITIONED", "dynamicFilters": {},
})

# -- SemiJoinNode -------------------------------------------------------
write("SemiJoinNode.json", {
    "@type": ".SemiJoinNode", "id": "3",
    "source": ORDERS, "filteringSource": CUSTOMER,
    "sourceJoinVariable": v("o_custkey", "bigint"),
    "filteringSourceJoinVariable": v("c_custkey", "bigint"),
    "semiJoinOutput": v("expr_9", "boolean"),
    "sourceHashVariable": None, "filteringSourceHashVariable": None,
    "distributionType": "REPLICATED", "dynamicFilters": {},
})

# -- WindowNode: row_number + framed sum --------------------------------
write("WindowNode.json", {
    "@type": ".WindowNode", "id": "3", "source": ORDERS,
    "specification": {
        "partitionBy": [v("o_custkey", "bigint")],
        "orderingScheme": {"orderBy": [
            {"variable": v("o_totalprice", "decimal(12,2)"),
             "sortOrder": "DESC_NULLS_LAST"}]},
    },
    "windowFunctions": {
        "rn<bigint>": {
            "functionCall": {
                "@type": "call", "displayName": "row_number",
                "functionHandle": agg_handle("row_number", "bigint", []),
                "returnType": "bigint", "arguments": []},
            "frame": {"type": "RANGE", "startType": "UNBOUNDED_PRECEDING",
                      "endType": "CURRENT_ROW"},
            "ignoreNulls": False},
        "running<decimal(38,2)>": {
            "functionCall": {
                "@type": "call", "displayName": "sum",
                "functionHandle": agg_handle("sum", "decimal(38,2)",
                                             ["decimal(12,2)"]),
                "returnType": "decimal(38,2)",
                "arguments": [v("o_totalprice", "decimal(12,2)")]},
            "frame": {"type": "ROWS", "startType": "PRECEDING",
                      "startValue": v("expr_f", "bigint"),
                      "originalStartValue": "1",
                      "endType": "CURRENT_ROW",
                      "originalEndValue": None},
            "ignoreNulls": False},
    },
    "hashVariable": None, "prePartitionedInputs": [],
    "preSortedOrderPrefix": 0,
})

# -- RowNumberNode ------------------------------------------------------
write("RowNumberNode.json", {
    "@type": "com.facebook.presto.sql.planner.plan.RowNumberNode",
    "id": "3", "source": ORDERS,
    "partitionBy": [v("o_custkey", "bigint")],
    "rowNumberVariable": v("row_number_11", "bigint"),
    "maxRowCountPerPartition": 2, "partial": False,
    "hashVariable": None,
})

# -- TopNRowNumberNode --------------------------------------------------
write("TopNRowNumberNode.json", {
    "@type": ".TopNRowNumberNode", "id": "3", "source": ORDERS,
    "specification": {
        "partitionBy": [v("o_custkey", "bigint")],
        "orderingScheme": {"orderBy": [
            {"variable": v("o_totalprice", "decimal(12,2)"),
             "sortOrder": "DESC_NULLS_LAST"}]},
    },
    "rankingType": "ROW_NUMBER",
    "rowNumberVariable": v("row_number_12", "bigint"),
    "maxRowCountPerPartition": 1, "partial": False,
    "hashVariable": None,
})

# -- MarkDistinctNode ---------------------------------------------------
write("MarkDistinctNode.json", {
    "@type": ".MarkDistinctNode", "id": "3", "source": ORDERS,
    "markerVariable": v("o_custkey$distinct", "boolean"),
    "distinctVariables": [v("o_custkey", "bigint")],
    "hashVariable": None,
})

# -- DistinctLimitNode --------------------------------------------------
write("DistinctLimitNode.json", {
    "@type": ".DistinctLimitNode", "id": "3", "source": ORDERS,
    "limit": 5, "partial": False,
    "distinctVariables": [v("o_custkey", "bigint")],
    "hashVariable": None, "timeoutMillis": 0,
})

# -- GroupIdNode: ROLLUP(custkey) = sets ((custkey), ()) ----------------
write("GroupIdNode.json", {
    "@type": "com.facebook.presto.sql.planner.plan.GroupIdNode",
    "id": "3", "source": ORDERS,
    "groupingSets": [[v("o_custkey$gid", "bigint")], []],
    "groupingColumns": {"o_custkey$gid<bigint>": v("o_custkey", "bigint")},
    "aggregationArguments": [v("o_totalprice", "decimal(12,2)")],
    "groupIdVariable": v("groupid", "bigint"),
})

# -- UnnestNode over a VALUES row with an array constant ----------------
VALUES_ARRAYS = {
    "@type": ".ValuesNode", "id": "1",
    "outputVariables": [v("id", "bigint"), v("arr", "array(bigint)")],
    "rows": [
        [const_bigint(1), const_array_bigint([10, 20])],
        [const_bigint(2), const_array_bigint([])],
        [const_bigint(3), const_array_bigint([30, 40, 50])],
    ],
}
write("UnnestNode.json", {
    "@type": ".UnnestNode", "id": "3", "source": VALUES_ARRAYS,
    "replicateVariables": [v("id", "bigint")],
    "unnestVariables": {"arr<array(bigint)>": [v("elem", "bigint")]},
    "ordinalityVariable": v("ord", "bigint"),
})

# -- AggregationNode: DISTINCT sum + mask'd count -----------------------
write("AggMaskedDistinct.json", {
    "@type": ".AggregationNode", "id": "3",
    "source": {
        "@type": ".MarkDistinctNode", "id": "2", "source": ORDERS,
        "markerVariable": v("mask$distinct", "boolean"),
        "distinctVariables": [v("o_custkey", "bigint")],
        "hashVariable": None,
    },
    "aggregations": {
        "distinct_custs<bigint>": {
            "call": {"@type": "call", "displayName": "count",
                     "functionHandle": agg_handle("count", "bigint",
                                                  ["bigint"]),
                     "returnType": "bigint",
                     "arguments": [v("o_custkey", "bigint")]},
            "distinct": False,
            "mask": v("mask$distinct", "boolean")},
        "sum_distinct_price<decimal(38,2)>": {
            "call": {"@type": "call", "displayName": "sum",
                     "functionHandle": agg_handle(
                         "sum", "decimal(38,2)", ["decimal(12,2)"]),
                     "returnType": "decimal(38,2)",
                     "arguments": [v("o_totalprice", "decimal(12,2)")]},
            "distinct": True},
        "n<bigint>": {
            "call": {"@type": "call", "displayName": "count",
                     "functionHandle": agg_handle("count", "bigint", []),
                     "returnType": "bigint", "arguments": []},
            "distinct": False},
    },
    "groupingSets": {"groupingSetCount": 1, "globalGroupingSets": [],
                     "groupingKeys": []},
    "step": "SINGLE",
})

# -- a q3-shaped TaskUpdateRequest fragment -----------------------------
LINEITEM = scan("lineitem", [("l_orderkey", "bigint"),
                             ("l_extendedprice", "decimal(12,2)")], "2")
ORDERS_Q3 = scan("orders", [("o_orderkey", "bigint"),
                            ("o_orderdate", "date"),
                            ("o_shippriority", "integer")], "1")
q3_join = {
    "@type": ".JoinNode", "id": "4", "type": "INNER",
    "left": {
        "@type": ".FilterNode", "id": "3", "source": ORDERS_Q3,
        "predicate": call("$operator$less_than", "boolean",
                          v("o_orderdate", "date"),
                          const_bigint(9204, "date"), name="LESS_THAN"),
    },
    "right": LINEITEM,
    "criteria": [{"left": v("o_orderkey", "bigint"),
                  "right": v("l_orderkey", "bigint")}],
    "outputVariables": [v("l_orderkey", "bigint"),
                        v("o_orderdate", "date"),
                        v("o_shippriority", "integer"),
                        v("l_extendedprice", "decimal(12,2)")],
    "filter": None, "leftHashVariable": None, "rightHashVariable": None,
    "distributionType": "PARTITIONED", "dynamicFilters": {},
}
q3_agg = {
    "@type": ".AggregationNode", "id": "5", "source": q3_join,
    "aggregations": {
        "revenue<decimal(38,2)>": {
            "call": {"@type": "call", "displayName": "sum",
                     "functionHandle": agg_handle(
                         "sum", "decimal(38,2)", ["decimal(12,2)"]),
                     "returnType": "decimal(38,2)",
                     "arguments": [v("l_extendedprice", "decimal(12,2)")]},
            "distinct": False}},
    "groupingSets": {
        "groupingSetCount": 1, "globalGroupingSets": [],
        "groupingKeys": [v("l_orderkey", "bigint"),
                         v("o_orderdate", "date"),
                         v("o_shippriority", "integer")]},
    "step": "SINGLE",
}
q3_topn = {
    "@type": ".TopNNode", "id": "6", "source": q3_agg, "count": 10,
    "orderingScheme": {"orderBy": [
        {"variable": v("revenue", "decimal(38,2)"),
         "sortOrder": "DESC_NULLS_LAST"},
        {"variable": v("o_orderdate", "date"),
         "sortOrder": "ASC_NULLS_LAST"}]},
    "step": "SINGLE",
}
q3_fragment = {
    "id": "1",
    "root": {"@type": ".OutputNode", "id": "7", "source": q3_topn,
             "columnNames": ["l_orderkey", "o_orderdate",
                             "o_shippriority", "revenue"],
             "outputVariables": [v("l_orderkey", "bigint"),
                                 v("o_orderdate", "date"),
                                 v("o_shippriority", "integer"),
                                 v("revenue", "decimal(38,2)")]},
    "tableScanSchedulingOrder": ["1", "2"],
}
write("TaskUpdateRequestQ3.json", {
    "extraCredentials": {},
    "fragment": base64.b64encode(json.dumps(q3_fragment).encode()).decode(),
    "session": {"queryId": "q3-protocol", "user": "tester",
                "systemProperties": {}},
    "sources": [{"planNodeId": "1", "splits": [], "noMoreSplits": True},
                {"planNodeId": "2", "splits": [], "noMoreSplits": True}],
    "outputIds": {"type": "PARTITIONED", "buffers": {"0": 0},
                  "noMoreBufferIds": True, "version": 1},
    "tableWriteInfo": {},
})

print("done")
