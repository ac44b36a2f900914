import numpy as np
import pytest

from presto_tpu import types as T
from presto_tpu.connectors import tpch
from presto_tpu.exec import run_query
from presto_tpu.exec.stats import RuntimeStats
from presto_tpu.expr import call, const, input_ref
from presto_tpu.plan import (FilterNode, LimitNode, OutputNode, TableScanNode,
                             validate_plan)
from presto_tpu.sql import plan_sql


def test_validate_clean_plan():
    p = plan_sql("SELECT custkey, count(*) FROM orders GROUP BY custkey")
    assert validate_plan(p) == []


def test_validate_rejects_unknown_function_and_connector():
    scan = TableScanNode("kudu", "t", ["x"], [T.BIGINT])
    f = FilterNode(scan, call("no_such_fn", T.BOOLEAN, input_ref(0, T.BIGINT)))
    v = validate_plan(OutputNode(f, ["x"]))
    assert any("no_such_fn" in s for s in v)
    assert any("kudu" in s for s in v)


def test_run_query_rejects_invalid_plan():
    scan = TableScanNode("kudu", "t", ["x"], [T.BIGINT])
    with pytest.raises(ValueError, match="PlanChecker"):
        run_query(OutputNode(scan, ["x"]))


def test_runtime_stats_in_result():
    cols = ["orderkey"]
    s = TableScanNode("tpch", "orders", cols,
                      [tpch.column_type("orders", c) for c in cols])
    res = run_query(OutputNode(LimitNode(s, 10), ["orderkey"]), sf=0.01)
    assert res.stats["output_rows"]["total"] == 10
    assert res.stats["scan_rows"]["total"] == tpch.table_row_count("orders", 0.01)
    assert res.query_stats.stages["execute"].wall_us > 0


def test_runtime_stats_merge():
    a, b = RuntimeStats(), RuntimeStats()
    a.add("x", 1.0)
    b.add("x", 2.0)
    b.add("y", 5.0)
    a.merge(b)
    snap = a.snapshot()
    assert snap["x"]["count"] == 2 and snap["x"]["total"] == 3.0
    assert snap["y"]["max"] == 5.0
