"""Connector statistics + the capacity-refinement pass.

The contract under test: `column_distinct_count` values are TRUE upper
bounds of what the generators emit (an underestimate would abort
queries with group-overflow errors), and `refine_capacities` shrinks
group tables onto the scatter-free small-table kernels without
changing any query result.
"""

import numpy as np
import pytest

from presto_tpu.connectors import catalog, schema_of
from presto_tpu.plan import nodes as N
from presto_tpu.plan.stats import (column_source, estimate_group_bound,
                                   refine_capacities)
from presto_tpu.sql.planner import plan_sql, sql

_SF = 0.002


def _bounded_columns(conn_name):
    mod = catalog(conn_name)
    out = []
    for table, cols in schema_of(conn_name).items():
        for col, _ty in cols:
            b = mod.column_distinct_count(table, col, _SF)
            if b is not None:
                out.append((table, col, b))
    return out


@pytest.mark.parametrize("conn", ["tpch", "tpcds"])
def test_ndv_bounds_hold_against_generator(conn):
    """Every declared bound >= the actual distinct count the generator
    produces (checked exhaustively at a small scale factor)."""
    mod = catalog(conn)
    checked = 0
    by_table = {}
    for table, col, bound in _bounded_columns(conn):
        by_table.setdefault(table, []).append((col, bound))
    for table, cols in by_table.items():
        arrays = mod.generate_columns(table, _SF, [c for c, _ in cols])
        for col, bound in cols:
            v = arrays[col]
            actual = len(np.unique(v))
            assert actual <= bound, \
                f"{conn}.{table}.{col}: actual {actual} > bound {bound}"
            checked += 1
    assert checked > 40  # both catalogs declare a real stats surface


def test_column_source_traces_through_plan():
    root = plan_sql("select returnflag, count(*) c from lineitem "
                    "where quantity < 10 group by returnflag")
    # find the aggregation; its key channel must trace to the base column
    def find_agg(n):
        if isinstance(n, N.AggregationNode):
            return n
        for s in n.sources:
            r = find_agg(s)
            if r is not None:
                return r
        return None

    agg = find_agg(root)
    src = column_source(agg.source, agg.group_channels[0])
    assert src == ("tpch", "lineitem", "returnflag")
    assert estimate_group_bound(agg.source, agg.group_channels, 0.01) == 4


def test_refine_capacities_shrinks_q1_group_table():
    root = plan_sql("select returnflag, linestatus, sum(quantity) q "
                    "from lineitem group by returnflag, linestatus")
    refined = refine_capacities(root, 0.01)

    def find_agg(n):
        if isinstance(n, N.AggregationNode):
            return n
        for s in n.sources:
            r = find_agg(s)
            if r is not None:
                return r
        return None

    assert find_agg(root).max_groups == 1 << 16  # planner default
    assert find_agg(refined).max_groups <= 16    # (3+1)*(2+1) -> 12 -> 16


def test_refined_query_results_unchanged(mesh8):
    q = ("select returnflag, linestatus, sum(quantity) q, count(*) c "
         "from lineitem group by returnflag, linestatus "
         "order by returnflag, linestatus")
    r = sql(q, sf=_SF)          # refinement applies inside run_query
    r8 = sql(q, sf=_SF, mesh=mesh8)
    assert list(zip(*[c for c in r.columns])) == \
        list(zip(*[c for c in r8.columns]))
    assert r.row_count == 4


def test_automatic_join_distribution_uses_row_estimates():
    from presto_tpu.plan.distribute import add_exchanges
    root = plan_sql("select o.orderkey from orders o "
                    "join lineitem l on o.orderkey = l.orderkey")
    # planner puts lineitem on the build side of this text; at SF100 the
    # estimated build (600M rows) exceeds the broadcast limit
    def join_of(n):
        if isinstance(n, N.JoinNode):
            return n
        for s in n.sources:
            r = join_of(s)
            if r is not None:
                return r
        return None

    big = join_of(add_exchanges(root, join_strategy="automatic", sf=100.0))
    small = join_of(add_exchanges(root, join_strategy="automatic", sf=0.01))
    assert big.distribution == "partitioned"
    assert small.distribution == "broadcast"
    # without sf, AUTOMATIC cannot cost anything: a build of unknown
    # size repartitions (a wrong broadcast at scale is an OOM, a wrong
    # repartition is only slower)
    unk = join_of(add_exchanges(root, join_strategy="automatic"))
    assert unk.distribution == "partitioned"


def test_unknown_columns_keep_default_capacity():
    root = plan_sql("select comment, count(*) c from orders group by comment")
    refined = refine_capacities(root, 0.01)

    def find_agg(n):
        if isinstance(n, N.AggregationNode):
            return n
        for s in n.sources:
            r = find_agg(s)
            if r is not None:
                return r
        return None

    assert find_agg(refined).max_groups == 1 << 16
