"""Parquet as a first-class connector: pushdown pruning + writer sink.

Reference behavior: presto-parquet's row-group statistics pruning
(ParquetReader.java predicate pushdown) and the ConnectorPageSink
write path (INSERT/CTAS producing parquet files with committed-version
semantics)."""

import numpy as np
import pytest

pa = pytest.importorskip("pyarrow")

from presto_tpu import types as T
from presto_tpu.connectors import parquet as pq_conn
from presto_tpu.connectors import tpch
from presto_tpu.sql import sql


@pytest.fixture
def lineitem_file(tmp_path):
    cols = tpch.generate_columns(
        "lineitem", 0.01,
        ["orderkey", "quantity", "extendedprice", "discount", "shipdate"])
    schema = dict(tpch.TPCH_SCHEMA["lineitem"])
    path = str(tmp_path / "lineitem.parquet")
    pq_conn.write_table(path, {c: cols[c] for c in cols},
                        {c: schema[c] for c in cols},
                        row_group_size=8192)
    pq_conn.register_table("pq_lineitem", path)
    yield path
    pq_conn.unregister_table("pq_lineitem")


def test_corpus_query_over_parquet_matches_generator(lineitem_file):
    q = ("SELECT sum(extendedprice * discount) FROM {t} "
         "WHERE shipdate >= date '1994-01-01' "
         "AND shipdate < date '1995-01-01' AND quantity < 24")
    got = sql(q.format(t="parquet.pq_lineitem"), sf=0.01).rows()
    want = sql(q.format(t="lineitem"), sf=0.01).rows()
    assert got == want


def test_rowgroup_pruning_measured(lineitem_file):
    res = sql("SELECT count(*) FROM parquet.pq_lineitem "
              "WHERE orderkey < 1000", sf=0.01)
    want = sql("SELECT count(*) FROM lineitem WHERE orderkey < 1000",
               sf=0.01).rows()[0][0]
    assert res.rows()[0][0] == want
    st = res.query_stats.counters
    # orderkey is sorted in dbgen order: most row groups prune away
    assert st["lake_row_groups_total"] > 0
    assert st["lake_row_groups_read"] < st["lake_row_groups_total"], st
    # pushdown never changes results: same query, pushdown off, and
    # the statement's own counters say every group was read
    off = sql("SELECT count(*) FROM parquet.pq_lineitem "
              "WHERE orderkey < 1000", sf=0.01,
              session={"scan_predicate_pushdown": False})
    assert off.rows()[0][0] == want
    st = off.query_stats.counters
    assert st["lake_row_groups_read"] == st["lake_row_groups_total"] > 0


def test_ctas_insert_roundtrip(tmp_path):
    pq_conn.set_warehouse(str(tmp_path))
    try:
        sql("CREATE TABLE parquet.ct AS SELECT nationkey, name "
            "FROM nation WHERE nationkey < 5", sf=0.01)
        v1 = pq_conn.data_version("ct")
        assert sql("SELECT count(*) FROM parquet.ct",
                   sf=0.01).rows()[0][0] == 5
        sql("INSERT INTO parquet.ct SELECT nationkey, name FROM nation "
            "WHERE nationkey >= 5 AND nationkey < 8", sf=0.01)
        assert sql("SELECT count(*) FROM parquet.ct",
                   sf=0.01).rows()[0][0] == 8
        # committed-version semantics: the data version advanced
        assert pq_conn.data_version("ct") != v1
        rows = sql("SELECT nationkey, name FROM parquet.ct "
                   "ORDER BY nationkey", sf=0.01).rows()
        want = sql("SELECT nationkey, name FROM nation "
                   "WHERE nationkey < 8 ORDER BY nationkey",
                   sf=0.01).rows()
        assert rows == want
        sql("DROP TABLE parquet.ct", sf=0.01)
        assert "ct" not in pq_conn.SCHEMA
    finally:
        pq_conn.set_warehouse(None)


def test_delete_update_on_parquet(tmp_path):
    pq_conn.set_warehouse(str(tmp_path))
    try:
        sql("CREATE TABLE parquet.du AS SELECT nationkey, regionkey "
            "FROM nation", sf=0.01)
        sql("DELETE FROM parquet.du WHERE regionkey = 0", sf=0.01)
        left = sql("SELECT count(*) FROM parquet.du", sf=0.01).rows()[0][0]
        want = sql("SELECT count(*) FROM nation WHERE regionkey <> 0",
                   sf=0.01).rows()[0][0]
        assert left == want
        sql("UPDATE parquet.du SET regionkey = 99 WHERE nationkey < 5",
            sf=0.01)
        n99 = sql("SELECT count(*) FROM parquet.du WHERE regionkey = 99",
                  sf=0.01).rows()[0][0]
        assert n99 == sql("SELECT count(*) FROM nation WHERE nationkey < 5 "
                          "AND regionkey <> 0", sf=0.01).rows()[0][0]
        sql("DROP TABLE parquet.du", sf=0.01)
    finally:
        pq_conn.set_warehouse(None)


# ---- ORC (the reference's other first-class lake format) -----------------


def test_orc_roundtrip_and_query(tmp_path):
    from presto_tpu.connectors import orc as orc_conn
    cols = tpch.generate_columns(
        "lineitem", 0.01, ["orderkey", "quantity", "shipdate"])
    schema = dict(tpch.TPCH_SCHEMA["lineitem"])
    path = str(tmp_path / "li.orc")
    orc_conn.write_table(path, {c: cols[c] for c in cols},
                         {c: schema[c] for c in cols})
    orc_conn.register_table("orc_li", path)
    try:
        q = ("SELECT count(*), sum(quantity) FROM {t} "
             "WHERE shipdate < date '1995-01-01'")
        got = sql(q.format(t="orc.orc_li"), sf=0.01).rows()
        want = sql(q.format(t="lineitem"), sf=0.01).rows()
        assert got == want
    finally:
        orc_conn.unregister_table("orc_li")


def test_orc_ctas_insert_delete(tmp_path):
    from presto_tpu.connectors import orc as orc_conn
    orc_conn.set_warehouse(str(tmp_path))
    try:
        sql("CREATE TABLE orc.t AS SELECT nationkey, regionkey "
            "FROM nation", sf=0.01)
        assert sql("SELECT count(*) FROM orc.t", sf=0.01).rows() == [(25,)]
        sql("INSERT INTO orc.t SELECT nationkey + 100, regionkey "
            "FROM nation WHERE nationkey < 3", sf=0.01)
        assert sql("SELECT count(*) FROM orc.t", sf=0.01).rows() == [(28,)]
        sql("DELETE FROM orc.t WHERE nationkey >= 100", sf=0.01)
        assert sql("SELECT count(*) FROM orc.t", sf=0.01).rows() == [(25,)]
        sql("DROP TABLE orc.t", sf=0.01)
        assert "t" not in orc_conn.SCHEMA
    finally:
        orc_conn.set_warehouse(None)
