"""TPC-H tables as Parquet files behind the `hive` catalog (ISSUE 32):
written by the paged CTAS a row group at a time, read back by row-group
splits in one decode, pruned and narrowed by footer statistics. Every
statement goes through `sql()` or the protocol; nothing here reaches
around the catalog but to read a file back and compare."""

import gc
import os
import sys
import threading
import time
import weakref

import numpy as np
import pytest

pa = pytest.importorskip("pyarrow")

from presto_tpu.block import HostStrings  # noqa: E402
from presto_tpu.client import QueryError, execute  # noqa: E402
from presto_tpu.connectors import catalog, memory, orc, parquet  # noqa: E402
from presto_tpu.connectors.tpch import generator as g  # noqa: E402
from presto_tpu.exec.runner import prepare_plan  # noqa: E402
from presto_tpu.plan import nodes as N  # noqa: E402
from presto_tpu.server.statement import StatementServer  # noqa: E402
from presto_tpu.sql import plan_sql, sql  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SF = 0.01
TABLES = ("lineitem", "orders", "customer", "part")
# hbm_budget_bytes that cut each table's CTAS into three pages or more
BUDGET = {"lineitem": 24_000_000, "orders": 6_000_000,
          "customer": 1_000_000, "part": 1_000_000}
Q6 = ("SELECT sum(extendedprice * discount) AS revenue FROM {t} "
      "WHERE shipdate >= date '1994-01-01' AND shipdate < date '1995-01-01' "
      "AND discount BETWEEN 0.05 AND 0.07 AND quantity < 24")


def _columns(table):
    return [c for c, _ in g.TPCH_SCHEMA[table]]


def _load(table, catalog_="hive.", props=" WITH (format = 'PARQUET')",
          **kw):
    return sql(f"CREATE TABLE {catalog_}{table}{props} AS SELECT "
               f"{', '.join(_columns(table))} FROM tpch.tiny.{table}",
               sf=SF, **kw)


@pytest.fixture(scope="module")
def warehouse(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("warehouse"))
    parquet.set_warehouse(d)
    orc.set_warehouse(d)
    yield d
    for mod in (parquet, orc):
        for t in list(mod.SCHEMA):
            mod.drop_table(t)
        mod.set_warehouse(None)


@pytest.fixture(scope="module")
def lake(warehouse):
    """The four tables in `hive` (paged) and in `memory`."""
    loads = {}
    for t in TABLES:
        loads[t] = _load(t, hbm_budget_bytes=BUDGET[t])
        sql(f"DROP TABLE IF EXISTS memory.{t}", sf=SF)
        _load(t, "memory.", "")
    yield loads
    for t in TABLES:
        sql(f"DROP TABLE IF EXISTS memory.{t}", sf=SF)


# -- (a) the round trip ------------------------------------------------------


@pytest.mark.parametrize("table", TABLES)
def test_every_column_written_by_pages_reads_back_exactly(lake, table):
    counters = lake[table].query_stats.counters
    rows = int(g.table_row_count(table, SF))
    assert lake[table].rows() == [(rows,)]
    assert counters["write_pages"] >= 3 and counters["write_rows"] == rows
    path = os.path.join(parquet._sink.warehouse_dir(), table + ".parquet")
    assert counters["write_bytes"] == os.path.getsize(path)
    cols = _columns(table)
    want = g.generate_columns(table, SF, cols)
    got, nulls = catalog("hive").read_columns(table, cols)
    assert catalog("hive").SCHEMA[table] == dict(g.TPCH_SCHEMA[table])
    for c in cols:
        assert not nulls[c].any()
        if isinstance(want[c], HostStrings):
            assert isinstance(got[c], HostStrings)  # no str per row
            assert np.array_equal(got[c].lengths, want[c].lengths)
            width = want[c].chars.shape[1]
            assert got[c].chars.shape[1] <= width
            assert np.array_equal(got[c].chars,
                                  want[c].chars[:, :got[c].chars.shape[1]])
        else:
            assert got[c].dtype == want[c].dtype
            assert np.array_equal(got[c], want[c]), c


def test_the_file_is_what_the_configuration_assumes(lake):
    import pyarrow.parquet as pq
    pf = pq.ParquetFile(os.path.join(parquet._sink.warehouse_dir(),
                                     "lineitem.parquet"))
    md = pf.metadata
    # a row group is cut at the writer's default and at each page's end
    assert md.num_row_groups == lake["lineitem"].query_stats.counters[
        "write_pages"]
    assert all(md.row_group(k).num_rows <= parquet.ROW_GROUP_ROWS
               for k in range(md.num_row_groups))
    by_name = {pf.schema.column(i).name: pf.schema.column(i)
               for i in range(len(pf.schema.names))}
    assert by_name["extendedprice"].physical_type == "INT64"
    assert str(by_name["extendedprice"].logical_type).startswith("Decimal")
    assert by_name["shipdate"].physical_type == "INT32"
    assert by_name["comment"].physical_type == "BYTE_ARRAY"
    assert md.row_group(0).column(0).compression == parquet.CODEC.upper()


EMPTY = ("SELECT linenumber AS i, orderkey AS b, CAST(quantity AS DOUBLE) "
         "AS d, extendedprice AS m, shipdate AS dt, shipmode AS s, "
         "quantity > 1 AS f FROM lineitem WHERE orderkey < 0")
ROWS = ("(1, 10, 2, 1.25, date '1995-01-02', 'abc', true), "
        "(NULL, NULL, NULL, NULL, NULL, NULL, NULL), "
        "(3, 7, 4, 0.75, date '1969-12-31', '', false)")


@pytest.mark.parametrize("fmt", ["PARQUET", "ORC"])
def test_nulls_of_every_type_survive_the_file(warehouse, fmt):
    """An empty CTAS gives the types; the VALUES rows, a NULL in every
    column, arrive by INSERT (the merge of a file with new pages)."""
    name = f"nulls_{fmt.lower()}"
    made = sql(f"CREATE TABLE hive.{name} WITH (format = '{fmt}') AS "
               f"{EMPTY}", sf=SF)
    try:
        assert made.rows() == [(0,)]
        assert name in (parquet if fmt == "PARQUET" else orc).SCHEMA
        assert sql(f"SELECT count(*) FROM hive.{name}", sf=SF).rows() == \
            [(0,)]
        assert sql(f"INSERT INTO hive.{name} VALUES {ROWS}", sf=SF
                   ).rows() == [(3,)]
        got = sql(f"SELECT i, b, d, m, dt, s, f FROM hive.{name}",
                  sf=SF).rows()
        assert got == [(1, 10, 2.0, 125, 9132, "abc", True),
                       (None,) * 7,
                       (3, 7, 4.0, 75, -1, "", False)]
        values, nulls = catalog("hive").read_columns(name, ["i", "s", "m"])
        assert nulls["i"].tolist() == [False, True, False]
        assert values["i"].tolist() == [1, 0, 3]  # a null row's lane is 0
        assert values["s"].lengths.tolist() == [3, 0, 0]
        assert values["m"].tolist() == [125, 0, 75]
        # a second insert lands beside the first
        sql(f"INSERT INTO hive.{name} VALUES {ROWS}", sf=SF)
        assert sql(f"SELECT count(*), count(i), sum(m) FROM hive.{name}",
                   sf=SF).rows() == [(6, 4, 400)]
    finally:
        sql(f"DROP TABLE hive.{name}", sf=SF)
    assert name not in catalog("hive").SCHEMA


# -- the catalog and its table properties -----------------------------------


def test_format_picks_the_module_and_the_old_names_still_reach_it(warehouse):
    sql("CREATE TABLE hive.n_orc WITH (format = 'ORC') AS "
        "SELECT nationkey, name FROM nation", sf=SF)
    sql("CREATE TABLE hive.n_pq AS SELECT nationkey, name FROM nation",
        sf=SF)
    try:
        assert "n_orc" in orc.SCHEMA and "n_pq" in parquet.SCHEMA
        want = sql("SELECT nationkey, name FROM nation ORDER BY nationkey",
                   sf=SF).rows()
        for t in ("hive.n_orc", "orc.n_orc", "hive.n_pq", "parquet.n_pq"):
            assert sql(f"SELECT nationkey, name FROM {t} "
                       "ORDER BY nationkey", sf=SF).rows() == want
        with pytest.raises(KeyError, match="already exists"):
            sql("CREATE TABLE hive.n_orc AS SELECT 1 AS a", sf=SF)
        sql("INSERT INTO hive.n_pq SELECT nationkey, name FROM nation "
            "WHERE nationkey < 3", sf=SF)
        assert sql("SELECT count(*) FROM hive.n_pq", sf=SF).rows() == [(28,)]
    finally:
        sql("DROP TABLE hive.n_orc", sf=SF)
        sql("DROP TABLE IF EXISTS hive.n_pq", sf=SF)
    sql("DROP TABLE IF EXISTS hive.never_was", sf=SF)


@pytest.mark.parametrize("text, says", [
    ("CREATE TABLE hive.x WITH (format = 'AVRO') AS SELECT 1 AS a",
     "unknown hive table format 'AVRO'"),
    ("CREATE TABLE hive.x WITH (bucket_count = 4) AS SELECT 1 AS a",
     "no table property 'bucket_count'"),
    ("CREATE TABLE memory.x WITH (format = 'ORC') AS SELECT 1 AS a",
     "catalog 'memory' has no table property 'format'"),
])
def test_an_unknown_property_or_format_is_an_error(warehouse, text, says):
    with pytest.raises(ValueError, match=says):
        sql(text, sf=SF)
    assert "x" not in catalog("hive").SCHEMA and "x" not in memory.SCHEMA


def test_a_leftover_file_is_replaced_and_nothing_shows_before_the_publish(
        warehouse, monkeypatch):
    path = os.path.join(warehouse, "fresh.parquet")
    parquet.write_table(path, {"a": np.arange(5, dtype=np.int64)},
                        {"a": g.TPCH_SCHEMA["nation"][0][1]})
    seen = []
    real = parquet._sink.append

    def watching(handle, columns, nulls=None):
        seen.append(("fresh" in catalog("hive").SCHEMA,
                     os.path.getsize(path)))
        return real(handle, columns, nulls)
    monkeypatch.setattr(parquet, "append", watching)
    before = os.path.getsize(path)
    sql("CREATE TABLE hive.fresh AS SELECT nationkey AS a FROM nation",
        sf=SF)
    try:
        # while pages arrive: no table, and the old file untouched
        assert seen == [(False, before)]
        assert sql("SELECT count(*), sum(a) FROM hive.fresh", sf=SF
                   ).rows() == [(25, 300)]
        assert [f for f in os.listdir(warehouse) if "staged" in f] == []
    finally:
        sql("DROP TABLE hive.fresh", sf=SF)


def test_a_failed_load_leaves_no_table_and_no_file(warehouse, monkeypatch):
    def failing(handle, columns, nulls=None):
        raise RuntimeError("disk full")
    monkeypatch.setattr(parquet, "append", failing)
    with pytest.raises(RuntimeError, match="disk full"):
        sql("CREATE TABLE hive.broken AS SELECT nationkey FROM nation",
            sf=SF)
    monkeypatch.undo()
    assert "broken" not in catalog("hive").SCHEMA
    assert [f for f in os.listdir(warehouse) if "broken" in f] == []
    sql("CREATE TABLE hive.broken AS SELECT nationkey FROM nation", sf=SF)
    sql("DROP TABLE hive.broken", sf=SF)


# -- (b) the cells' statements over hive, memory and numpy -------------------


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, ROOT)
    from benchmarks.harness import judge, traffic
    return traffic, judge.Reference(SF, os.path.join(ROOT, ".cache"))


@pytest.mark.parametrize("template, mix", [
    ("q6", "q6_stream"), ("q14", "q14_q3_stream"), ("q3", "q14_q3_stream")])
def test_the_cells_statements_agree_over_hive_memory_and_numpy(
        lake, bench, template, mix):
    traffic, reference = bench
    module = reference.module(template)
    for params in traffic.template_of(traffic.read_json("traffic", mix),
                                      template)["sets"]:
        over = {c: sql(traffic.statement_text(template, c, params), sf=SF
                       ).rows() for c in ("hive.", "memory.")}
        assert over["hive."] == over["memory."]
        rows = over["hive."]
        if template == "q3":  # the wire renders a DATE, rows() its days
            from benchmarks.harness.population import day_text
            rows = [(k, rev, day_text(d), p) for k, rev, d, p in rows]
        got = module.from_wire(rows)
        assert module.gap(got, reference.answer(template, params)) \
            <= module.LIMIT


# -- (c) pruning by footer statistics ---------------------------------------


def test_a_file_sorted_by_shipdate_prunes_and_the_generators_order_does_not(
        lake, warehouse):
    cols = ["quantity", "extendedprice", "discount", "shipdate"]
    data = g.generate_columns("lineitem", SF, cols)
    order = np.argsort(data["shipdate"], kind="stable")
    path = os.path.join(warehouse, "by_shipdate.parquet")
    parquet.write_table(path, {c: data[c][order] for c in cols},
                        dict(g.TPCH_SCHEMA["lineitem"]), row_group_size=4096)
    parquet.register_table("by_shipdate", path)
    try:
        want = sql(Q6.format(t="tpch.lineitem"), sf=SF).rows()
        pruned = sql(Q6.format(t="hive.by_shipdate"), sf=SF)
        assert pruned.rows() == want
        c = pruned.query_stats.counters
        assert 0 < c["lake_row_groups_read"] < c["lake_row_groups_total"] \
            == 15
        whole = sql(Q6.format(t="hive.lineitem"), sf=SF)
        assert whole.rows() == want
        c = whole.query_stats.counters
        assert c["lake_row_groups_read"] == c["lake_row_groups_total"] > 0
        # fewer groups read is fewer bytes opened, decoded and staged
        small, big = pruned.query_stats, whole.query_stats
        assert small.counters["lake_file_bytes"] < \
            big.counters["lake_file_bytes"]
        assert small.stages["staging"].rows < big.stages["staging"].rows
        # at the table's capacity all the same: one program for both
        assert small.stages["staging"].bytes == big.stages["staging"].bytes
    finally:
        parquet.unregister_table("by_shipdate")


# -- (d) widths from the footers, (e) one decode a scan ----------------------


def _scan(text):
    node = prepare_plan(plan_sql(text), sf=SF)
    while not isinstance(node, N.TableScanNode):
        (node,) = node.sources
    return node


def test_footer_ranges_narrow_q6_to_the_memory_tables_lanes(lake):
    for c in ("quantity", "extendedprice", "discount", "shipdate",
              "orderkey"):
        assert parquet.column_range("lineitem", c) == \
            memory.column_range("lineitem", c), c
    assert parquet.column_range("lineitem", "comment") is None
    over_file = _scan(Q6.format(t="hive.lineitem"))
    over_memory = _scan(Q6.format(t="memory.lineitem"))
    assert over_file.pushdown is not None  # pruning and narrowing both
    assert over_file.columns == over_memory.columns
    assert over_file.physical_dtypes == over_memory.physical_dtypes
    assert set(over_file.physical_dtypes) <= {"int8", "int16", "int32"}


def test_q6_over_hive_and_memory_run_programs_of_the_same_shapes(lake):
    """The lake cell compiles nothing the memory path would not: same
    capacity, same physical lanes, so the same bytes staged and the
    same program planned."""
    a = sql(Q6.format(t="hive.lineitem"), sf=SF).query_stats
    b = sql(Q6.format(t="memory.lineitem"), sf=SF).query_stats
    assert a.stages["staging"].bytes == b.stages["staging"].bytes == \
        60000 * 14
    assert a.counters["program_hbm_bytes"] == b.counters["program_hbm_bytes"]
    assert a.counters["narrowed_bytes_saved"] == \
        b.counters["narrowed_bytes_saved"]


def test_a_scan_decodes_once_and_its_hops_tile_staging(lake, monkeypatch):
    """Each column of each group is decoded once; each hop is recorded
    once a scan, inside `staging` (they overlap there and no longer
    tile it: `test_the_hops_of_a_pipelined_scan_overlap`)."""
    from presto_tpu.exec.stats import StatsCollector, collecting
    calls = []
    real = parquet.arrow_to_engine
    monkeypatch.setattr(parquet, "arrow_to_engine",
                        lambda arr, ty: calls.append(len(arr))
                        or real(arr, ty))
    collector = StatsCollector()
    with collecting(collector):
        qs = sql(Q6.format(t="hive.lineitem"), sf=SF).query_stats
    groups = qs.counters["lake_row_groups_read"]
    # four columns of every row group, each decoded once
    assert len(calls) == 4 * groups and sum(calls) == 4 * 60000
    assert qs.counters["lake_row_groups_pipelined"] == groups
    hops = qs.datapath
    assert "narrow_cast" not in hops  # its proof and cast are the decode
    assert [hops[h].invocations for h in
            ("connector_read", "decode", "device_put")] == [1, 1, 1]
    assert hops["connector_read"].bytes == qs.counters["lake_file_bytes"] > 0
    # the narrowed lanes (2 + 4 + 1 + 2 bytes a row) and four masks
    assert hops["decode"].bytes == qs.counters["lake_decoded_bytes"] \
        == 60000 * (9 + 4)
    assert hops["device_put"].bytes == qs.stages["staging"].bytes
    spans = {name: (t0, t1, span, parent)
             for name, t0, t1, _a, span, parent in collector.spans}
    s0, s1, staging, _ = spans["staging"]
    for h in ("connector_read", "decode", "device_put"):
        t0, t1, _span, parent = spans[h]
        assert parent == staging and s0 <= t0 <= t1 <= s1, h
        assert abs(hops[h].wall_us - (t1 - t0) * 1e6) <= 1


def test_a_join_filtered_by_its_build_side_reads_the_file_once(lake,
                                                               monkeypatch):
    reads = []
    real = parquet.read_columns
    monkeypatch.setattr(parquet, "read_columns",
                        lambda t, *a, **k: reads.append(t) or real(t, *a, **k))
    text = ("SELECT count(*) FROM {c}lineitem l JOIN {c}part p "
            "ON l.partkey = p.partkey WHERE p.size = 1")
    got = sql(text.format(c="hive."), sf=SF)
    assert got.rows() == sql(text.format(c="tpch."), sf=SF).rows()
    assert got.stats["dynamic_filter_rows_pruned"]["total"] > 0
    assert reads.count("lineitem") == 1


def test_scans_from_more_threads_than_cores_share_the_decode_pool(lake):
    """Every scan's row groups decode on the one pool into that scan's
    own lanes: concurrent statements may not see each other's slices."""
    from concurrent.futures import ThreadPoolExecutor
    cols = ["orderkey", "extendedprice", "shipdate", "comment"]
    want = g.generate_columns("lineitem", SF, cols)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with ThreadPoolExecutor((os.cpu_count() or 1) + 4) as callers:
            scans = [callers.submit(parquet.read_columns, "lineitem", cols,
                                    7 * k, 60000 - 11 * k)
                     for k in range(24)]
            for k, scan in enumerate(scans):
                got, nulls = scan.result(timeout=120)
                cut = slice(7 * k, 7 * k + 60000 - 11 * k)
                for c in cols[:3]:
                    assert np.array_equal(got[c], want[c][cut]), (k, c)
                assert np.array_equal(got["comment"].lengths,
                                      want["comment"].lengths[cut])
                assert not any(n.any() for n in nulls.values())
    finally:
        sys.setswitchinterval(interval)


def test_the_writer_holds_one_page_at_a_time(warehouse, monkeypatch):
    pages, alive_before = [], []
    real = parquet._sink.append

    def watching(handle, columns, nulls=None):
        gc.collect()
        alive_before.append(sum(r() is not None for r in pages))
        pages.append(weakref.ref(columns[0]))
        return real(handle, columns, nulls)
    monkeypatch.setattr(parquet, "append", watching)
    sql("DROP TABLE IF EXISTS hive.orders", sf=SF)
    done = _load("orders", hbm_budget_bytes=BUDGET["orders"])
    assert done.query_stats.counters["write_pages"] == len(pages) >= 3
    # when a page arrives, every page before it has been let go
    assert alive_before == [0] * len(pages)


# -- (f) the pipeline: a row group is the unit of staging ---------------------

Q6_COLS = ["quantity", "extendedprice", "discount", "shipdate"]


@pytest.fixture(scope="module")
def sorted_file(warehouse):
    """lineitem's Q6 columns sorted by shipdate, 59 row groups of 1,024
    rows: more groups than the pool has threads or a scan has in
    flight."""
    data = g.generate_columns("lineitem", SF, Q6_COLS)
    order = np.argsort(data["shipdate"], kind="stable")
    path = os.path.join(warehouse, "sorted_1k.parquet")
    parquet.write_table(path, {c: data[c][order] for c in Q6_COLS},
                        dict(g.TPCH_SCHEMA["lineitem"]), row_group_size=1024)
    parquet.register_table("sorted_1k", path)
    yield {c: data[c][order] for c in Q6_COLS}
    parquet.unregister_table("sorted_1k")


def _numpy_q6(data):
    keep = (data["shipdate"] >= 8766) & (data["shipdate"] < 9131) \
        & (data["discount"] >= 5) & (data["discount"] <= 7) \
        & (data["quantity"] < 2400)
    return int((data["extendedprice"][keep] * data["discount"][keep]).sum())


def _leaves(batch):
    import jax
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(batch)]


def test_a_stale_range_stages_that_column_wide_and_never_wraps(
        lake, sorted_file):
    """A `column_range` that lies narrower than one group's values: the
    group refuses its lane, the scan stages whole and that column wide,
    and the answer is memory's and numpy's."""
    want = sql(Q6.format(t="memory.lineitem"), sf=SF).rows()
    assert want == [(_numpy_q6(sorted_file),)]
    honest = parquet.column_range("sorted_1k", "extendedprice")
    assert honest[1] > 127
    parquet._tables["sorted_1k"]["ranges"]["extendedprice"] = (0, 100)
    try:
        node = _scan(Q6.format(t="hive.sorted_1k"))
        lanes = dict(zip(node.columns, node.physical_dtypes))
        assert lanes["extendedprice"] == "int8"  # the plan trusted it
        got = sql(Q6.format(t="hive.sorted_1k"), sf=SF)
        assert got.rows() == want
        c = got.query_stats.counters
        assert c["lake_row_groups_pipelined"] == 0 < c["lake_row_groups_read"]
        assert got.query_stats.datapath["narrow_cast"].invocations == 1
        # the other three stay narrow: 8 + 2 + 1 + 2 and four masks, and
        # the batch's own mask, a row
        assert got.query_stats.stages["staging"].bytes == 60000 * (13 + 5)
    finally:
        parquet._tables["sorted_1k"]["ranges"]["extendedprice"] = honest
    again = sql(Q6.format(t="hive.sorted_1k"), sf=SF).query_stats.counters
    assert again["lake_row_groups_pipelined"] == again["lake_row_groups_read"]


def test_a_pruned_scan_through_the_pipeline_is_the_whole_scan_row_for_row(
        sorted_file):
    """Pruned groups, fewer rows than `count`: the batch the pipeline
    assembles on the device is the one `batch_from_numpy` makes of the
    host columns, leaf for leaf, order included."""
    from presto_tpu.block import batch_from_numpy
    from presto_tpu.exec.runner import stage_scan_split
    from presto_tpu.plan.widths import checked_physical_dtypes
    node = _scan(Q6.format(t="hive.sorted_1k"))
    assert node.pushdown is not None and any(node.physical_dtypes)
    predicate = tuple(node.pushdown)
    # the whole file; a range that cuts its first and last group; one
    # inside 1994 at a capacity under a row group's rows
    for start, count, capacity, pruned in ((0, 60000, 60000, True),
                                           (1500, 40000, 40960, True),
                                           (20000, 3000, 3000, False)):
        got = stage_scan_split(catalog("hive"), node, SF, start, count,
                               capacity, predicate)
        values, nulls = parquet.read_columns("sorted_1k", node.columns,
                                             start, count, predicate)
        arrays = [values[c] for c in node.columns]
        masks = [nulls[c] for c in node.columns]
        phys = checked_physical_dtypes(node.physical_dtypes,
                                       node.column_types, arrays, masks)
        assert phys == node.physical_dtypes
        want = batch_from_numpy(node.column_types, arrays, nulls=masks,
                                capacity=capacity, physical_dtypes=phys)
        # statistics excluded groups: fewer rows than `count`
        assert 0 < len(arrays[0]) <= count - pruned
        assert int(np.asarray(got.active).sum()) == len(arrays[0])
        for a, b in zip(_leaves(got), _leaves(want)):
            assert a.dtype == b.dtype and np.array_equal(a, b)
    # every group excluded: an empty batch of the same lanes
    none = stage_scan_split(catalog("hive"), node, SF, 0, 700, 1024,
                            predicate)
    assert not np.asarray(none.active).any()
    assert [str(c.values.dtype) for c in none.columns] == \
        list(node.physical_dtypes)
    assert all(np.asarray(c.nulls).all() for c in none.columns)


def test_nulls_of_every_fixed_width_type_survive_the_narrowed_decode(
        warehouse):
    made = sql("CREATE TABLE hive.nulls_fixed AS SELECT linenumber AS i, "
               "orderkey AS b, CAST(quantity AS DOUBLE) AS d, "
               "extendedprice AS m, shipdate AS dt, quantity > 1 AS f "
               "FROM lineitem WHERE orderkey < 0", sf=SF)
    try:
        assert made.rows() == [(0,)]
        rows = ("(1, 10, 2, 1.25, date '1995-01-02', true), "
                "(NULL, NULL, NULL, NULL, NULL, NULL), "
                "(3, 7, 4, 0.75, date '1969-12-31', false)")
        sql(f"INSERT INTO hive.nulls_fixed VALUES {rows}", sf=SF)
        node = _scan("SELECT i, b, d, m, dt, f FROM hive.nulls_fixed")
        lanes = dict(zip(node.columns, node.physical_dtypes))
        assert lanes["i"] == lanes["b"] == lanes["m"] == "int8"
        assert lanes["dt"] == "int16" and lanes["d"] is lanes["f"] is None
        got = sql("SELECT i, b, d, m, dt, f FROM hive.nulls_fixed", sf=SF)
        assert got.rows() == [(1, 10, 2.0, 125, 9132, True), (None,) * 6,
                              (3, 7, 4.0, 75, -1, False)]
        c = got.query_stats.counters
        assert c["lake_row_groups_pipelined"] == c["lake_row_groups_read"] > 0
        assert sql("SELECT count(*), count(i), count(f), sum(m), max(dt) "
                   "FROM hive.nulls_fixed", sf=SF).rows() == \
            [(3, 2, 2, 200, 9132)]
    finally:
        sql("DROP TABLE hive.nulls_fixed", sf=SF)


def _watch_pieces(monkeypatch):
    """Counts of the pieces the pool began and finished."""
    seen = {"began": 0, "ended": 0}
    lock = threading.Lock()
    real = parquet.PieceScan._piece

    def watching(self, src):
        with lock:
            seen["began"] += 1
        try:
            return real(self, src)
        finally:
            with lock:
                seen["ended"] += 1
    monkeypatch.setattr(parquet.PieceScan, "_piece", watching)
    return seen


def test_a_decode_that_raises_fails_the_statement_and_clears_the_pool(
        sorted_file, monkeypatch):
    seen = _watch_pieces(monkeypatch)
    real = parquet.arrow_to_engine
    calls = []

    def failing(arr, ty):
        calls.append(1)
        if len(calls) == 5:  # a group in the middle of the first wave
            raise RuntimeError("page checksum mismatch")
        time.sleep(0.001)
        return real(arr, ty)
    monkeypatch.setattr(parquet, "arrow_to_engine", failing)
    with pytest.raises(RuntimeError, match="page checksum mismatch"):
        sql("SELECT sum(quantity) FROM hive.sorted_1k", sf=SF)
    # the error surfaced with nothing of the scan left on the pool
    assert seen["began"] == seen["ended"] < 59
    assert parquet._decode_pool()._work_queue.empty()
    time.sleep(0.05)
    assert seen["began"] == seen["ended"]
    monkeypatch.undo()
    assert sql("SELECT count(*) FROM hive.sorted_1k", sf=SF).rows() == \
        [(60000,)]


@pytest.mark.parametrize("dtypes", [None, ["int16", "int32", "int8",
                                           "int16"]])
def test_the_groups_in_flight_never_exceed_the_bound(sorted_file,
                                                     monkeypatch, dtypes):
    """Both consumers' form of the producer: the groups the pool has
    begun and the consumer has not yet taken stay within `depth`, and a
    slow consumer finds it full."""
    seen = _watch_pieces(monkeypatch)
    scan = parquet.scan_pieces("sorted_1k", Q6_COLS, dtypes=dtypes)
    assert len(scan.sources) == 59 > scan.depth >= 2
    taken, ahead, rows = 0, [], []
    for piece in scan:
        time.sleep(0.002)  # the consumer is the slow side
        ahead.append(seen["began"] - taken)
        taken += 1
        rows.append(piece.values["shipdate"][:piece.rows])
    assert taken == 59 and max(ahead) <= scan.depth + 1
    assert max(ahead) >= scan.depth  # the bound is what held it back
    assert np.array_equal(np.concatenate(rows), sorted_file["shipdate"])


def test_the_hops_of_a_pipelined_scan_overlap(sorted_file, monkeypatch):
    """Where groups are still being read while others are put, the
    hops' walls sum to more than the `staging` that encloses them."""
    text = "SELECT sum(quantity), max(shipdate) FROM hive.sorted_1k"
    sql(text, sf=SF)  # the programs that assemble the batch compile here
    real = parquet.arrow_to_engine

    def slow(arr, ty):
        time.sleep(0.002)  # 59 groups of two columns on at most 16 threads
        return real(arr, ty)
    monkeypatch.setattr(parquet, "arrow_to_engine", slow)
    qs = sql(text, sf=SF).query_stats
    assert qs.counters["lake_row_groups_pipelined"] == 59
    assert qs.counters.get("xla_compiles", 0) == 0
    hops = qs.datapath
    walls = [hops[h].wall_us for h in ("connector_read", "decode",
                                       "device_put")]
    staging = qs.stages["staging"].wall_us
    assert max(walls) <= staging < sum(walls)


class _SteppedClock:
    """`time.time` as a counter: every reading, of any thread, is one
    millisecond after the last."""

    def __init__(self):
        self.now, self.lock = 1_000_000.0, threading.Lock()

    def __call__(self):
        with self.lock:
            self.now += 0.001
            return self.now


@pytest.mark.parametrize("consumer", ["device", "host"])
def test_a_scans_thread_seconds_are_its_pieces_intervals_summed(
        sorted_file, monkeypatch, consumer):
    """What the envelopes of a pipelined scan hide: the pool's threads'
    own seconds, each piece's read and decode intervals summed in file
    order, and the statement thread's wait for pieces, noted once a
    scan by the one producer for both consumers."""
    from presto_tpu.exec.stats import StatsCollector, collecting, stage
    text = "SELECT sum(quantity), max(shipdate) FROM hive.sorted_1k"
    if consumer == "device":
        sql(text, sf=SF)  # compile outside the stepped clock
    pieces = {}
    real = parquet.PieceScan._piece

    def keeping(self, src):
        piece = pieces[src[0]] = real(self, src)
        return piece
    monkeypatch.setattr(parquet.PieceScan, "_piece", keeping)
    monkeypatch.setattr(time, "time", _SteppedClock())
    collector = StatsCollector()
    with collecting(collector):
        if consumer == "device":
            sql(text, sf=SF)
        else:
            with stage("staging"):
                parquet.read_columns("sorted_1k", Q6_COLS)
    monkeypatch.undo()
    counters = collector.stats.counters
    assert len(pieces) == 59 == counters["lake_row_groups_read"]
    assert counters["lake_row_groups_pipelined"] == \
        (59 if consumer == "device" else 0)
    read_s = decode_s = 0.0
    for group in sorted(pieces):
        r, d = pieces[group].read_at, pieces[group].decode_at
        read_s += r[1] - r[0]
        decode_s += d[1] - d[0]
    assert counters["lake_read_thread_us"] == round(read_s * 1e6) >= 59_000
    assert counters["lake_decode_thread_us"] == round(decode_s * 1e6) \
        >= 59_000
    # the consumer read the clock twice a piece around its wait
    staging = collector.stats.stages["staging"].wall_us
    assert 59_000 <= counters["lake_consumer_wait_us"] <= staging
    # the envelopes are what they were: first entry to last exit
    spans = {name: (t0, t1) for name, t0, t1, *_rest in collector.spans}
    for hop, at in (("connector_read", "read_at"), ("decode", "decode_at")):
        assert spans[hop][0] == min(getattr(p, at)[0]
                                    for p in pieces.values()), hop
        assert spans[hop][1] >= max(getattr(p, at)[1]
                                    for p in pieces.values()), hop


def test_a_scan_that_reads_no_file_notes_no_thread_seconds():
    counters = sql(Q6.format(t="tpch.tiny.lineitem"),
                   sf=SF).query_stats.counters
    assert not [k for k in counters if k.startswith("lake_")]


def test_q6_pipelines_every_group_and_a_filtered_join_none_of_lineitems(
        lake):
    c = sql(Q6.format(t="hive.lineitem"), sf=SF).query_stats.counters
    assert c["lake_row_groups_pipelined"] == c["lake_row_groups_read"] > 0
    text = ("SELECT count(*) FROM hive.lineitem l JOIN hive.part p "
            "ON l.partkey = p.partkey WHERE p.size = 1")
    got = sql(text, sf=SF)
    assert got.stats["dynamic_filter_rows_pruned"]["total"] > 0
    c = got.query_stats.counters
    import pyarrow.parquet as pq
    groups = {t: pq.ParquetFile(os.path.join(
        parquet._sink.warehouse_dir(), t + ".parquet")).metadata
        .num_row_groups for t in ("lineitem", "part")}
    # lineitem's scan is pruned on the host by the filter part built
    assert c["lake_row_groups_pipelined"] % groups["part"] == 0
    assert c["lake_row_groups_pipelined"] == \
        c["lake_row_groups_read"] - groups["lineitem"]


# -- the protocol: what the benchmark's load sends ---------------------------


def test_the_load_and_a_q6_over_the_protocol(warehouse):
    with StatementServer(sf=SF) as srv:
        execute(srv.url, "DROP TABLE IF EXISTS hive.lineitem")
        made = execute(
            srv.url,
            "CREATE TABLE hive.lineitem WITH (format = 'PARQUET') AS SELECT "
            f"{', '.join(_columns('lineitem'))} FROM tpch.tiny.lineitem",
            session={"hbm_budget_bytes": "24000000"})
        assert int(made.data[0][0]) == 60000
        counters = made.stats["queryStats"]["counters"]
        assert counters["write_pages"] >= 3
        back = execute(srv.url, "SELECT count(*) FROM hive.lineitem")
        assert int(back.data[0][0]) == 60000
        done = execute(srv.url, Q6.format(t="hive.lineitem"))
        qs = done.stats["queryStats"]
        assert qs["counters"]["lake_row_groups_read"] == \
            qs["counters"]["lake_row_groups_total"] >= 3
        assert qs["counters"]["lake_file_bytes"] > 0
        assert qs["datapath"]["decode"]["wall_us"] > 0
        assert qs["datapath"]["decode"]["bytes"] == \
            qs["counters"]["lake_decoded_bytes"]
        with pytest.raises(QueryError, match="sf10"):
            execute(srv.url, "CREATE TABLE hive.x WITH (format = 'PARQUET') "
                    "AS SELECT orderkey FROM tpch.sf10.orders")
        assert "x" not in catalog("hive").SCHEMA
