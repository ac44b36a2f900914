"""Strings held encoded from generator to table, CTAS by pages, the
`tpch` catalog's schema names, and the counters a deployment-size
statement is read by (ISSUE 28).

The generator's string columns are `block.HostStrings` (bytes and
lengths); the loops below are the generator as it was, one Python
string per row, kept as the reference the encoded form has to equal
byte for byte."""

import json
import os
import sys
import urllib.request

import numpy as np
import pytest

from presto_tpu.block import HostStrings
from presto_tpu.client import QueryError, execute
from presto_tpu.connectors import memory
from presto_tpu.connectors.tpch import generator as g
from presto_tpu.server.statement import StatementServer
from presto_tpu.server.tracing import RecordingTracer, get_tracer, \
    set_tracer
from presto_tpu.sql import plan_sql, sql

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SF = 0.01

# -- (i) the encoded form against the generator as it was -------------------


def _pick(table, column, idx, choices):
    codes = g._h(table, column, idx) % np.uint64(len(choices))
    return [choices[int(c)] for c in codes]


def _comment(table, idx, nwords, max_chars=None):
    words = [_pick(table, f"comment{k}", idx, g._COMMENT_WORDS)
             for k in range(nwords)]
    rows = [" ".join(w) for w in zip(*words)]
    return [r[:max_chars] for r in rows] if max_chars else rows


def _numbered(prefix, num):
    return [f"{prefix}#{int(v):09d}" for v in num]


def _phone(table, idx):
    nk = g._uniform(table, "nationkey", idx, 0, 24)
    h = g._h(table, "phone", idx).astype(np.int64)
    return [f"{10 + int(n)}-{int(v) % 900 + 100}-"
            f"{(int(v) >> 10) % 900 + 100}-{(int(v) >> 20) % 9000 + 1000}"
            for n, v in zip(nk, h)]


def _as_it_was(table, column, idx, sf):
    """The strings of `table.column` for rows `idx`, made row by row."""
    if column == "comment":
        words, cut = {"lineitem": (3, None), "orders": (5, None),
                      "customer": (6, None), "part": (2, 23),
                      "supplier": (5, None), "partsupp": (8, None),
                      "nation": (4, None), "region": (4, None)}[table]
        return _comment(table, idx, words, cut)
    if column == "address":
        return _comment(table, idx, 2)
    if column == "phone":
        return _phone(table, idx)
    if (table, column) == ("part", "name"):
        return _comment("part", idx, 3)
    if (table, column) == ("nation", "name"):
        return [g._NATIONS[int(i)] for i in idx]
    if (table, column) == ("region", "name"):
        return [g._REGIONS[int(i)] for i in idx]
    if column == "name":
        return _numbered(table.capitalize(), idx + 1)
    if column == "clerk":
        return _numbered("Clerk", g._uniform("orders", "clerk", idx, 1,
                                             max(int(1000 * sf), 1)))
    if column in ("returnflag", "linestatus"):
        odate = g._orders_orderdate(idx // g.LINES_PER_ORDER)
        ship = odate + g._uniform("lineitem", "shipdate", idx, 1, 121)
        if column == "linestatus":
            return ["O" if s > g._CUTOFF_1995_06_17 else "F" for s in ship]
        receipt = ship + g._uniform("lineitem", "receiptdate", idx, 1, 30)
        ra = _pick("lineitem", "returnflag", idx, ["R", "A"])
        return [f if r <= g._CUTOFF_1995_06_17 else "N"
                for f, r in zip(ra, receipt)]
    if column == "mfgr":
        return [f"Manufacturer#{v}"
                for v in g._uniform("part", "mfgr", idx, 1, 5)]
    if column == "brand":
        return [f"Brand#{m}{b}" for m, b in zip(
            g._uniform("part", "mfgr", idx, 1, 5),
            g._uniform("part", "brand", idx, 1, 5))]
    choices = {"shipinstruct": g._INSTRUCTS, "shipmode": g._MODES,
               "orderstatus": ["F", "O", "P"],
               "orderpriority": g._PRIORITIES, "mktsegment": g._SEGMENTS,
               "type": g.P_TYPES, "container": g._CONTAINERS}[column]
    return _pick(table, column, idx, choices)


STRING_COLUMNS = [(t, c) for t, cols in g.TPCH_SCHEMA.items()
                  for c, ty in cols if ty.is_string]


@pytest.mark.parametrize("table,column", STRING_COLUMNS,
                         ids=[f"{t}.{c}" for t, c in STRING_COLUMNS])
def test_encoded_column_decodes_to_the_generators_strings(table, column):
    rows = g.table_row_count(table, SF)
    start = rows // 3
    count = min(rows - start, 700)
    got = g.generate_columns(table, SF, [column], start, count)[column]
    assert isinstance(got, HostStrings)
    assert got.chars.dtype == np.uint8 and got.lengths.dtype == np.int32
    want = _as_it_was(table, column,
                      np.arange(start, start + count, dtype=np.int64), SF)
    assert got.tolist() == want
    assert [bytes(r[:n]) for r, n in zip(got.chars, got.lengths)] == \
        [w.encode() for w in want]
    # zero beyond each length, so that rows compare whole on the device
    beyond = np.arange(got.chars.shape[1])[None, :] >= got.lengths[:, None]
    assert not got.chars[beyond].any()
    assert int(got.lengths.max()) <= g.column_type(table, column).max_length


def test_host_strings_round_trip_and_look_like_an_object_array():
    from presto_tpu import types as T
    from presto_tpu.block import from_numpy, to_numpy
    values = ["abc", "", None, "héllo", "x\x00y", "z" * 9]
    enc = HostStrings.from_objects(values)
    want = ["" if v is None else v for v in values]
    assert enc.tolist() == list(enc) == want
    assert [enc[i] for i in range(len(enc))] == want
    assert np.asarray(enc).dtype == object and enc.dtype == object
    assert (enc == "abc").tolist() == [True] + [False] * 5
    assert (enc != "abc").tolist() == [False] + [True] * 5
    assert enc[1:4].tolist() == want[1:4]
    assert enc[np.array([0, 3, 5])].tolist() == ["abc", "héllo", "z" * 9]
    assert enc[np.arange(6) % 2 == 0].tolist() == want[::2]
    assert enc.nbytes == enc.chars.nbytes + enc.lengths.nbytes
    both = HostStrings.concat([enc, HostStrings.from_objects(["a wider one"])])
    assert both.tolist() == want + ["a wider one"]
    assert HostStrings.concat([]).tolist() == []
    # to the device and back: the same bytes, the null mask beside them
    nulls = np.array([v is None for v in values])
    col = from_numpy(T.varchar(20), enc, nulls, capacity=8)
    back, back_nulls = to_numpy(col)
    assert isinstance(back, HostStrings)
    assert back.tolist() == want + ["", ""]
    assert back_nulls.tolist() == nulls.tolist() + [True, True]
    # Python strings are still taken, encoded on the way in
    again, _ = to_numpy(from_numpy(T.varchar(20),
                                   np.array(values, dtype=object)))
    assert again.tolist() == want


def test_a_large_split_is_made_on_threads_and_equals_the_small_ones():
    cols = [c for c, _ in g.TPCH_SCHEMA["orders"]]
    n = g._CHUNK_ROWS + 1000
    whole = g.generate_columns("orders", 1.0, cols, 5, n)
    parts = [g.generate_columns("orders", 1.0, cols, 5 + at, 1000)
             for at in (0, n - 1000)]
    for c in cols:
        for part, at in zip(parts, (0, n - 1000)):
            a, b = whole[c][at:at + 1000], part[c]
            assert (a.tolist() == b.tolist()) if isinstance(a, HostStrings) \
                else (a == b).all()


# -- stored strings answer as the generated ones ------------------------------

STORED = ["customer", "orders", "nation"]
ON_STRINGS = [
    "SELECT name, phone FROM {c}customer WHERE custkey < 6 ORDER BY custkey",
    "SELECT count(*) FROM {c}customer WHERE mktsegment = 'BUILDING'",
    "SELECT count(*), min(comment) FROM {c}orders "
    "WHERE comment LIKE '%furiously%' AND clerk LIKE 'Clerk#00000000%'",
    "SELECT orderpriority, count(*) FROM {c}orders "
    "GROUP BY orderpriority ORDER BY orderpriority",
    "SELECT n.name, count(*) FROM {c}customer c JOIN {c}nation n "
    "ON c.nationkey = n.nationkey WHERE n.name LIKE 'A%' OR n.name = 'PERU' "
    "GROUP BY n.name ORDER BY n.name",
]


@pytest.fixture(scope="module")
def stored_tables():
    for t in STORED:
        memory.drop_table(f"enc_{t}", if_exists=True)
        cols = ", ".join(c for c, _ in g.TPCH_SCHEMA[t])
        sql(f"CREATE TABLE memory.enc_{t} AS SELECT {cols} "
            f"FROM tpch.tiny.{t}", sf=SF)
    yield
    for t in STORED:
        memory.drop_table(f"enc_{t}", if_exists=True)


@pytest.mark.parametrize("text", ON_STRINGS)
def test_stored_strings_answer_as_before(stored_tables, text):
    want = sql(text.format(c="tpch."), sf=SF).rows()
    got = sql(text.format(c="memory.enc_"), sf=SF).rows()
    assert got == want and len(want) > 0


def test_the_store_keeps_bytes_and_lengths(stored_tables):
    t = memory._tables["enc_customer"]
    for ty, col in zip(t.types, t.values):
        assert isinstance(col, HostStrings) == ty.is_string
        assert col.dtype != object or ty.is_string
    scanned = memory.generate_columns("enc_customer", 0, ["name"], 3, 4)
    assert isinstance(scanned["name"], HostStrings)
    assert scanned["name"].tolist() == \
        [f"Customer#{k:09d}" for k in range(4, 8)]
    assert memory.column_range("enc_customer", "name") is None
    assert memory.column_range("enc_customer", "custkey") == (1, 1500)


def test_python_strings_and_nulls_still_go_in_and_out():
    memory.drop_table("enc_values", if_exists=True)
    try:
        from presto_tpu import types as T
        memory.create_table("enc_values", ["k", "s"],
                            [T.BIGINT, T.varchar(20)])
        sql("INSERT INTO memory.enc_values VALUES (1, 'één'), (2, NULL), "
            "(3, '')", sf=SF)
        sql("INSERT INTO memory.enc_values VALUES (4, 'a longer string')",
            sf=SF)
        assert sql("SELECT k, s FROM memory.enc_values ORDER BY k",
                   sf=SF).rows() == [
            (1, "één"), (2, None), (3, ""), (4, "a longer string")]
        sql("DELETE FROM memory.enc_values WHERE s = ''", sf=SF)
        assert sql("SELECT s FROM memory.enc_values WHERE s IS NOT NULL "
                   "ORDER BY k", sf=SF).rows() == \
            [("één",), ("a longer string",)]
    finally:
        memory.drop_table("enc_values", if_exists=True)


# -- the tpch catalog's schema names ------------------------------------------


def test_a_schema_of_the_served_scale_resolves():
    scan = [n for n in _nodes(plan_sql("SELECT name FROM tpch.tiny.nation"))
            if type(n).__name__ == "TableScanNode"]
    assert [(s.connector, s.table, s.schema) for s in scan] == \
        [("tpch", "nation", "tiny")]
    assert sql("SELECT count(*) FROM tpch.tiny.nation", sf=0.01).rows() == \
        sql("SELECT count(*) FROM tpch.sf1.nation", sf=1.0).rows() == [(25,)]


def test_a_schema_of_another_scale_is_refused_before_anything_is_read():
    memory.drop_table("enc_refused", if_exists=True)
    with pytest.raises(KeyError, match="sf10.*scale factor 10.*serves.*0.01"):
        sql("CREATE TABLE memory.enc_refused AS "
            "SELECT name FROM tpch.sf10.nation", sf=0.01)
    assert "enc_refused" not in memory.table_names()
    with pytest.raises(KeyError, match="'bogus' not in catalog 'tpch'"):
        plan_sql("SELECT name FROM tpch.bogus.nation")


def test_two_part_names_keep_working():
    assert sql("SELECT count(*) FROM tpch.region", sf=SF).rows() == [(5,)]
    assert sql("SELECT count(*) FROM region", sf=SF).rows() == [(5,)]
    assert [n.schema for n in _nodes(plan_sql("SELECT name FROM tpch.region"))
            if type(n).__name__ == "TableScanNode"] == [None]


def _nodes(root):
    out, todo = [], [root]
    while todo:
        n = todo.pop()
        out.append(n)
        todo.extend(n.sources)
    return out


# -- (ii) CTAS by pages -------------------------------------------------------

LINEITEM = ", ".join(c for c, _ in g.TPCH_SCHEMA["lineitem"])
PAGED = f"CREATE TABLE memory.{{t}} AS SELECT {LINEITEM} FROM tpch.tiny.lineitem"
SMALL = 24_000_000   # of hbm_budget_bytes: a page is an eighth of it


def _same_table(a: str, b: str):
    ta, tb = memory._tables[a], memory._tables[b]
    assert ta.columns == tb.columns and ta.row_count == tb.row_count
    for x, y, nx, ny in zip(ta.values, tb.values, ta.nulls, tb.nulls):
        if isinstance(x, HostStrings):
            assert (x.lengths == y.lengths).all()
            assert x.tolist() == y.tolist()
        else:
            assert x.dtype == y.dtype and (x == y).all()
        assert (nx == ny).all()


@pytest.fixture()
def no_tables():
    names = ("enc_one", "enc_paged", "enc_broken")
    for t in names:
        memory.drop_table(t, if_exists=True)
    yield
    for t in names:
        memory.drop_table(t, if_exists=True)


def test_a_paged_ctas_equals_the_one_page_table(no_tables):
    one = sql(PAGED.format(t="enc_one"), sf=SF)
    paged = sql(PAGED.format(t="enc_paged"), sf=SF, hbm_budget_bytes=SMALL)
    assert one.rows() == paged.rows() == [(60000,)]
    assert one.query_stats.counters["write_pages"] == 1
    counters = paged.query_stats.counters
    assert counters["write_pages"] > 2
    assert counters["write_rows"] == 60000
    assert counters["write_bytes"] == one.query_stats.counters["write_bytes"]
    # every page ran the one program: compiled once, then found again
    assert counters["plan_cache_hits"] >= counters["write_pages"] - 1
    assert counters.get("xla_compiles", 0) <= 1
    write = paged.query_stats.stages["write"]
    assert write.invocations == counters["write_pages"]
    _same_table("enc_one", "enc_paged")


def test_a_failure_in_the_third_page_leaves_no_table(no_tables, monkeypatch):
    seen = []
    real = memory.append

    def failing(handle, columns, nulls=None):
        # a reader during the write sees none of it
        seen.append((memory.table_row_count("enc_broken"),
                     len(memory.generate_columns(
                         "enc_broken", 0, ["orderkey"])["orderkey"])))
        if len(seen) == 3:
            raise RuntimeError("planted in the third page")
        return real(handle, columns, nulls)
    monkeypatch.setattr(memory, "append", failing)
    with pytest.raises(RuntimeError, match="third page"):
        sql(PAGED.format(t="enc_broken"), sf=SF, hbm_budget_bytes=SMALL)
    assert seen == [(0, 0)] * 3
    assert "enc_broken" not in memory.table_names()
    assert not memory._pending


def test_what_cannot_be_cut_is_one_page(no_tables):
    res = sql("CREATE TABLE memory.enc_one AS SELECT returnflag, count(*) c "
              "FROM tpch.lineitem GROUP BY returnflag", sf=SF,
              hbm_budget_bytes=1 << 20)
    assert res.query_stats.counters["write_pages"] == 1
    assert memory.table_row_count("enc_one") == 3


# -- (iii) the system against the plain references, on paged tables ------------


@pytest.fixture(scope="module")
def served():
    sys.path.insert(0, ROOT)
    from benchmarks.harness import judge, traffic
    config = traffic.read_json("configs", "tpch_sf10_memory")
    mix = traffic.read_json("traffic", "q14_q3_stream")
    reference = judge.Reference(SF, os.path.join(ROOT, ".cache"))
    tables = sorted({t for tpl in mix["templates"] for t in tpl["tables"]})
    before = get_tracer()
    set_tracer(RecordingTracer())   # /v1/trace serves what it records
    with StatementServer(sf=SF) as srv:
        loads = {}
        for t in tables:
            execute(srv.url, f"DROP TABLE IF EXISTS memory.{t}")
            text = config["load"].replace("tpch.sf10.", "tpch.tiny.").format(
                table=t, columns=", ".join(config["columns"][t]))
            loads[t] = execute(srv.url, text,
                               session={"hbm_budget_bytes": str(SMALL)})
        try:
            yield srv, traffic, mix, reference, loads
        finally:
            set_tracer(before)
            for t in tables:
                execute(srv.url, f"DROP TABLE IF EXISTS memory.{t}")


def test_the_load_over_the_protocol_is_paged_and_says_so(served):
    srv, _, _, reference, loads = served
    for t, done in loads.items():
        assert int(done.data[0][0]) == reference.pop.rows(t)
    qs = loads["lineitem"].stats["queryStats"]
    pages = qs["counters"]["write_pages"]
    assert pages > 2 and qs["counters"]["write_rows"] == 60000
    assert qs["stages"]["write"]["invocations"] == pages
    with urllib.request.urlopen(
            f"{srv.url}/v1/trace/{loads['lineitem'].query_id}") as r:
        spans = json.load(r)["spans"]
    writes = {s["spanId"] for s in spans if s["name"] == "stage.write"}
    page_spans = [s for s in spans if s["name"] == "stage.write.page"]
    assert len(page_spans) == pages
    assert all(s["parentId"] in writes for s in page_spans)
    assert sorted(s["attributes"]["page"] for s in page_spans) == \
        list(range(pages))
    assert sum(s["attributes"]["rows"] for s in page_spans) == 60000
    assert [s["parentId"] in writes for s in spans
            if s["name"] == "stage.write.publish"] == [True]


@pytest.mark.parametrize("template", ["q14", "q3"])
def test_answers_equal_the_plain_reference_and_float32_does_not(
        served, template):
    srv, traffic, mix, reference, _ = served
    module = reference.module(template)
    params = traffic.template_of(mix, template)["sets"][0]
    done = execute(srv.url, traffic.statement_text(template, "memory.",
                                                   params))
    want = reference.answer(template, params)
    assert module.gap(module.from_wire(done.data), want) <= module.LIMIT
    assert module.LIMIT == (0 if template == "q3" else 1e-10)
    control = reference.answer(template, params, control=True)
    assert module.gap(control, want) > module.LIMIT


# -- (iv) the counters of a statement that overflows and of one that does not --

JOIN = ("SELECT o.orderpriority, count(*) FROM tpch.lineitem l "
        "JOIN tpch.orders o ON l.orderkey = o.orderkey "
        "WHERE l.quantity < {q} GROUP BY o.orderpriority")


def test_capacity_reruns_and_program_bytes_of_an_overflow():
    calm = sql(JOIN.format(q=11), sf=SF).query_stats.to_json()["counters"]
    assert calm["capacity_reruns"] == 0 and calm["program_hbm_bytes"] > 0
    tight = sql(JOIN.format(q=12), sf=SF, join_capacity=1 << 12)
    counters = tight.query_stats.to_json()["counters"]
    assert counters["capacity_reruns"] == 1
    # the largest program dispatched: the rerun's, four times the capacity
    assert counters["program_hbm_bytes"] > 0
    assert tight.query_stats.stages["dispatch"].invocations == 2


def test_both_counters_ride_every_answer_of_the_server():
    with StatementServer(sf=SF) as srv:
        for text in ("SELECT count(*) FROM lineitem WHERE shipmode = 'AIR'",
                     JOIN.format(q=13)):
            counters = execute(srv.url, text).stats["queryStats"]["counters"]
            assert counters["capacity_reruns"] == 0
            assert counters["program_hbm_bytes"] > 0


def test_the_server_refuses_another_scales_schema_with_its_reason():
    with StatementServer(sf=SF) as srv:
        with pytest.raises(QueryError, match="sf10"):
            execute(srv.url, "SELECT count(*) FROM tpch.sf10.customer")
        assert int(execute(srv.url, "SELECT count(*) FROM tpch.tiny.customer"
                           ).data[0][0]) == 1500
