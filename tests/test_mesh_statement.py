"""A statement runs over the chips its tables are spread over.

`CREATE TABLE memory.t WITH (workers = N) AS ...` keeps the placement
with the table; a statement that scans such a table runs as one SPMD
program over `min(N, chips)` devices, for every entry point alike
(here: `POST /v1/statement` and `sql()`), with per-shard staging,
exchanges sized from the shard and counters kept with the compiled
plan. A statement over tables without the property runs the one-chip
path as before. The suite's 8 virtual CPU devices stand for the chips.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import judge, traffic  # noqa: E402
from presto_tpu.client import QueryError, execute  # noqa: E402
from presto_tpu.connectors import catalog, memory  # noqa: E402
from presto_tpu.connectors.tpch import generator as g  # noqa: E402
from presto_tpu.exec import runner  # noqa: E402
from presto_tpu.exec.plan_cache import plan_fingerprint  # noqa: E402
from presto_tpu.plan import nodes as N  # noqa: E402
from presto_tpu.server.statement import StatementServer  # noqa: E402
from presto_tpu.sql import plan_sql, sql  # noqa: E402

SF = 0.01
TABLES = ("lineitem", "orders", "customer", "part")
PARAMS = {
    "q3": {"SEGMENT": "BUILDING", "DATE": "1995-03-15"},
    "q14": {"DATE_LO": "1995-09-01", "DATE_HI": "1995-10-01"},
    "q6": {"DATE_LO": "1994-01-01", "DATE_HI": "1995-01-01",
           "DISCOUNT_LO": "0.05", "DISCOUNT_HI": "0.07", "QUANTITY": "24"},
}
EXCHANGE_COUNTERS = ("mesh_chips", "exchanges", "exchange_bytes",
                     "exchange_slot_bytes", "exchange_row_bytes")


def _columns(table):
    return ", ".join(c for c, _ in g.TPCH_SCHEMA[table])


def _text(template, prefix):
    """The benchmark's statement over `<prefix><table>` memory tables."""
    text = traffic.statement_text(template, "memory.", PARAMS[template])
    for t in TABLES:
        text = text.replace(f"memory.{t}", f"memory.{prefix}{t}")
    return text


def _counters(done):
    return done.stats["queryStats"]["counters"]


@pytest.fixture(scope="module")
def server():
    """One server, each table twice: `x4_<t>` spread over four workers,
    `x1_<t>` without the property."""
    with StatementServer(sf=SF) as srv:
        for t in TABLES:
            for name, props in ((f"x4_{t}", " WITH (workers = 4)"),
                                (f"x1_{t}", "")):
                execute(srv.url, f"DROP TABLE IF EXISTS memory.{name}")
                made = execute(
                    srv.url, f"CREATE TABLE memory.{name}{props} AS SELECT "
                    f"{_columns(t)} FROM tpch.tiny.{t}")
                assert int(made.data[0][0]) == g.table_row_count(t, SF)
        yield srv
        for t in TABLES:
            memory.drop_table(f"x4_{t}", if_exists=True)
            memory.drop_table(f"x1_{t}", if_exists=True)


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    return judge.Reference(SF, str(tmp_path_factory.mktemp("refs")))


def test_the_property_is_kept_with_the_table(server):
    assert memory.table_workers("x4_lineitem") == 4
    assert memory.table_properties_of("x4_lineitem") == {"workers": 4}
    assert memory.table_workers("x1_lineitem") == 1
    assert memory.table_properties_of("x1_lineitem") == {}


@pytest.mark.parametrize("template", ["q3", "q14", "q6"])
def test_a_meshed_statement_equals_one_chip_and_the_oracle(
        server, reference, template):
    meshed = execute(server.url, _text(template, "x4_"))
    single = execute(server.url, _text(template, "x1_"))
    assert meshed.data == single.data
    module = reference.module(template)
    want = reference.answer(template, PARAMS[template])
    assert module.gap(module.from_wire(meshed.data), want) <= module.LIMIT
    assert _counters(meshed)["mesh_chips"] == 4
    assert "mesh_chips" not in _counters(single)
    assert "exchanges" not in _counters(single)


def test_count_star_reads_every_shard(server):
    for t in TABLES:
        back = execute(server.url, f"SELECT count(*) FROM memory.x4_{t}")
        assert int(back.data[0][0]) == g.table_row_count(t, SF)
        assert _counters(back)["mesh_chips"] == 4


@pytest.mark.parametrize("template", ["q3", "q14"])
def test_a_cache_hit_reports_its_programs_exchanges(server, template):
    """Exchange counters are kept with the compiled plan: the statement
    that traced the program and one served from the plan cache agree."""
    first = _counters(execute(server.url, _text(template, "x4_")))
    again = _counters(execute(server.url, _text(template, "x4_")))
    assert again["plan_cache_hits"] >= 1 and "plan_cache_misses" not in again
    for name in EXCHANGE_COUNTERS:
        assert first[name] == again[name], name
    assert again["exchanges"] >= 2
    kinds = {k: v for k, v in again.items() if k.startswith("exchange.")}
    assert sum(kinds.values()) == again["exchanges"]
    assert kinds == {k: v for k, v in first.items()
                     if k.startswith("exchange.")}
    assert again["exchange_slot_bytes"] <= again["exchange_bytes"]
    assert again["exchange_row_bytes"] <= again["exchange_slot_bytes"]
    if kinds.get("exchange.hash"):  # Q3's group-by; Q14 has none
        assert 0 < again["exchange_row_bytes"]
    assert again["capacity_reruns"] == 0


def test_a_table_without_the_property_joins_one_with_it(server):
    """Q14 with lineitem spread and part not: both scans are staged in
    shards and the rows are the one-chip rows."""
    text = _text("q14", "x4_").replace("memory.x4_part", "memory.x1_part")
    mixed = execute(server.url, text)
    assert mixed.data == execute(server.url, _text("q14", "x1_")).data
    assert _counters(mixed)["mesh_chips"] == 4


def _staged(table, columns, mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    from presto_tpu.parallel.mesh import WORKERS_AXIS
    root = runner.prepare_plan(plan_sql(
        f"SELECT {columns} FROM memory.{table}"), sf=SF)
    scan = root
    while not isinstance(scan, N.TableScanNode):
        scan = scan.sources[0]
    sharding = NamedSharding(mesh, PartitionSpec(WORKERS_AXIS))
    return scan, runner._scan_batch(scan, SF, None, mesh.devices.size * 8,
                                    sharding=sharding)


@pytest.mark.parametrize("table,columns", [
    ("x4_lineitem", "orderkey, extendedprice, discount, shipdate"),
    ("x4_customer", "custkey, mktsegment"),
])
def test_a_scans_shards_lie_on_their_own_devices(server, table, columns):
    """Each device holds its contiguous range of the table's rows and
    nothing more; device 0 holds no more than the others."""
    import jax
    from presto_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(4)
    scan, batch = _staged(table, columns, mesh)
    rows = memory.table_row_count(table)
    per = batch.capacity // 4
    assert per * 4 == batch.capacity and per * 3 < rows <= batch.capacity
    held = {d.id: 0 for d in mesh.devices.flat}
    for leaf in jax.tree_util.tree_leaves(batch):
        assert leaf.shape[0] == batch.capacity
        shards = sorted(leaf.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        assert [s.device.id for s in shards] == list(held)
        for k, s in enumerate(shards):
            assert (s.index[0].start or 0) == k * per
            assert s.data.shape[0] == per
            held[s.device.id] += s.data.nbytes
    assert len(set(held.values())) == 1  # device 0 as much as any other
    host = memory.generate_columns(table, SF, scan.columns)
    first = np.asarray(batch.column(0).values)
    assert np.array_equal(first[:rows], np.asarray(host[scan.columns[0]]))
    assert int(np.asarray(batch.active).sum()) == rows
    # narrowed shard by shard to the lanes the plan annotated
    assert [str(batch.column(i).values.dtype)
            for i, dt in enumerate(scan.physical_dtypes or []) if dt] == \
        [dt for dt in (scan.physical_dtypes or []) if dt]


def test_staging_hops_carry_the_shard_count(server):
    done = sql(_text("q6", "x4_"), sf=SF)
    hops = done.query_stats.datapath
    assert hops["device_put"].invocations == 1
    assert hops["narrow_cast"].invocations == 1
    assert done.query_stats.counters["mesh_chips"] == 4


def test_a_planted_skew_overflows_the_slots_reruns_and_answers(server):
    """Every probe row one key: a hash exchange sends all of them one
    way, the slots overflow, the ladder reruns at larger slots and the
    rows are right; the slots that fitted are remembered with the
    plan."""
    made = {"skew4": " WITH (workers = 4)", "skew1": ""}
    for name, props in made.items():
        sql(f"DROP TABLE IF EXISTS memory.{name}", sf=SF)
        sql(f"CREATE TABLE memory.{name}{props} AS SELECT orderkey * 0 + 7 "
            "AS k, orderkey AS v FROM tpch.tiny.lineitem "
            "WHERE orderkey <= 4000", sf=SF)
    sql("DROP TABLE IF EXISTS memory.one_key", sf=SF)
    sql("CREATE TABLE memory.one_key AS SELECT orderkey * 0 + 7 AS k, "
        "orderkey AS w FROM tpch.tiny.orders WHERE orderkey = 1", sf=SF)
    try:
        text = ("SELECT count(*) AS c, sum(a.v + b.w) AS s FROM memory.{t} a "
                "JOIN memory.one_key b ON a.k = b.k")
        session = {"join_distribution_type": "PARTITIONED"}
        meshed = sql(text.format(t="skew4"), sf=SF, session=session)
        single = sql(text.format(t="skew1"), sf=SF, session=session)
        assert meshed.rows() == single.rows() and meshed.rows()[0][0] > 0
        assert meshed.query_stats.counters["capacity_reruns"] > 0
        assert meshed.stats["exchange_slot_reruns"]["total"] > 0
        assert "capacity_reruns" not in single.stats
        again = sql(text.format(t="skew4"), sf=SF, session=session)
        assert again.rows() == single.rows()
        assert again.query_stats.counters["capacity_reruns"] == 0
    finally:
        for name in (*made, "one_key"):
            memory.drop_table(name, if_exists=True)


def _join_distributions(root):
    out, todo = [], [root]
    while todo:
        n = todo.pop()
        if isinstance(n, N.JoinNode):
            out.append(n.distribution)
        todo.extend(n.sources)
    return out


@pytest.mark.parametrize("build_rows,want", [
    (1 << 20, "broadcast"),          # at the limit: replicated
    ((1 << 20) + 1, "partitioned"),  # over it: repartitioned with its probe
    (None, "partitioned"),           # unknown size: repartitioned
])
def test_default_session_picks_the_distribution_from_the_estimate(
        server, monkeypatch, build_rows, want):
    """AUTOMATIC is the default under a mesh: a memory table's row count
    is the estimate (`memory.table_row_count`), stood in for here."""
    real = memory.table_row_count

    def rows(table, sf=0.0):
        if table != "x4_part":
            return real(table, sf)
        if build_rows is None:
            raise KeyError(table)
        return build_rows

    monkeypatch.setattr(memory, "table_row_count", rows)
    root = runner.prepare_plan(plan_sql(
        "SELECT l.orderkey, p.size FROM memory.x4_lineitem l "
        "JOIN memory.x4_part p ON l.partkey = p.partkey"), sf=SF)
    assert _join_distributions(root) == [want]


@pytest.mark.parametrize("value,want", [
    ("BROADCAST", "broadcast"), ("PARTITIONED", "partitioned")])
def test_the_session_values_keep_working(server, value, want):
    root = runner.prepare_plan(plan_sql(_text("q14", "x4_")), sf=SF,
                               session={"join_distribution_type": value})
    assert _join_distributions(root) == [want]
    done = execute(server.url, _text("q14", "x4_"),
                   session={"join_distribution_type": value})
    assert done.data == execute(server.url, _text("q14", "x1_")).data
    kinds = _counters(done)
    assert (kinds.get("exchange.hash", 0) >= 2) == (want == "partitioned")


@pytest.mark.parametrize("value", ["BROADCAST", "PARTITIONED"])
@pytest.mark.parametrize("template,joins", [("q3", 2), ("q14", 1)])
def test_every_join_is_answered_by_its_directory(server, template, joins,
                                                 value):
    """The build keys are primary keys (custkey, orderkey, partkey): a
    replicated build holds their whole span, a hash-exchanged one a
    quarter of it spread over the whole span, in a directory four times
    wider (`spread`). Either way the directory answers every lookup,
    with no search trip, on every chip, and the rows are one chip's."""
    done = execute(server.url, _text(template, "x4_"),
                   session={"join_distribution_type": value})
    assert done.data == execute(server.url, _text(template, "x1_")).data
    counters = _counters(done)
    assert (counters.get("exchange.hash", 0) >= 2) == (value == "PARTITIONED")
    assert counters["join_lookup_direct"] == joins
    assert counters["join_search_steps"] == 0


def test_a_receivers_capacity_follows_its_shard(server):
    """Slots are sized from the sender's shard: after a hash exchange a
    chip holds a little over what it sent, not the whole table's
    capacity."""
    from presto_tpu.parallel.exchange import slot_for
    for capacity in (1 << 12, 1 << 20, 45_000_000):
        for n in (2, 4, 8):
            assert n * slot_for(capacity, n) <= 1.5 * capacity
    assert slot_for(8, 4) == 8  # a small table keeps its whole capacity
    import jax
    from presto_tpu.exec.planner import compile_plan
    from presto_tpu.parallel.mesh import make_mesh
    mesh = make_mesh(4)
    scan, batch = _staged("x4_lineitem", "orderkey, partkey", mesh)
    plan = compile_plan(N.ExchangeNode(
        scan, kind="REPARTITION", scope="REMOTE", partition_channels=[0]),
        mesh)
    out, _ = jax.eval_shape(plan.fn, (batch,))
    assert batch.capacity < out.capacity <= 1.5 * batch.capacity
    done = _counters(execute(
        server.url, _text("q3", "x4_"),
        session={"join_distribution_type": "PARTITIONED"}))
    assert done["exchange.hash"] >= 3


@pytest.mark.parametrize("template", ["q3", "q6"])
def test_without_the_property_or_on_one_chip_it_is_the_parents_path(
        server, monkeypatch, template):
    """`workers = 4` in a one-device process and a table without the
    property give the same plan, plan-cache key and counters."""
    plain = runner.prepare_plan(plan_sql(_text(template, "x1_")), sf=SF)
    assert runner.placement_mesh(plain) is None
    assert not any(isinstance(n, N.ExchangeNode) and n.scope == "REMOTE"
                   for n in _walk(plain))
    spread = runner.prepare_plan(plan_sql(_text(template, "x4_")), sf=SF)
    assert runner.placement_mesh(spread).devices.size == 4
    monkeypatch.setattr(runner, "_process_chips", lambda: 1)
    one = runner.prepare_plan(plan_sql(_text(template, "x4_")), sf=SF)
    assert runner.placement_mesh(one) is None
    # the same plan but for the tables' names, so another cache key than
    # the meshed plan's and the same as a plain table's would be
    assert plan_fingerprint(one) != plan_fingerprint(spread)
    assert _shape(one) == _shape(plain)
    a = sql(_text(template, "x4_"), sf=SF)
    b = sql(_text(template, "x1_"), sf=SF)
    assert a.rows() == b.rows()
    volatile = ("plan_cache_hits", "plan_cache_misses", "xla_compiles",
                "compile_cache_reads")
    ca, cb = ({k: v for k, v in r.query_stats.counters.items()
               if k not in volatile} for r in (a, b))
    assert ca == cb and "mesh_chips" not in ca


def _walk(root):
    todo = [root]
    while todo:
        n = todo.pop()
        yield n
        todo.extend(n.sources)


def _shape(root):
    """The plan's node types, in order, with each scan's lanes."""
    return [(type(n).__name__, tuple(n.physical_dtypes or ())
             if isinstance(n, N.TableScanNode) else None)
            for n in _walk(root)]


@pytest.mark.parametrize("props,match", [
    ("workers = 0", "positive integer"),
    ("workers = 'x'", "positive integer"),
    ("workers = 4, replicas = 2", "no table property 'replicas'"),
    ("format = 'PARQUET'", "no table property 'format'"),
])
def test_a_bad_property_is_the_statements_error(server, props, match):
    with pytest.raises(QueryError, match=match):
        execute(server.url, f"CREATE TABLE memory.bad WITH ({props}) AS "
                "SELECT orderkey FROM tpch.tiny.orders")
    assert "bad" not in catalog("memory").SCHEMA


def test_the_batching_executor_passes_a_meshed_statement_by(server):
    from presto_tpu.exec.batching import get_batching_executor
    ex = get_batching_executor()
    assert ex._prepare_uncached(
        _text("q6", "x4_"), sf=SF, session={}, max_groups=None,
        join_capacity=None, catalog="tpch") == (None, None, None, None)
    prepared, template, _, _ = ex._prepare_uncached(
        _text("q6", "x1_"), sf=SF, session={}, max_groups=None,
        join_capacity=None, catalog="tpch")
    assert prepared is not None and template is not None


def test_a_ctas_from_a_spread_table_and_the_write_stay_whole(server):
    """The write is the paged CTAS on one chip; a CTAS that reads a
    spread table runs its SELECT over the mesh in one page."""
    sql("DROP TABLE IF EXISTS memory.copy4", sf=SF)
    made = sql("CREATE TABLE memory.copy4 WITH (workers = 2) AS SELECT "
               "orderkey, custkey FROM memory.x4_orders", sf=SF)
    try:
        assert made.rows()[0][0] == g.table_row_count("orders", SF)
        assert memory.table_workers("copy4") == 2
        got = sql("SELECT sum(orderkey), count(*) FROM memory.copy4", sf=SF)
        want = sql("SELECT sum(orderkey), count(*) FROM memory.x1_orders",
                   sf=SF)
        assert got.rows() == want.rows()
        assert got.query_stats.counters["mesh_chips"] == 2
    finally:
        memory.drop_table("copy4", if_exists=True)
