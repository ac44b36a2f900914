"""The resident tier (exec/resident.py): a whole-table scan of a memory
table takes its staged columns from HBM by table version.

On the CPU the device reports no memory limit, so the tier is used only
where a statement sets `hbm_budget_bytes`; every test here sets one but
the test of the unknown limit. Tables are TPC-H at sf 0.01, loaded by
CTAS under names of this file's own."""

import gc
import os
import sys
import threading
import weakref

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import layers, traffic  # noqa: E402
from presto_tpu.connectors import memory  # noqa: E402
from presto_tpu.connectors.tpch import generator as g  # noqa: E402
from presto_tpu.exec import resident  # noqa: E402
from presto_tpu.exec.memory import MemoryPool  # noqa: E402
from presto_tpu.exec.stats import StatsCollector, collecting  # noqa: E402
from presto_tpu.sql import sql  # noqa: E402

SF = 0.01
BUDGET = 1 << 30
TABLES = ("lineitem", "orders", "customer", "part")
PARAMS = {"q3": {"SEGMENT": "BUILDING", "DATE": "1995-03-15"},
          "q14": {"DATE_LO": "1995-09-01", "DATE_HI": "1995-10-01"}}
SUM_Q = "SELECT count(*), sum(quantity), max(shipdate) FROM memory.{t}"
COUNTERS = ("resident_hits", "resident_misses", "resident_bytes")


def _columns(table):
    return ", ".join(c for c, _ in g.TPCH_SCHEMA[table])


def _text(template, prefix):
    """The benchmark's statement over `<prefix><table>` memory tables."""
    text = traffic.statement_text(template, "memory.", PARAMS[template])
    for t in TABLES:
        text = text.replace(f"memory.{t}", f"memory.{prefix}{t}")
    return text


def _run(text, **kw):
    """`text` through `sql()` on a collector of the test's own: the
    result, its counters and its span records by name."""
    collector = StatsCollector()
    with collecting(collector):
        res = sql(text, sf=SF, **kw)
    spans = {}
    for rec in collector.spans:
        spans.setdefault(rec[0], []).append(rec)
    return res, res.query_stats.counters, spans


def _resident(counters):
    return {k: v for k, v in counters.items() if k in COUNTERS}


def _held_arrays():
    """Every array the tier holds, with its group."""
    held = []
    for gkey, group in resident.tier()._groups.items():
        for block in group.columns.values():
            held += [(gkey, a) for a in jax.tree_util.tree_leaves(block)]
        held += [(gkey, a) for a, _ in group.actives.values()]
    return held


@pytest.fixture(scope="module")
def tables():
    """The four tables as `rc_<t>` on one chip and `rc4_<t>` spread over
    four workers."""
    for t in TABLES:
        for name, props in ((f"rc_{t}", ""),
                            (f"rc4_{t}", " WITH (workers = 4)")):
            sql(f"DROP TABLE IF EXISTS memory.{name}", sf=SF)
            sql(f"CREATE TABLE memory.{name}{props} AS SELECT "
                f"{_columns(t)} FROM tpch.tiny.{t}", sf=SF)
    yield
    for t in TABLES:
        for name in (f"rc_{t}", f"rc4_{t}", f"rcx_{t}"):
            memory.drop_table(name, if_exists=True)


@pytest.fixture(autouse=True)
def fresh_tier():
    resident.tier().clear()
    yield
    resident.tier().clear()


def test_the_second_scan_is_a_hit(tables):
    """The first whole-table scan stages and keeps its columns; the
    second takes them as they lie: no `narrow_cast`, no `device_put`,
    the mask not read back, the same rows."""
    text = SUM_Q.format(t="rc_lineitem")
    first, c1, s1 = _run(text, hbm_budget_bytes=BUDGET)
    again, c2, s2 = _run(text, hbm_budget_bytes=BUDGET)
    assert first.rows() == again.rows() == sql(text, sf=SF).rows()
    assert c1["resident_misses"] == 2 and c1["resident_hits"] == 0
    assert c2["resident_hits"] == 2 and c2["resident_misses"] == 0
    assert "narrow_cast" in s1 and "device_put" in s1
    assert "narrow_cast" not in s2 and "device_put" not in s2
    assert set(again.query_stats.datapath) >= {"connector_read"}
    assert "device_put" not in again.query_stats.datapath
    (count,) = s2["scan_count"]
    assert count[3] == {"scans": 1, "bytes_read_back": 0}
    assert again.query_stats.stages["staging"].rows == \
        first.query_stats.stages["staging"].rows == \
        g.table_row_count("lineitem", SF)
    assert again.query_stats.stages["staging"].bytes == \
        first.query_stats.stages["staging"].bytes
    assert c2["resident_bytes"] == c1["resident_bytes"] \
        == resident.tier().held_bytes() > 0
    # rows and bytes, the operator records and narrowing as before
    assert again.stats["narrowed_bytes_saved"] == \
        first.stats["narrowed_bytes_saved"]


def test_another_column_of_the_table_is_added_to_its_group(tables):
    _run(SUM_Q.format(t="rc_lineitem"), hbm_budget_bytes=BUDGET)
    res, counters, spans = _run(
        "SELECT sum(quantity), sum(discount) FROM memory.rc_lineitem",
        hbm_budget_bytes=BUDGET)
    assert _resident(counters)["resident_hits"] == 1
    assert counters["resident_misses"] == 1
    assert len(spans["device_put"]) == 1
    assert res.rows() == sql("SELECT sum(quantity), sum(discount) FROM "
                             "memory.rc_lineitem", sf=SF).rows()
    assert list(resident.tier()._groups) == [
        ("memory", "rc_lineitem", memory.table_version("rc_lineitem"))]


@pytest.mark.parametrize("change", ["insert", "ctas", "drop"])
def test_a_new_version_drops_the_old_columns(tables, change):
    """INSERT, DROP, and CREATE TABLE AS over a dropped name each move
    the table to a new version: its resident columns go at once, and
    the next statement reads the new rows, staged afresh."""
    src = "SELECT {c} FROM tpch.tiny.lineitem WHERE orderkey < 100"
    sql(f"DROP TABLE IF EXISTS memory.rcx_lineitem", sf=SF)
    sql(f"CREATE TABLE memory.rcx_lineitem AS "
        f"{src.format(c=_columns('lineitem'))}", sf=SF)
    text = SUM_Q.format(t="rcx_lineitem")
    before, _, _ = _run(text, hbm_budget_bytes=BUDGET)
    old = memory.table_version("rcx_lineitem")
    held = [weakref.ref(a) for gkey, a in _held_arrays()
            if gkey[1] == "rcx_lineitem"]
    assert held
    if change == "insert":
        sql(f"INSERT INTO memory.rcx_lineitem "
            f"{src.format(c=_columns('lineitem'))}", sf=SF)
    else:
        sql("DROP TABLE memory.rcx_lineitem", sf=SF)
    if change == "ctas":
        sql(f"CREATE TABLE memory.rcx_lineitem AS SELECT "
            f"{_columns('lineitem')} FROM tpch.tiny.lineitem "
            f"WHERE orderkey < 50", sf=SF)
    assert memory.table_version("rcx_lineitem") > old
    assert not [gkey for gkey in resident.tier()._groups
                if gkey[1] == "rcx_lineitem"]
    gc.collect()
    assert not [ref for ref in held if ref() is not None]  # freed
    if change == "drop":
        return
    after, counters, spans = _run(text, hbm_budget_bytes=BUDGET)
    assert counters["resident_hits"] == 0 and "device_put" in spans
    assert after.rows() == sql(text, sf=SF).rows() != before.rows()
    n = before.rows()[0][0]
    assert after.rows()[0][0] == (2 * n if change == "insert" else
                                  sql("SELECT count(*) FROM tpch.tiny."
                                      "lineitem WHERE orderkey < 50",
                                      sf=SF).rows()[0][0])
    assert {gkey[2] for gkey in resident.tier()._groups
            if gkey[1] == "rcx_lineitem"} == \
        {memory.table_version("rcx_lineitem")}


def test_a_small_budget_evicts_the_least_recently_used_table(tables):
    """Room for one table's columns beside the largest program: the
    table scanned longest ago goes, whole, and every answer is the same
    as without the tier."""
    texts = [SUM_Q.format(t="rc_lineitem"),
             "SELECT count(*), sum(totalprice) FROM memory.rc_orders"]
    want = [sql(t, sf=SF).rows() for t in texts]
    each = []
    for text in texts:
        _run(text, hbm_budget_bytes=BUDGET)
        each.append(resident.tier().held_bytes() - sum(each))
    # the room is the budget less the largest program dispatched yet
    small = resident.tier()._largest_program + (max(each) + sum(each)) // 2
    resident.tier().clear()
    for text, rows, name in zip(texts + texts[:1], want + want[:1],
                                ["rc_lineitem", "rc_orders",
                                 "rc_lineitem"]):
        got, counters, _ = _run(text, hbm_budget_bytes=small)
        assert got.rows() == rows and counters["resident_hits"] == 0
        assert [gkey[1] for gkey in resident.tier()._groups] == [name]
    assert resident.tier().held_bytes() <= small


def test_a_program_larger_than_the_room_leaves_no_room(tables):
    text = SUM_Q.format(t="rc_lineitem")
    _run(text, hbm_budget_bytes=BUDGET)
    assert resident.tier().held_bytes() > 0
    resident.tier().note_program(BUDGET, BUDGET)
    assert resident.tier().held_bytes() == 0


def test_the_tier_makes_room_before_a_larger_program_runs(tables,
                                                         monkeypatch):
    """A statement whose program is larger than any before it finds the
    tier trimmed to the room beside that program when it is dispatched,
    not after: at every status read the program's planned bytes are
    known and the tier holds no more than the budget leaves."""
    from presto_tpu.exec import runner
    _run(SUM_Q.format(t="rc_lineitem"), hbm_budget_bytes=BUDGET)
    program = resident.tier()._largest_program
    small = resident.tier().held_bytes() + program
    seen = []
    real = runner._read_status

    def status(overflow, plan, expand_steps):
        seen.append((resident.tier().held_bytes(), dict(plan.hbm_bytes)))
        return real(overflow, plan, expand_steps)

    text = _text("q3", "rc_")
    want = sql(text, sf=SF).rows()
    monkeypatch.setattr(runner, "_read_status", status)
    res, _, _ = _run(text, hbm_budget_bytes=small)
    assert res.rows() == want
    assert seen
    for held, planned in seen:
        assert planned  # analysed before its dispatch
        assert held <= max(small - max(planned.values()), 0)
    assert max(max(p.values()) for _, p in seen) > program


def test_a_budget_is_capped_at_the_device_and_is_the_callers(monkeypatch):
    """A session's budget never exceeds the chip's `bytes_limit`, and
    each call trims to its own caller's room: a small budget of one
    statement does not stay to size the tier for the next."""
    import jax.numpy as jnp
    monkeypatch.setattr(resident, "_device_limit", lambda: 1000)
    assert resident.budget(None) == resident.budget(5000) == 1000
    assert resident.budget(500) == 500 and resident.budget("700") == 700
    tier_ = resident.ResidentTier()
    lane, mask = jnp.zeros(64, jnp.int32), jnp.ones(64, bool)
    each = lane.nbytes + mask.nbytes

    def keep(table, room):
        tier_.keep(("memory", table, 1, 64, None),
                   {("a", None): jnp.zeros(64, jnp.int32)},
                   jnp.ones(64, bool), 64, room)

    keep("t1", 10 * each)
    keep("t2", 10 * each)
    assert tier_.held_bytes() == 2 * each
    tier_.note_program(0, each)  # one caller's small room
    assert [gkey[1] for gkey in tier_._groups] == ["t2"]
    keep("t3", 10 * each)  # the next caller's room is its own
    assert tier_.held_bytes() == 2 * each
    tier_.note_program(0, None)  # no room known: nothing trimmed
    assert tier_.held_bytes() == 2 * each


def test_with_no_limit_known_the_tier_holds_nothing(tables):
    """The CPU backend reports no `bytes_limit`: without a session
    budget nothing is kept and no statement counts a hit or a miss."""
    assert resident.budget(None) is None and resident.budget(0) is None
    assert resident.budget("4096") == 4096
    for _ in range(2):
        _, counters, spans = _run(SUM_Q.format(t="rc_lineitem"))
        assert not _resident(counters) and "device_put" in spans
    assert resident.tier().held_bytes() == 0


def _bypasses(text, **kw):
    _, counters, _ = _run(text, **kw)
    assert not _resident(counters), text
    assert resident.tier().held_bytes() == 0


def test_generated_and_row_range_scans_bypass_the_tier(tables):
    """The generated catalog makes its rows at every scan; a streamed
    aggregation and a CTAS written in pages scan row ranges."""
    _bypasses("SELECT count(*), sum(quantity) FROM tpch.tiny.lineitem",
              hbm_budget_bytes=BUDGET)
    _bypasses("SELECT returnflag, sum(quantity) FROM memory.rc_lineitem "
              "GROUP BY returnflag", split_rows=8192,
              hbm_budget_bytes=BUDGET)
    sql("DROP TABLE IF EXISTS memory.rcx_lineitem", sf=SF)
    _bypasses(f"CREATE TABLE memory.rcx_lineitem AS SELECT "
              f"{_columns('lineitem')} FROM memory.rc_lineitem",
              hbm_budget_bytes=24_000_000)
    done = sql("SELECT count(*) FROM memory.rcx_lineitem", sf=SF)
    assert done.rows()[0][0] == g.table_row_count("lineitem", SF)


def test_a_lake_table_bypasses_the_tier(tables, tmp_path):
    pytest.importorskip("pyarrow")
    from presto_tpu.connectors import parquet
    parquet.set_warehouse(str(tmp_path))
    try:
        sql("CREATE TABLE hive.rc_orders WITH (format = 'PARQUET') AS "
            f"SELECT {_columns('orders')} FROM tpch.tiny.orders", sf=SF)
        for _ in range(2):
            _bypasses("SELECT count(*), sum(totalprice) FROM hive.rc_orders",
                      hbm_budget_bytes=BUDGET)
    finally:
        parquet.drop_table("rc_orders")
        parquet.set_warehouse(None)


def test_a_dynamic_filtered_scan_bypasses_the_tier(tables):
    """Q3's lineitem is pruned by a dimension side's key domains on
    the host: it stages as before, with `prune`. The dimension sides'
    own scans, whose program is traced anew in every statement, stage
    afresh too, inside `dynfilter`; the statement's whole scans of
    customer and orders are what the tier holds."""
    text = _text("q3", "rc_")
    _run(text, hbm_budget_bytes=BUDGET)
    res, counters, spans = _run(text, hbm_budget_bytes=BUDGET)
    assert res.rows() == sql(text, sf=SF).rows()
    assert res.stats.get("dynamic_filters", {}).get("total", 0) >= 1
    assert "prune" in spans and "device_put" in spans
    assert counters["resident_hits"] > 0 and counters["resident_misses"] == 0
    assert {gkey[1] for gkey in resident.tier()._groups} == \
        {"rc_customer", "rc_orders"}
    (dyn,) = spans["dynfilter"]
    in_dyn = [r for r in spans["device_put"] if dyn[1] <= r[1] <= dyn[2]]
    assert in_dyn and len(spans["device_put"]) == \
        len(spans["prune"]) + len(in_dyn)


def test_resident_leaves_are_never_donated(tables):
    """With `buffer_donation` on and the region executor dispatching
    one program a region, the scan leaves stay the tier's: after two
    statements every array it holds is alive and the rows are the
    undonated ones."""
    from presto_tpu.queries.tpch_sql import tpch_query
    q = tpch_query(6)
    text = q.text.replace("FROM lineitem", "FROM memory.rc_lineitem")
    off = sql(text, sf=SF, session={"fusion": False}, max_groups=q.max_groups)
    for _ in range(2):
        on = sql(text, sf=SF, max_groups=q.max_groups,
                 session={"fusion": False, "buffer_donation": True},
                 memory_pool=MemoryPool(1 << 34), hbm_budget_bytes=BUDGET)
        assert on.canonical_rows() == off.canonical_rows()
    held = _held_arrays()
    assert held and not any(a.is_deleted() for _, a in held)
    assert on.query_stats.counters["resident_hits"] > 0


def test_a_pool_registers_and_revokes_resident_bytes(tables):
    """Where the statement has a `MemoryPool` the tier's bytes are
    revocable there: a reservation the pool cannot hold beside them
    evicts them before it fails, and a statement after reads the same
    rows."""
    pool = MemoryPool(64 << 20)
    text = SUM_Q.format(t="rc_lineitem")
    first, _, _ = _run(text, hbm_budget_bytes=BUDGET, memory_pool=pool,
                       query_id="rc-pool-1")
    held = resident.tier().held_bytes()
    assert held > 0 and pool.reserved_bytes == held
    pool.reserve("other", pool.capacity - held // 2)
    assert resident.tier().held_bytes() == 0
    assert pool.revoked_bytes == held
    pool.free("other")
    assert pool.reserved_bytes == 0
    again, counters, _ = _run(text, hbm_budget_bytes=BUDGET,
                              memory_pool=pool, query_id="rc-pool-2")
    assert again.rows() == first.rows() and counters["resident_hits"] == 0


def test_a_pool_counts_resident_bytes_once(tables, monkeypatch):
    """A statement's reservation leaves out what the tier has
    registered of its scans, and the columns it stages for the tier
    move from its reservation to the tier's: at the dispatch of a
    miss and of a hit the pool holds the statement's planned bytes,
    never those and the tier's besides, and the tier's registration is
    what the device holds of the scan."""
    from presto_tpu.exec import runner
    pool = MemoryPool(1 << 34)
    seen = []
    real = runner._read_status

    def status(overflow, plan, expand_steps):
        seen.append((pool.reserved_bytes, pool.query_bytes(qid),
                     pool.query_peak_bytes(qid)))
        return real(overflow, plan, expand_steps)

    text = SUM_Q.format(t="rc_lineitem")
    want = sql(text, sf=SF).rows()
    monkeypatch.setattr(runner, "_read_status", status)
    for qid in ("rc-once-1", "rc-once-2"):
        res, counters, _ = _run(text, hbm_budget_bytes=BUDGET,
                                memory_pool=pool, query_id=qid)
        assert res.rows() == want
    assert counters["resident_hits"] == 2
    held = resident.tier().held_bytes()
    (miss_total, miss_own, planned), (hit_total, hit_own, hit_peak) = seen
    assert held > 0 and planned > held
    # the device holds the scan once, in the tier's registration
    assert miss_total - miss_own == hit_total - hit_own == held
    assert miss_own == hit_own == hit_peak == planned - held
    assert miss_total == hit_total == pool.peak_bytes == planned
    assert pool.reserved_bytes == held  # the statements let theirs go


def test_the_store_tells_its_listeners_after_its_lock(tables):
    """A version bump reaches `on_publish` listeners once the store's
    lock is let go: another thread can take it from inside one."""
    free = []

    def listener(table, version):
        if table != "rcx_listened":
            return
        got = []
        t = threading.Thread(target=lambda: got.append(
            memory._lock.acquire(timeout=5) and memory._lock.release()
            is None))
        t.start()
        t.join()
        free.append(got == [True])

    memory.on_publish(listener)
    try:
        sql("CREATE TABLE memory.rcx_listened AS SELECT orderkey FROM "
            "tpch.tiny.orders WHERE orderkey < 10", sf=SF)
        sql("DROP TABLE memory.rcx_listened", sf=SF)
    finally:
        memory._publish_listeners.remove(listener)
    assert free and all(free)


def test_the_batcher_leaves_memory_tables_to_the_tier(tables):
    """The batcher's replay of staged inputs keys on `data_version`;
    a memory table's template stages through the tier instead, so no
    second cache pins its columns."""
    from presto_tpu.exec import runner
    from presto_tpu.exec.batching import BatchingExecutor
    from presto_tpu.exec.planner import compile_plan
    from presto_tpu.sql import plan_sql
    ex = BatchingExecutor()
    for table, replayed in (("memory.rc_orders", False),
                            ("tpch.tiny.orders", True)):
        plan = compile_plan(runner.prepare_plan(
            plan_sql(f"SELECT sum(totalprice) FROM {table}"), sf=SF))
        first = ex._stage_inputs(table, plan, SF)
        assert (ex._stage_inputs(table, plan, SF) is first) == replayed
    assert [k[0] for k in ex._staged] == ["tpch.tiny.orders"]


@pytest.mark.parametrize("template", ["q3", "q14"])
def test_a_meshed_scan_hits_by_shard(tables, template):
    """Over four workers a resident scan is sharded as a fresh one
    (a shard a chip), `resident_bytes` is a chip's share, and the rows
    are one chip's."""
    single = sql(_text(template, "rc_"), sf=SF).rows()
    first, c1, _ = _run(_text(template, "rc4_"), hbm_budget_bytes=BUDGET)
    again, c2, spans = _run(_text(template, "rc4_"), hbm_budget_bytes=BUDGET)
    assert first.rows() == again.rows() == single
    assert c2["mesh_chips"] == 4
    assert c2["resident_hits"] > 0 and c2["resident_misses"] == 0
    assert "device_put" not in spans and "narrow_cast" not in spans
    held = _held_arrays()
    devices = {d.id for _, a in held for d in a.sharding.device_set}
    assert len(devices) == 4
    total = sum(a.nbytes for _, a in held)
    assert c2["resident_bytes"] == resident.tier().held_bytes() == total // 4
    for _, a in held:
        assert [s.data.shape[0] for s in a.addressable_shards] == \
            [a.shape[0] // 4] * 4


def test_two_concurrent_statements_agree(tables):
    text = _text("q14", "rc_")
    want = sql(text, sf=SF).rows()
    got, errors = [], []

    def one():
        try:
            for _ in range(3):
                got.append(sql(text, sf=SF, hbm_budget_bytes=BUDGET).rows())
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=one) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors and got == [want] * 6


def test_the_tier_keeps_its_books_under_contention():
    """Sixteen threads take, keep and move tables to new versions on
    one tier with a short switch interval: afterwards every group's
    bytes are what its arrays hold, every mask the tier counts rows by
    is one it holds, and the fullest chip fits the room."""
    import jax.numpy as jnp
    tier_ = resident.ResidentTier()
    lane = jnp.zeros(64, jnp.int32)
    mask = jnp.ones(64, bool)
    room = 40 * (lane.nbytes + mask.nbytes)

    def work(k):
        for i in range(200):
            table = f"t{(k + i) % 7}"
            version = i // 50
            place = ("memory", table, version, 64, None)
            _, kept = tier_.take(place, [("a", None), ("b", "int8")])
            tier_.keep(place, {("a", None): jnp.zeros(64, jnp.int32)},
                       jnp.ones(64, bool) if kept is None else kept[0],
                       64, room + (1 << 20), None)
            if i % 50 == 49:
                tier_.drop_older("memory", table, version + 1)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    actives = set()
    for gkey, group in tier_._groups.items():
        arrays = list(group.columns.values()) + \
            [a for a, _ in group.actives.values()]
        assert group.held == resident._bytes_by_device(arrays)
        actives |= {id(a) for a, _ in group.actives.values()}
        assert gkey[2] >= tier_._newest.get(gkey[:2], 0)
    assert set(tier_._rows) == actives
    assert tier_.held_bytes() <= room + (1 << 20)


def test_a_snapshot_pairs_one_version_with_its_rows(tables):
    version, rows, values, nulls = memory.scan_snapshot(
        "rc_orders", ["orderkey", "totalprice"])
    assert version == memory.table_version("rc_orders")
    assert rows == len(values[0]) == len(nulls[1]) \
        == g.table_row_count("orders", SF)
    with pytest.raises(ValueError):
        values[0][0] = 1  # published arrays are read in place


# -- the two readers ----------------------------------------------------


def _recorded(*counters):
    return {"statements": [
        {"template": "q", "wall_s": 1.0, "traced": True,
         "stats": {"state": "FINISHED", "queryStats": {
             "stages": {"execute": {"wall_us": 900_000, "invocations": 1}},
             "counters": c}}} for c in counters],
        "trace": None, "device_kind": "TPU v5 lite",
        "cache_misses_in_window": 0}


@pytest.mark.parametrize("counters,pct,mb", [
    # a warmed window: every column found, the tier's bytes after each
    (({"resident_hits": 6, "resident_misses": 0,
       "resident_bytes": 1_500_000_000},
      {"resident_hits": 9, "resident_misses": 0,
       "resident_bytes": 1_700_000_000}), 100.0, 1_600.0),
    # the first statements of a process
    (({"resident_hits": 0, "resident_misses": 6, "resident_bytes": 2e8},
      {"resident_hits": 2, "resident_misses": 0, "resident_bytes": 4e8}),
     25.0, 300.0),
    # a statement that scans no memory table carries none: left out
    (({"resident_hits": 1, "resident_misses": 1, "resident_bytes": 1e6},
      {"plan_cache_hits": 1}), 50.0, 1.0),
    # the parent's program, or a cell whose scans all bypass the tier
    (({"plan_cache_hits": 1}, {}), None, None),
])
def test_the_readers_read_the_counters(counters, pct, mb):
    run = _recorded(*counters)
    got = layers.read_metric("resident_hit_pct", run)
    assert got == (pytest.approx(pct) if pct is not None else None)
    got = layers.read_metric("resident_mb", run)
    assert got == (pytest.approx(mb) if mb is not None else None)


def test_the_readers_read_a_recorded_statement(tables):
    """The counters as a statement records them reach both readers."""
    text = SUM_Q.format(t="rc_orders").replace("quantity", "totalprice") \
        .replace("shipdate", "orderdate")
    docs = [_run(text, hbm_budget_bytes=BUDGET)[0].query_stats.to_json()
            for _ in range(2)]
    run = {"statements": [{"stats": {"queryStats": d}} for d in docs]}
    assert layers.read_metric("resident_hit_pct", run) == 50.0
    assert layers.read_metric("resident_mb", run) == pytest.approx(
        resident.tier().held_bytes() * 1e-6)
    assert np.isfinite(layers.read_metric("resident_mb", run))
