"""One span seam from POST to last row (exec/stats.py).

Every statement is covered by the program's own spans: recorded on one
collector per statement, carried in ``QueryStats`` over the protocol,
shipped with parent edges to ``/v1/trace`` and written as
``presto:<name>`` into the profiler's trace; device ops are named by
operator and ``ops/`` function, their region by the dispatch's span.
"""

import glob
import json
import os
import time
import urllib.request

import jax
import pytest

from presto_tpu.client import execute
from presto_tpu.connectors import memory
from presto_tpu.server.statement import StatementServer
from presto_tpu.server.tracing import RecordingTracer, get_tracer, \
    set_tracer
from presto_tpu.sql import plan_sql, sql

# what tiles the server-side life of a statement, in order (`write`
# only where there is a sink, `batch` only for a hot text shape)
TOP_LEVEL = ("queue", "batch", "plan", "dynfilter", "staging", "execute",
             "fetch", "finish", "render", "write")

SELECT = ("SELECT orderkey, sum(quantity) FROM lineitem "
          "WHERE quantity < 30 GROUP BY orderkey "
          "ORDER BY orderkey LIMIT 5")
CTAS = ("CREATE TABLE memory.spans_ctas AS "
        "SELECT orderkey, quantity FROM lineitem WHERE quantity < 10")
Q3 = """
SELECT l.orderkey, sum(l.extendedprice * (1 - l.discount)) AS revenue,
       o.orderdate, o.shippriority
FROM customer c
JOIN orders o ON c.custkey = o.custkey
JOIN lineitem l ON l.orderkey = o.orderkey
WHERE c.mktsegment = 'BUILDING'
  AND o.orderdate < date '1995-03-15'
  AND l.shipdate > date '1995-03-15'
GROUP BY l.orderkey, o.orderdate, o.shippriority
ORDER BY revenue DESC, o.orderdate
LIMIT 10
"""


def _statement(url, text):
    """One statement over the protocol: its final stats document, its
    /v1/trace spans and the client's wall in microseconds."""
    t0 = time.time()
    done = execute(url, text)
    wall_us = (time.time() - t0) * 1e6
    with urllib.request.urlopen(f"{url}/v1/trace/{done.query_id}") as r:
        spans = json.load(r)["spans"]
    return {"stats": done.stats["queryStats"], "spans": spans,
            "wall_us": wall_us}


@pytest.fixture(scope="module")
def served():
    """SELECT three times (the third is a hot shape: batching looks at
    it) and one CTAS, through a StatementServer at sf 0.01."""
    from presto_tpu.exec.plan_cache import clear_plan_cache
    before = get_tracer()
    set_tracer(RecordingTracer())
    clear_plan_cache()
    try:
        with StatementServer(sf=0.01) as srv:
            runs = [_statement(srv.url, SELECT) for _ in range(3)]
            ctas = _statement(srv.url, CTAS)
        yield {"select": runs, "ctas": ctas}
    finally:
        set_tracer(before)
        memory.drop_table("spans_ctas", if_exists=True)


@pytest.mark.parametrize("name", [n for n in TOP_LEVEL if n != "write"]
                         + ["dispatch", "device_wait", "plan.sql",
                            "plan.prepare", "batch.prepare", "batch.wait",
                            "scan_count"])
def test_protocol_stats_carry_every_stage(served, name):
    stages = served["select"][-1]["stats"]["stages"]
    assert name in stages, sorted(stages)
    assert stages[name]["invocations"] >= 1
    assert stages[name]["wall_us"] >= 0


def _tree(spans):
    by_id = {s["spanId"]: s for s in spans}
    return by_id, [(s, by_id[s["parentId"]]) for s in spans
                   if s["parentId"] in by_id]


def _assert_tiles(run):
    """Children lie inside their parents, the top level sums to no
    more than the client's wall and no two top-level spans overlap."""
    _by_id, edges = _tree(run["spans"])
    inside = [(c["name"], p["name"]) for c, p in edges
              if c["name"].startswith("stage.")
              and not (p["startUs"] <= c["startUs"]
                       and c["endUs"] <= p["endUs"])]
    assert not inside, inside
    # the top level sums to no more than POST-to-FINISHED, and nothing
    # is counted twice: children are not in the sum, `compile` is the
    # synthetic span carved out of `execute`
    stages = run["stats"]["stages"]
    top = sum(stages[n]["wall_us"] for n in TOP_LEVEL if n in stages)
    assert 0 < top <= run["wall_us"], (top, run["wall_us"])
    # the spans of the top level do not overlap each other
    tops = sorted((s["startUs"], s["endUs"], s["name"])
                  for s in run["spans"]
                  if s["name"] in {f"stage.{n}" for n in TOP_LEVEL})
    overlaps = [(a, b) for a, b in zip(tops, tops[1:]) if b[0] < a[1]]
    assert not overlaps, overlaps


@pytest.mark.parametrize("which", ["select", "ctas"])
def test_children_inside_parents_and_top_level_tiles(served, which):
    _assert_tiles(served[which][-1] if which == "select"
                  else served[which])


@pytest.mark.parametrize("child,parent", [
    ("stage.plan.sql", "stage.plan"),
    ("stage.plan.prepare", "stage.plan"),
    ("stage.connector_read", "stage.staging"),
    ("stage.device_put", "stage.staging"),
    ("stage.scan_count", "stage.staging"),
    ("stage.finish", "query"),
    ("stage.dispatch", "stage.execute"),
    ("stage.device_wait", "stage.execute"),
    ("stage.batch.wait", "stage.batch"),
    ("stage.staging", "query"),
    ("stage.queue", "query"),
    ("stage.render", "query"),
])
def test_trace_serves_the_spans_with_parent_edges(served, child, parent):
    run = served["select"][-1]
    by_id, edges = _tree(run["spans"])
    # every parentId of the trace is a span of the trace (the root's
    # own parent aside), and the named edge is among them
    dangling = [s["name"] for s in run["spans"]
                if s["parentId"] is not None and s["parentId"] not in by_id]
    assert not dangling, dangling
    assert (child, parent) in {(c["name"], p["name"]) for c, p in edges}
    # /v1/trace and the protocol's stats are one record: as many spans
    # of a stage as the stage counts invocations
    name = child[len("stage."):]
    if name in run["stats"]["stages"]:
        assert sum(1 for s in run["spans"] if s["name"] == child) == \
            run["stats"]["stages"][name]["invocations"]
    if child in ("stage.dispatch", "stage.device_wait"):
        assert all(s["attributes"].get("region") == "R0"
                   for s in run["spans"] if s["name"] == child)


def test_ctas_carries_write_beside_the_inner_select(served):
    stages = served["ctas"]["stats"]["stages"]
    for name in ("queue", "plan", "staging", "execute", "fetch", "write",
                 "render"):
        assert name in stages, sorted(stages)
    # the inner SELECT ran once, on the statement's collector: none of
    # its stages is counted twice, and `write` is a sibling of theirs
    for name in ("staging", "execute", "fetch", "write", "dispatch"):
        assert stages[name]["invocations"] == 1, (name, stages[name])
    by_id, edges = _tree(served["ctas"]["spans"])
    parents = {c["name"]: p["name"] for c, p in edges}
    assert parents["stage.write"] == "query"
    assert parents["stage.staging"] == "query"


def test_library_call_collects_on_its_own_collector():
    res = sql(SELECT, sf=0.01)
    stages = res.query_stats.stages
    for name in ("plan", "plan.sql", "plan.prepare", "dynfilter",
                 "staging", "execute", "dispatch", "device_wait", "fetch"):
        assert name in stages, sorted(stages)
    # no server: nothing queued, batched or rendered
    assert not {"queue", "batch", "render"} & set(stages)
    assert stages["dispatch"].wall_us + stages["device_wait"].wall_us \
        <= stages["execute"].wall_us


def test_second_run_hits_the_plan_cache_and_compiles_nothing(served):
    first, second = (r["stats"]["counters"] for r in served["select"][:2])
    assert first.get("plan_cache_misses", 0) >= 1
    assert first.get("xla_compiles", 0) >= 1
    assert second.get("plan_cache_hits", 0) > 0
    assert second.get("plan_cache_misses", 0) == 0
    assert second.get("xla_compiles", 0) == 0
    # programs enqueued, reruns included: the dispatch stage's count
    for run in served["select"][:2]:
        assert run["stats"]["stages"]["dispatch"]["invocations"] == 1


def test_spans_are_written_into_the_profilers_trace(tmp_path):
    """Under jax.profiler.trace the host plane holds the statement's
    spans as presto:<name>, inside the statement's interval (read with
    ProfileData, as the benchmark's trace_reduce.read_xplane does)."""
    from jax.profiler import ProfileData
    sql(SELECT, sf=0.01)  # warm: the traced run compiles nothing
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("test:statement"):
            sql(SELECT, sf=0.01)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert found
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for plane in ProfileData.from_file(found[-1]).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    (_n, s0, s1, _st), = [e for e in events if e[0] == "test:statement"]
    for name in ("presto:plan", "presto:staging", "presto:connector_read",
                 "presto:dispatch", "presto:device_wait"):
        mine = [e for e in events if e[0] == name]
        assert mine, (name, sorted({e[0] for e in events
                                    if e[0].startswith("presto:")}))
        assert all(s0 <= a and b <= s1 for _n, a, b, _st in mine), name
    assert {e[3].get("region") for e in events
            if e[0] == "presto:dispatch"} == {"R0"}


def _region_texts(text):
    """Plan `text` anew and lower each of its region programs."""
    from presto_tpu.exec.planner import compile_plan
    from presto_tpu.exec.regions import partition_regions
    from presto_tpu.exec.runner import _scan_batch, prepare_plan
    root = prepare_plan(plan_sql(text), sf=0.01)
    out, ids = [], set()

    def walk(n):
        ids.add(n.id)
        for s in n.sources:
            walk(s)
    walk(root)
    for reg in partition_regions(root, sf=0.01).regions:
        plan = compile_plan(reg.root)
        batches = tuple(_scan_batch(s, 0.01, None, 8)
                        for s in plan.scan_nodes)
        out.append(jax.jit(plan.fn).lower(batches)
                   .as_text(debug_info=True))
    return out, ids


def test_device_ops_are_named_by_operator_and_function():
    """Q3's region programs, lowered twice from two plannings, give the
    same debug text, and it names scopes by structure, never by the
    process-wide node id."""
    import re
    # one call site: the debug text also holds the caller's own line
    (first, ids1), (second, ids2) = [_region_texts(Q3) for _ in range(2)]
    assert ids1.isdisjoint(ids2)  # two plannings: every node id differs
    assert first == second
    text = "\n".join(first)
    scopes = set(re.findall(r'loc\("jit\(run\)/([^"]*)"', text))
    # the root's scope is the outermost: what a program is named by
    # follows from its plan alone, never from who asked for it
    named = {s for s in scopes if "Node." in s}
    assert named and all(s.startswith("OutputNode.0") for s in named)
    assert "region." not in "".join(scopes)
    parts = {p for s in scopes for p in s.split("/")}
    for want in (r"JoinNode\.\d+", r"AggregationNode\.\d+",
                 r"TopNNode\.\d+", "hash_join", "_sort_build",
                 "_group_by_sorted", "lex_sort", "top_n"):
        assert any(re.fullmatch(want, p) for p in parts), (want, parts)
    # the k of <NodeType>.<k> is the pre-order index: small, dense
    ks = sorted({int(p.rsplit(".", 1)[1]) for p in parts
                 if re.fullmatch(r"[A-Za-z]+Node\.\d+", p)})
    assert ks[-1] < len(ids1)


def test_a_plan_cache_hit_under_another_tag_is_not_mislabelled():
    """One compiled program serves every region with its fingerprint:
    the region is named by the `dispatch` and `device_wait` spans of
    each call, which are true per dispatch, and not by the program."""
    from presto_tpu.exec import runner
    from presto_tpu.exec.plan_cache import cached_compile
    from presto_tpu.exec.stats import RuntimeStats, StatsCollector, \
        collecting
    root = runner.prepare_plan(plan_sql(SELECT), sf=0.01)
    seen = []
    for tag in ("R0", "R7"):
        collector = StatsCollector()
        with collecting(collector):
            plan, jfn, lock = cached_compile(root, None, 1 << 16)
            batches = [runner._scan_batch(s, 0.01, None, 8)
                       for s in plan.scan_nodes]
            runner._dispatch_ladder(
                plan.root, plan, jfn, lock, batches, None, 1 << 16, True,
                None, RuntimeStats(), True, False, None, tag)
        seen.append((jfn, collector.stats.counters,
                     [(name, attrs["region"])
                      for name, _s, _e, attrs, _i, _p in collector.spans
                      if "region" in attrs]))
    (fn0, first, spans0), (fn7, second, spans7) = seen
    assert fn0 is fn7 and second.get("plan_cache_hits") == 1
    assert spans0 == [("dispatch", "R0"), ("device_wait", "R0")]
    assert spans7 == [("dispatch", "R7"), ("device_wait", "R7")]


Q6 = ("SELECT sum(extendedprice * discount) FROM lineitem "
      "WHERE shipdate >= date '1994-01-01' AND shipdate < date '1995-01-01' "
      "AND discount BETWEEN 0.05 AND 0.07 AND quantity < 24")


@pytest.mark.parametrize("text,joins", [(Q3, 2), (Q6, 0)],
                         ids=["q3", "q6"])
def test_join_search_steps_ride_the_status_word(text, joins):
    """The trips `_match_ranges` took and the lookups its directory
    answered alone leave the device in the word the program already
    returns (bits 8 and up) and land in the statement's counters over
    the protocol; a statement without a join has neither. Q3's build
    keys (customer, orders) are dense: both directories answer, with no
    trip."""
    with StatementServer(sf=0.01) as srv:
        stats = execute(srv.url, text).stats["queryStats"]
    steps = stats["counters"].get("join_search_steps")
    direct = stats["counters"].get("join_lookup_direct")
    if not joins:
        assert steps is None and direct is None
    else:
        assert (steps, direct) == (0, joins)
    assert stats["stages"]["device_wait"]["invocations"] == \
        stats["stages"]["dispatch"]["invocations"]


@pytest.mark.parametrize("text,joins", [(Q3, 2), (Q6, 0)],
                         ids=["q3", "q6"])
def test_join_expand_steps_ride_the_compiled_plan(text, joins):
    """The gather trips a slot of a join's expansion takes to find its
    row (`ops/join._slot_rows`) are a constant of the program: noted
    where it is traced, kept with the compiled plan, and in the
    counters of every statement that dispatches it, a plan-cache hit
    too: log2 of the probe's rows a slot, 0 where (as at this scale) the
    default capacity holds more slots than the probe has rows; a
    statement without a join has no such counter."""
    with StatementServer(sf=0.01) as srv:
        first = execute(srv.url, text).stats["queryStats"]["counters"]
        again = execute(srv.url, text).stats["queryStats"]["counters"]
    assert first.get("join_expand_steps") == (0 if joins else None)
    assert again.get("join_expand_steps") == first.get("join_expand_steps")
    assert again["plan_cache_hits"] >= 1


def test_a_hard_overflow_still_reruns_under_the_steps():
    """Bits 0-1 of the status word stay the ladder's: a join capacity
    too small by 16x reruns once, at the power of two at or above the
    rows the overflowed dispatch counted (where the one scale climbed
    x4 twice), and answers as the roomy run does; each of the two
    dispatches adds its trips."""
    text = ("SELECT count(*), sum(o.totalprice) FROM orders o "
            "JOIN customer c ON o.custkey = c.custkey "
            "WHERE c.nationkey < 20")
    roomy = sql(text, sf=0.01)
    tight = sql(text, sf=0.01, join_capacity=1024)
    assert tight.rows() == roomy.rows()
    assert roomy.query_stats.stages["dispatch"].invocations == 1
    assert tight.query_stats.stages["dispatch"].invocations == 2
    assert tight.stats["capacity_reruns"]["count"] == 1
    assert roomy.query_stats.counters["join_lookup_direct"] == 1
    assert tight.query_stats.counters["join_lookup_direct"] == 2
    assert tight.query_stats.counters["join_search_steps"] == 0
    # 11,976 probe rows a slot: blocks of 1 at 65,536 slots; of 16 and
    # 1 at 1,024 and 16,384
    assert roomy.query_stats.counters["join_expand_steps"] == 0
    assert tight.query_stats.counters["join_expand_steps"] == 4 + 0


# -- the templates the cells send ---------------------------------------

# what the four templates read, column by column (the cells load whole
# records; a test loads what its statements touch)
CELL_TABLES = {
    "lineitem": ("orderkey, partkey, quantity, extendedprice, discount, "
                 "returnflag, linestatus, shipdate"),
    "orders": "orderkey, custkey, orderdate, shippriority",
    "customer": "custkey, mktsegment",
    "part": "partkey, type",
}
CELL_STATEMENTS = ("q14-mem", "q3-mem", "q6-mem", "q1-mem", "q6-gen")
HOPS = ("connector_read", "narrow_cast", "device_put")


def _cell_text(name):
    """`q14-mem` is TPC-H Q14 (presto_tpu/queries/tpch_sql.py's text)
    over the memory tables, `q6-gen` Q6 over the generated catalog."""
    import re
    from presto_tpu.queries.tpch_sql import tpch_query
    number, where = name.split("-")
    text = tpch_query(int(number[1:])).text
    if where == "mem":
        text = re.sub(r"\b(FROM|JOIN) (lineitem|orders|customer|part)\b",
                      r"\1 memory.cells_\2", text)
    return text


@pytest.fixture(scope="module")
def cells():
    """Q14, Q3, Q6 and Q1 over memory tables loaded by CTAS, and Q6
    over the generated catalog, through a StatementServer at sf 0.01:
    the templates `mem_sf1.join`, `mem_sf10.join` and `gen_sf1.scan`
    send and the first waiting cell's, one statement each."""
    before = get_tracer()
    set_tracer(RecordingTracer())
    try:
        with StatementServer(sf=0.01) as srv:
            for table, columns in CELL_TABLES.items():
                execute(srv.url, f"CREATE TABLE memory.cells_{table} AS "
                                 f"SELECT {columns} FROM tpch.{table}")
            runs = {name: _statement(srv.url, _cell_text(name))
                    for name in CELL_STATEMENTS}
        yield runs
    finally:
        set_tracer(before)
        for table in CELL_TABLES:
            memory.drop_table(f"cells_{table}", if_exists=True)


@pytest.mark.parametrize("hop", HOPS)
@pytest.mark.parametrize("name", CELL_STATEMENTS)
def test_hop_walls_are_span_walls(cells, name, hop):
    """A hop is timed once (`datapath.timed_hop`: the span's own two
    clock readings) for two sinks: the wall in `datapath.<hop>` is the
    sum of its spans' walls to rounding (a span's ends are rounded to
    the microsecond apart: 1 us an invocation), with as many
    invocations as spans, and where one is absent so is the other."""
    run = cells[name]
    spans = [s for s in run["spans"] if s["name"] == f"stage.{hop}"]
    ledger = run["stats"]["datapath"].get(hop)
    if ledger is None:
        assert not spans
        return
    assert ledger["invocations"] == len(spans) >= 1
    walls = sum(s["endUs"] - s["startUs"] for s in spans)
    assert abs(ledger["wall_us"] - walls) <= len(spans), \
        (ledger["wall_us"], walls)
    assert ledger["max_wall_us"] <= ledger["wall_us"]


def test_every_cell_statement_stages_and_puts(cells):
    """The absent-together branch above is not the only one taken:
    every template reads its connector and puts its columns."""
    for name, run in cells.items():
        for hop in ("connector_read", "device_put"):
            assert hop in run["stats"]["datapath"], (name, hop)


@pytest.mark.parametrize("name", CELL_STATEMENTS)
def test_top_level_tiles_on_cell_templates(cells, name):
    """The tiling holds on statements with joins, `dynfilter`, top-N
    and string keys, not on one SELECT only."""
    run = cells[name]
    _assert_tiles(run)
    stages = run["stats"]["stages"]
    for stage_name in ("queue", "plan", "staging", "execute", "fetch",
                       "render"):
        assert stage_name in stages, (name, sorted(stages))


@pytest.mark.parametrize("name", CELL_STATEMENTS)
def test_kernel_hop_is_device_wait(cells, name):
    """`device_wait` is the one record of a dispatch's device side:
    with `dispatch` it lies inside `execute`, and the datapath's
    `kernel` hop (the same interval less the compile) is no longer
    than what `execute` holds beside the compile."""
    stats = cells[name]["stats"]
    stages = stats["stages"]
    execute_us = stages["execute"]["wall_us"]
    assert stages["dispatch"]["invocations"] == \
        stages["device_wait"]["invocations"] >= 1
    assert stages["dispatch"]["wall_us"] + \
        stages["device_wait"]["wall_us"] <= execute_us
    compile_us = stages.get("compile", {}).get("wall_us", 0)
    assert compile_us <= execute_us
    kernel = stats["datapath"]["kernel"]
    assert kernel["invocations"] == 1
    assert kernel["wall_us"] <= execute_us - compile_us + 1


def _named(run, name):
    return sorted((s for s in run["spans"] if s["name"] == f"stage.{name}"),
                  key=lambda s: s["startUs"])


@pytest.mark.parametrize("name", CELL_STATEMENTS)
def test_scan_count_closes_staging_before_execute_opens(cells, name):
    """What was staged is counted under `staging`, as its last child:
    one `scan_count` a statement, over as many scans as were put, its
    `bytes_read_back` the `active` masks read back whole to count rows
    (a byte a row of capacity: 60,000 for lineitem at this scale where
    nothing pruned it); `execute` opens after it."""
    run = cells[name]
    (count,), (staging,), (execute_,) = (
        _named(run, n) for n in ("scan_count", "staging", "execute"))
    assert count["parentId"] == staging["spanId"]
    assert staging["startUs"] <= count["startUs"] \
        and count["endUs"] <= staging["endUs"] <= execute_["startUs"]
    puts = [p for p in _named(run, "device_put")  # not `dynfilter`'s
            if p["parentId"] == staging["spanId"]]
    assert count["attributes"]["scans"] == len(puts) >= 1
    assert all(p["endUs"] <= count["startUs"] for p in puts)
    read_back = count["attributes"]["bytes_read_back"]
    staged = run["stats"]["stages"]["staging"]
    assert read_back >= staged["rows"] > 0  # a live row has a mask byte
    if name.startswith("q6"):
        assert read_back == 60_000
    assert run["stats"]["stages"]["scan_count"]["invocations"] == 1


@pytest.mark.parametrize("name", ["q6-mem", "q1-mem"])
def test_a_resident_hit_stages_without_a_put(cells, name):
    """Where a budget is known (here the statement's `hbm_budget_bytes`)
    a whole-table scan of a memory table goes through the resident
    tier: a statement's second run takes every column from HBM, so its
    `staging` holds the store's snapshot (`connector_read`) and
    `scan_count` and no `narrow_cast` or `device_put`, and `scan_count`
    reads no mask back; the rows it counts are the first run's."""
    from presto_tpu.exec.resident import tier
    tier().clear()
    try:
        (first, s1), (again, s2) = (
            _library_spans(_cell_text(name), hbm_budget_bytes=1 << 30)
            for _ in range(2))
    finally:
        tier().clear()
    assert first.rows() == again.rows()
    assert "device_put" in s1 and "narrow_cast" in s1
    assert "device_put" not in s2 and "narrow_cast" not in s2
    (staging,), (count,) = s2["staging"], s2["scan_count"]
    (read,) = s2["connector_read"]
    assert read[5] == count[5] == staging[4]  # children of `staging`
    assert count[3] == {"scans": 1, "bytes_read_back": 0}
    assert s1["scan_count"][0][3]["bytes_read_back"] == 0
    counters = again.query_stats.counters
    assert counters["resident_hits"] >= 1 and counters["resident_misses"] == 0
    assert again.query_stats.stages["staging"].rows == \
        first.query_stats.stages["staging"].rows > 0


@pytest.mark.parametrize("name", CELL_STATEMENTS)
def test_finish_is_top_level_and_follows_fetch(cells, name):
    """From `fetch`'s exit to the return into the server: one `finish`
    under the statement's root, after `fetch`, before `render`."""
    run = cells[name]
    (fetch,), (finish,), (render,), (staging,) = (
        _named(run, n) for n in ("fetch", "finish", "render", "staging"))
    # a sibling of `staging` and `render`: the statement's root, which
    # the server ships last, may not be in the trace yet
    assert finish["parentId"] == staging["parentId"] == render["parentId"]
    assert finish["parentId"] not in {
        s["spanId"] for s in run["spans"] if s["name"].startswith("stage.")}
    assert fetch["endUs"] <= finish["startUs"] \
        and finish["endUs"] <= render["startUs"]
    assert run["stats"]["stages"]["finish"]["invocations"] == 1


def _library_spans(text, **kw):
    """`text` through `sql()` on a collector of the test's own: the
    result and the collector's span records by name."""
    from presto_tpu.exec.stats import StatsCollector, collecting
    collector = StatsCollector()
    with collecting(collector):
        res = sql(text, sf=0.01, **kw)
    by_name = {}
    for rec in sorted(collector.spans, key=lambda r: r[1]):
        by_name.setdefault(rec[0], []).append(rec)
    return res, by_name


def test_prune_is_the_dynamic_filters_host_side(cells):
    """A dynamic-filtered scan reads its split, prunes it on the host
    and only then re-proves and puts what is left: `prune` lies between
    that scan's `connector_read` and `narrow_cast`, under `staging`,
    and its `rows_in` - `rows_kept` are the rows the filter pruned; a
    statement with no dynamic filter has no such span."""
    res, spans = _library_spans(_cell_text("q3-mem"))
    prunes = spans["prune"]
    assert prunes and res.query_stats.stages["prune"].invocations \
        == len(prunes)
    (staging,) = spans["staging"]
    pruned = 0
    for name, start, end, attrs, _span, parent in prunes:
        assert parent == staging[4]
        pruned += attrs["rows_in"] - attrs["rows_kept"]
        reads = [r for r in spans["connector_read"] if r[2] <= start]
        narrows = [r for r in spans["narrow_cast"] if r[1] >= end]
        assert reads and narrows
        # nothing of the scan's other hops lies between them
        between = [r for hop in HOPS for r in spans.get(hop, ())
                   if reads[-1][2] < r[1] and r[2] < narrows[0][1]]
        assert not between, between
    assert pruned == res.stats["dynamic_filter_rows_pruned"]["total"] > 0
    assert sum(a["rows_kept"] for _n, _s, _e, a, *_r in prunes) == \
        res.stats["dynamic_filter_rows_staged"]["total"]
    # over the protocol: in Q3's stages, not in Q6's
    assert "prune" in cells["q3-mem"]["stats"]["stages"]
    assert _named(cells["q3-mem"], "prune")
    for name in ("q6-mem", "q6-gen"):
        assert "prune" not in cells[name]["stats"]["stages"]
        assert not _named(cells[name], "prune")
    off, spans_off = _library_spans(
        _cell_text("q3-mem"), session={"dynamic_filtering": False})
    assert "prune" not in spans_off
    assert off.rows() == res.rows()


# a probe of 60,000 rows is four times as long as 4,096 slots; at the
# default 65,536 no join of this scale has a second form
TIGHT = {"join_capacity": "4096"}


@pytest.mark.parametrize("name,session,compacted", [
    # 1.2% of lineitem pass l_shipdate: some 750 rows fit 4,096
    ("q14-mem", TIGHT, 1),
    # at this scale the dynamic filter prunes Q3's lineitem to 29,104
    # rows before it is staged, and what passes l_shipdate of those fits
    ("q3-mem", TIGHT, 1),
    ("q14-mem", None, 0), ("q3-mem", None, 0),
    ("q6-mem", TIGHT, None), ("q6-gen", None, None)],
    ids=["q14-tight", "q3-tight-pruned", "q14", "q3", "q6-tight", "q6-gen"])
def test_join_probe_compacted_rides_the_status_word(cells, name, session,
                                                    compacted):
    """How many of a statement's joins looked up only the probe rows
    that can emit (`ops/join._probe_side`: the device's choice, by its
    own count) leaves the device above the search trips in the word the
    program already returns: in the counters of every statement whose
    program holds a join, 0 too and on a plan-cache hit too; a
    statement without a join has no such counter."""
    with StatementServer(sf=0.01) as srv:
        first, again = (
            execute(srv.url, _cell_text(name), session=session)
            .stats["queryStats"] for _ in range(2))
    for stats in (first, again):
        assert stats["counters"].get("join_probe_compacted") == compacted
        assert stats["stages"]["device_wait"]["invocations"] == \
            stats["stages"]["dispatch"]["invocations"]  # one host read each
    assert again["counters"]["plan_cache_hits"] >= 1
    if session is None and compacted is not None:
        assert cells[name]["stats"]["counters"]["join_probe_compacted"] == 0


def test_a_probe_that_does_not_fit_is_looked_up_whole(cells):
    """Q3 as a cell sends it, its lineitem unpruned (the dynamic filter
    is off over 1M build rows; here by the session): 54% of 60,000 rows
    pass l_shipdate, 32,000 do not fit 4,096, and its second join's
    probe is as long as its output. The device takes the whole-probe
    form, nothing overflows, nothing reruns: 0."""
    res = sql(_cell_text("q3-mem"), sf=0.01, join_capacity=4096,
              session={"dynamic_filtering": False})
    assert res.query_stats.counters["join_probe_compacted"] == 0
    assert res.query_stats.counters["capacity_reruns"] == 0
    assert res.query_stats.stages["dispatch"].invocations == 1
    assert res.rows() == sql(_cell_text("q3-mem"), sf=0.01).rows()


def test_a_ladder_rerun_sums_the_compacted_probes():
    """A probe that fits the compacted capacity is no overflow, and an
    output that overflows is no reason to leave the form: 779 of 60,000
    lineitem rows pass the filter and fan out to four slots each, so
    the dispatch at 1,024 slots compacts and overflows, the rerun at
    4,096 compacts and fits, and the counter adds over both; the
    answer is the roomy run's, which has no second form."""
    text = ("SELECT count(*), sum(l2.quantity) FROM lineitem l "
            "JOIN lineitem l2 ON l.orderkey = l2.orderkey "
            "WHERE l.shipdate >= date '1995-09-01' "
            "AND l.shipdate < date '1995-10-01'")
    roomy = sql(text, sf=0.01)
    tight = sql(text, sf=0.01, join_capacity=1024,
                session={"dynamic_filtering": False})
    assert tight.rows() == roomy.rows()
    assert roomy.query_stats.stages["dispatch"].invocations == 1
    assert roomy.query_stats.counters["join_probe_compacted"] == 0
    assert tight.query_stats.stages["dispatch"].invocations == 2
    assert tight.stats["capacity_reruns"]["count"] == 1
    assert tight.query_stats.counters["join_probe_compacted"] == 1 + 1
    # blocks of 64 probe rows at 1,024 slots, of 16 at 4,096: the trips
    # of the compaction in the one form, of the expansion in the other
    assert tight.query_stats.counters["join_expand_steps"] == 6 + 4


def test_the_status_word_splits_four_ways():
    """Flags in bits 0-7, the search trips in the twelve above them
    (held to the field), then five bits each for the compacted probes
    and the lookups the directory answered; an array of words (a
    vmapped program's) splits lane by lane."""
    import numpy as np
    from presto_tpu.exec.planner import (COUNT_BITS, FLAG_BITS, STEP_BITS,
                                         split_flags)
    at = FLAG_BITS + STEP_BITS
    word = 1 + (37 << FLAG_BITS) + (2 << at) + (3 << at + COUNT_BITS)
    assert split_flags(word) == (1, 37, 2, 3)
    assert split_flags(2) == (2, 0, 0, 0)
    full = (31 << at) + (31 << at + COUNT_BITS)  # both fields at their cap
    assert split_flags(full) == (0, 0, 31, 31)
    flags, steps, compacted, direct = split_flags(
        np.asarray([word, 3 << FLAG_BITS, 1 << at, full], dtype=np.int32))
    assert (flags.tolist(), steps.tolist(), compacted.tolist(),
            direct.tolist()) == \
        ([1, 0, 0, 0], [37, 3, 0, 0], [2, 0, 1, 31], [3, 0, 0, 31])


# -- a failed statement -------------------------------------------------


def _oom_executor(pool):
    """The default executor's library call with an admission pool, so
    that `memory.reserve` is on the statement's path."""
    def run(text, session_values, query_id, txn_id):
        return sql(text, sf=0.01, memory_pool=pool, query_id=query_id,
                   session=dict(session_values))
    return run


FAILURES = {
    # how: (failpoint spec or None, text, spans opened before the fault,
    #       spans never opened)
    "memory.reserve": ("memory.reserve=oom:once", SELECT,
                       {"queue", "plan", "plan.sql", "plan.prepare",
                        "dynfilter"}, {"staging", "execute"}),
    "statement.execute": ("statement.execute=error:once", SELECT,
                          {"queue"}, {"plan", "staging", "execute"}),
    "unknown-column": (None, "SELECT nosuch FROM lineitem",
                       {"queue", "plan", "plan.sql"},
                       {"plan.prepare", "staging", "execute"}),
}


@pytest.mark.parametrize("how", sorted(FAILURES))
def test_failed_statement_closes_its_spans(how, monkeypatch):
    """A statement that fails after `plan`, before planning or inside
    `plan`: every span opened before the fault is in the statement's
    collector, closed; the tracer holds the statement's `query` root
    and state spans, closed, under its trace id; and its collector is
    closed, once, on the way out (`StatementServer._run`), so the stage
    spans are served by /v1/trace under that root and their walls are
    in presto_tpu_stage_seconds."""
    from presto_tpu.server import metrics
    from presto_tpu.client import QueryError
    from presto_tpu.exec.memory import MemoryPool
    from presto_tpu.exec.stats import StatsCollector
    spec, text, opened, never = FAILURES[how]
    closes = []
    real_close = StatsCollector.close

    def counted_close(self, trace=None):
        closes.append(self.query_id)
        return real_close(self, trace)
    monkeypatch.setattr(StatsCollector, "close", counted_close)
    observed = []
    real_observe = metrics.observe_histogram

    def counted_observe(name, value, labels=None, **kw):
        if name == "presto_tpu_stage_seconds":
            observed.append((labels["stage"], kw.get("trace_id")))
        return real_observe(name, value, labels=labels, **kw)
    monkeypatch.setattr(metrics, "observe_histogram", counted_observe)
    before = get_tracer()
    set_tracer(RecordingTracer())
    try:
        executor = _oom_executor(MemoryPool(1 << 30)) \
            if how == "memory.reserve" else None
        with StatementServer(sf=0.01, executor=executor) as srv:
            with pytest.raises(QueryError):
                execute(srv.url, text,
                        session={"failpoints": spec} if spec else {})
            (qid, q), = srv._queries.items()
            assert q.machine.state == "FAILED"
            deadline = time.time() + 5  # the client saw FAILED; the
            while not q.collector.closed and time.time() < deadline:
                time.sleep(0.01)  # statement's thread is on its way out
            with urllib.request.urlopen(f"{srv.url}/v1/trace/{qid}") as r:
                spans = json.load(r)["spans"]
            recorded = list(q.collector.spans)
    finally:
        set_tracer(before)
    # the collector: every span opened before the fault, each closed
    names = {rec[0] for rec in recorded}
    assert opened <= names, (opened - names, names)
    assert not never & names, never & names
    assert all(end >= start for _n, start, end, *_rest in recorded)
    # the tracer: the root and the states it passed, each closed
    by_name = {s["name"]: s for s in spans}
    assert by_name["query"]["attributes"]["state"] == "FAILED"
    assert "query.failed" in by_name
    assert all(s["endUs"] >= s["startUs"] for s in spans)
    by_id = {s["spanId"] for s in spans}
    assert all(s["parentId"] in by_id for s in spans
               if s["name"] != "query")
    # closed once, and every recorded span shipped under the root
    assert closes.count(qid) == 1
    shipped = sorted(s["name"] for s in spans
                     if s["name"].startswith("stage."))
    assert shipped == sorted(f"stage.{rec[0]}" for rec in recorded)
    assert {f"stage.{n}" for n in opened} <= set(shipped)
    # the walls of its stages, exemplar'd with the statement's trace id
    walled = {n for n, st in q.collector.stats.stages.items()
              if st.wall_us}
    assert {stage_name for stage_name, _tid in observed} == walled
    assert {tid for _n, tid in observed} <= {q.trace_ctx.trace_id}


def test_flight_dump_has_one_account_of_time(tmp_path):
    """A slow statement's flight dump: its header, its events and the
    datapath's hop walls; no second or third ledger of the same time
    beside them."""
    from presto_tpu.server.flight_recorder import (FlightRecorder,
                                                   set_flight_recorder)
    rec = FlightRecorder(dump_dir=str(tmp_path))
    set_flight_recorder(rec)
    try:
        with StatementServer(sf=0.01) as srv:
            done = execute(srv.url, "SELECT count(*) AS n FROM lineitem",
                           session={"slow_query_threshold_ms": "1"})
            deadline, path = time.time() + 5, None
            while path is None and time.time() < deadline:
                path = rec.dump_path(done.query_id)
                time.sleep(0.05)
        assert path is not None
        with open(path) as f:
            lines = [json.loads(line) for line in f]
    finally:
        set_flight_recorder(None)
    assert lines[0]["dump"]["reason"] == "slow"
    assert lines[0]["dump"]["events"] == \
        sum(1 for line in lines if "kind" in line) >= 1
    (hops,) = [line["datapath"]["hops"] for line in lines
               if "datapath" in line]
    assert hops["connector_read"]["wall_us"] >= 0
    assert not [key for line in lines for key in line
                if key in ("timeline", "profile")]
