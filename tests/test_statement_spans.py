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
             "fetch", "render", "write")

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
                            "plan.prepare", "batch.prepare", "batch.wait"])
def test_protocol_stats_carry_every_stage(served, name):
    stages = served["select"][-1]["stats"]["stages"]
    assert name in stages, sorted(stages)
    assert stages[name]["invocations"] >= 1
    assert stages[name]["wall_us"] >= 0


def _tree(spans):
    by_id = {s["spanId"]: s for s in spans}
    return by_id, [(s, by_id[s["parentId"]]) for s in spans
                   if s["parentId"] in by_id]


@pytest.mark.parametrize("which", ["select", "ctas"])
def test_children_inside_parents_and_top_level_tiles(served, which):
    run = served[which][-1] if which == "select" else served[which]
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


@pytest.mark.parametrize("child,parent", [
    ("stage.plan.sql", "stage.plan"),
    ("stage.plan.prepare", "stage.plan"),
    ("stage.connector_read", "stage.staging"),
    ("stage.device_put", "stage.staging"),
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
    """The trips `_match_ranges` took leave the device in the word the
    program already returns (bits 8 and up) and land in the statement's
    counters over the protocol; a statement without a join has none."""
    with StatementServer(sf=0.01) as srv:
        stats = execute(srv.url, text).stats["queryStats"]
    steps = stats["counters"].get("join_search_steps")
    if not joins:
        assert steps is None
    else:
        # at least a trip a join, at most a whole binary search of the
        # largest build capacity (the default join capacity, 2**16) each
        assert joins <= steps <= joins * 16
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
    too small by 16x reruns twice (x4 each) and answers as the roomy
    run does; each of the three dispatches adds its trips."""
    text = ("SELECT count(*), sum(o.totalprice) FROM orders o "
            "JOIN customer c ON o.custkey = c.custkey "
            "WHERE c.nationkey < 20")
    roomy = sql(text, sf=0.01)
    tight = sql(text, sf=0.01, join_capacity=1024)
    assert tight.rows() == roomy.rows()
    assert roomy.query_stats.stages["dispatch"].invocations == 1
    assert tight.query_stats.stages["dispatch"].invocations == 3
    assert tight.stats["capacity_reruns"]["count"] == 2
    once = roomy.query_stats.counters["join_search_steps"]
    assert once >= 1
    assert tight.query_stats.counters["join_search_steps"] == 3 * once
    # 11,976 probe rows a slot: blocks of 1 at 65,536 slots; of 16, 4
    # and 1 at 1,024, 4,096 and 16,384
    assert roomy.query_stats.counters["join_expand_steps"] == 0
    assert tight.query_stats.counters["join_expand_steps"] == 4 + 2 + 0
