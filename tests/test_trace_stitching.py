"""Distributed trace stitching: span ids + parent edges, X-Presto-Trace
propagation across the HTTP tiers, worker span ship-home, the stitched
GET /v1/trace/{queryId} document, and the waterfall renderer.

Reference behavior: the OpenTelemetry plugin's Tracer SPI +
QueryStateTracingListener (spans at query state transitions) and W3C
trace-context propagation (traceparent) as the OTel HTTP
instrumentation speaks it -- one trace per query across coordinator and
workers, every non-root span's parent present in the trace."""

import json
import threading
import urllib.request

import pytest

from presto_tpu.server.tracing import (
    RecordingTracer, SpanBuffer, TraceContext, emit_span, get_tracer,
    new_span_id, new_trace_id, parse_traceparent, set_tracer,
    span_buffer, trace_context, tracing_totals)

SPAN_KEYS = {"traceId", "spanId", "parentId", "name", "startUs",
             "endUs", "attributes"}


@pytest.fixture(autouse=True)
def _clean_tracer():
    yield
    set_tracer(None)


# -- context + header ---------------------------------------------------

def test_traceparent_header_roundtrip():
    ctx = TraceContext(new_trace_id(), new_span_id())
    assert parse_traceparent(ctx.header()) == ctx
    # legacy query.<qid> trace ids ride the same header shape
    legacy = TraceContext("query.deadbeef", new_span_id())
    assert parse_traceparent(legacy.header()) == legacy
    child = ctx.child()
    assert child.trace_id == ctx.trace_id
    assert child.span_id != ctx.span_id


def test_traceparent_parse_tolerates_garbage():
    for bad in (None, "", "not-a-header", "00-", "00--01", "x"):
        assert parse_traceparent(bad) is None


# -- golden span schema (satellite: exporters cannot drift silently) ----

def test_span_json_golden_schema():
    t = RecordingTracer()
    set_tracer(t)
    sid = t.span("tr1", "query", 1.0, 2.5, {"user": "alice"},
                 parent_id=None)
    emit_span("tr1", "stage.execute", 1.2, 2.0, {"rows": 5},
              parent_id=sid)
    for s in t.spans("tr1"):
        assert set(s) == SPAN_KEYS
        assert isinstance(s["traceId"], str)
        assert isinstance(s["spanId"], str) and len(s["spanId"]) == 16
        assert s["parentId"] is None or isinstance(s["parentId"], str)
        assert isinstance(s["name"], str)
        assert isinstance(s["startUs"], int)
        assert isinstance(s["endUs"], int) and s["endUs"] >= s["startUs"]
        assert isinstance(s["attributes"], dict)
    root, child = t.spans("tr1")
    assert root["startUs"] == 1_000_000 and root["endUs"] == 2_500_000
    assert child["parentId"] == root["spanId"]


def test_write_query_spans_join_propagated_trace():
    """Write/DDL roots delegate through _run_write_root; the propagated
    TraceContext must survive the delegation so INSERT/CTAS stage spans
    land in the client's trace, parented under its span (not stranded
    in a query-id-keyed trace of their own)."""
    from presto_tpu.connectors import memory
    from presto_tpu.sql import sql
    t = RecordingTracer()
    set_tracer(t)
    ctx = TraceContext(new_trace_id(), new_span_id())
    try:
        res = sql("CREATE TABLE memory.tw_trace AS "
                  "SELECT orderkey, custkey FROM orders",
                  sf=0.001, trace_id=ctx)
        assert res.rows()  # the write itself succeeded
        spans = t.spans(ctx.trace_id)
        names = {s["name"] for s in spans}
        assert any(n.startswith("stage.") for n in names), names
        # top-level stages hang under the propagated span, their
        # children (plan.sql, the staging hops, dispatch) under them
        ids = {s["spanId"]: s["name"] for s in spans}
        assert all(s["parentId"] == ctx.span_id or s["parentId"] in ids
                   for s in spans)
        top = {s["name"] for s in spans if s["parentId"] == ctx.span_id}
        assert {"stage.plan", "stage.staging", "stage.execute",
                "stage.write"} <= top, top
    finally:
        memory.drop_table("tw_trace", if_exists=True)


def test_jsonl_export_same_schema(tmp_path):
    t = RecordingTracer()
    t.span("tr2", "a", 0.0, 1.0)
    path = tmp_path / "spans.jsonl"
    t.export_jsonl(str(path))
    doc = json.loads(path.read_text().splitlines()[0])
    assert set(doc) == SPAN_KEYS


# -- RecordingTracer under concurrency (satellite) ----------------------

def test_parallel_span_appends_all_retained():
    t = RecordingTracer()
    set_tracer(t)
    n_threads, per_thread = 8, 50

    def emit_many(i):
        for j in range(per_thread):
            emit_span("shared", f"s{i}.{j}", j, j + 1)
            t.span(f"trace{i}", "x", j, j + 1)

    threads = [threading.Thread(target=emit_many, args=(i,))
               for i in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(t.spans("shared")) == n_threads * per_thread
    for i in range(n_threads):
        assert len(t.spans(f"trace{i}")) == per_thread
    # every span id unique across the shared trace
    ids = [s["spanId"] for s in t.spans("shared")]
    assert len(set(ids)) == len(ids)


def test_concurrent_appends_respect_lru_eviction_order():
    t = RecordingTracer(max_traces=4)
    before = tracing_totals()["evicted"]
    done = threading.Barrier(4)

    def fill(i):
        t.span(f"t{i}", "x", 0.0, 1.0)
        done.wait()
        # refresh every trace but t0 so it becomes the eviction victim
        if i != 0:
            t.span(f"t{i}", "y", 1.0, 2.0)

    threads = [threading.Thread(target=fill, args=(i,)) for i in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    t.span("t0", "z", 2.0, 3.0)   # refresh t0 LAST: now t1..t3 older
    t.span("new", "x", 0.0, 1.0)  # evicts the least-recently-updated
    assert "t0" in t.traces       # refreshed last -> survived
    assert "new" in t.traces
    assert len(t.traces) == 4
    assert tracing_totals()["evicted"] == before + 1


def test_broken_tracer_query_still_succeeds():
    from presto_tpu.server.metrics import suppressed_error_totals
    from presto_tpu.sql import sql

    class BrokenTracer:
        def span(self, *a, **k):
            raise RuntimeError("tracer backend down")

    set_tracer(BrokenTracer())
    before = tracing_totals()["dropped"]
    res = sql("SELECT count(*) FROM region", sf=0.01,
              query_id="broken-tracer-q")
    assert res.rows() == [(5,)]               # query unharmed
    assert tracing_totals()["dropped"] > before
    totals = suppressed_error_totals()
    assert any(k[0] == "tracing" for k in totals)


def test_legacy_five_arg_tracer_still_receives_spans():
    # the pre-span-id pluggable SPI: span(trace_id, name, start, end,
    # attributes) with NO **kwargs -- emit_span degrades to it instead
    # of dropping every span
    class LegacyTracer:
        def __init__(self):
            self.calls = []

        def span(self, trace_id, name, start_s, end_s, attributes=None):
            self.calls.append((trace_id, name))

    legacy = LegacyTracer()
    set_tracer(legacy)
    before = tracing_totals()["dropped"]
    sid = emit_span("trL", "stage.execute", 0.0, 1.0)
    assert sid is not None                       # delivered
    assert legacy.calls == [("trL", "stage.execute")]
    assert tracing_totals()["dropped"] == before  # not a drop


def test_add_spans_rejects_docs_without_timestamps():
    # a foreign-build span missing startUs/endUs must not poison
    # trace_doc's start-ordering for the whole trace
    t = RecordingTracer()
    good = {"spanId": "s1", "name": "ok", "startUs": 5, "endUs": 9}
    bad = {"spanId": "s2", "name": "no-times"}
    assert t.add_spans("trM", [good, bad]) == 1
    doc = t.trace_doc("trM")
    assert [s["spanId"] for s in doc["spans"]] == ["s1"]


# -- emission seam: thread-local buffers + stitching --------------------

def test_span_buffer_captures_and_ships():
    set_tracer(None)  # buffer alone must still capture (worker tier)
    with span_buffer() as buf:
        emit_span("trX", "task.t1", 0.0, 1.0)
        emit_span("trX", "stage.execute", 0.2, 0.8)
    assert [s["name"] for s in buf.spans] == ["task.t1", "stage.execute"]
    # ... and add_spans stitches them into a tracer idempotently
    t = RecordingTracer()
    assert t.add_spans("trX", buf.spans) == 2
    assert t.add_spans("trX", buf.spans) == 0     # dedup by spanId
    assert len(t.spans("trX")) == 2
    assert t.add_spans("trX", [{"bogus": 1}]) == 0  # malformed skipped


def test_ambient_trace_context_nests():
    a = TraceContext("tr", new_span_id())
    b = a.child()
    from presto_tpu.server.tracing import current_context
    assert current_context() is None
    with trace_context(a):
        assert current_context() == a
        with trace_context(b):
            assert current_context() == b
        assert current_context() == a
    assert current_context() is None


# -- the stitched distributed trace, end to end -------------------------

@pytest.fixture(scope="module")
def distributed_statement_server():
    """StatementServer fronting a 2-worker Coordinator: the full
    client -> coordinator -> workers -> stitched-trace path."""
    from presto_tpu.exec.runner import QueryResult
    from presto_tpu.plan.distribute import add_exchanges
    from presto_tpu.server import Coordinator, TpuWorkerServer
    from presto_tpu.server.statement import StatementServer
    from presto_tpu.sql import plan_sql

    workers = [TpuWorkerServer(sf=0.01).start() for _ in range(2)]
    coord = Coordinator([f"http://127.0.0.1:{w.port}" for w in workers])
    holder = {}

    def executor(text, session_values, query_id, txn_id):
        root = add_exchanges(plan_sql(text, max_groups=1 << 14))
        cols, names = coord.execute(
            root, sf=0.01,
            trace_ctx=holder["srv"]._trace_ctx_of(query_id))
        return QueryResult([v for v, _ in cols], [n for _, n in cols],
                           names, len(cols[0][0]) if cols else 0,
                           types=root.output_types())

    srv = StatementServer(sf=0.01, executor=executor)
    holder["srv"] = srv
    srv.start()
    try:
        yield srv
    finally:
        srv.stop()
        for w in workers:
            w.stop()


def test_distributed_query_stitches_one_trace(distributed_statement_server):
    from presto_tpu.client import execute
    srv = distributed_statement_server
    tracer = RecordingTracer()
    set_tracer(tracer)
    r = execute(srv.url, "SELECT custkey, count(*) AS c FROM orders "
                         "GROUP BY custkey")
    assert len(r.data) > 0
    with urllib.request.urlopen(
            f"{srv.url}/v1/trace/{r.query_id}") as resp:
        doc = json.loads(resp.read().decode())
    assert doc["queryId"] == r.query_id
    spans = doc["spans"]
    names = [s["name"] for s in spans]
    # coordinator-tier spans ...
    assert "query" in names                       # statement root
    assert "query.running" in names               # state machine
    assert "coordinator.execute" in names
    assert any(n.startswith("fragment.f") for n in names)
    assert "coordinator.fetch_results" in names
    assert "client.fetch" in names                # result drain leg
    # ... and worker-tier spans, shipped home on final task status
    assert any(n.startswith("task.") for n in names)
    assert any(n == "stage.execute" for n in names)
    assert any(n == "exchange.fetch" for n in names)  # consumer pull
    # the stitch contract: ONE root, every non-root parent IN the trace
    ids = {s["spanId"] for s in spans}
    roots = [s for s in spans if s["parentId"] is None]
    assert len(roots) == 1 and roots[0]["name"] == "query"
    for s in spans:
        if s["parentId"] is not None:
            assert s["parentId"] in ids, f"orphan {s['name']}"
        assert set(s) == SPAN_KEYS


def test_client_propagated_trace_id_wins(distributed_statement_server):
    from presto_tpu.client import execute
    from presto_tpu.server.tracing import TRACE_HEADER
    srv = distributed_statement_server
    tracer = RecordingTracer()
    set_tracer(tracer)
    ctx = TraceContext(new_trace_id(), new_span_id())
    r = execute(srv.url, "SELECT count(*) FROM region",
                extra_headers={TRACE_HEADER: ctx.header()})
    assert r.data == [[5]]
    # the served trace is the CLIENT's trace id; the query root span
    # parents under the client's span
    with urllib.request.urlopen(
            f"{srv.url}/v1/trace/{r.query_id}") as resp:
        doc = json.loads(resp.read().decode())
    assert doc["traceId"] == ctx.trace_id
    root = next(s for s in doc["spans"] if s["name"] == "query")
    assert root["parentId"] == ctx.span_id


def test_trace_endpoint_404_without_trace(distributed_statement_server):
    srv = distributed_statement_server
    set_tracer(RecordingTracer())
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(f"{srv.url}/v1/trace/nope")
    assert ei.value.code == 404


def test_trace_endpoint_404_with_foreign_tracer(distributed_statement_server):
    """The tracer SPI only promises span(); a custom exporter without
    trace_doc must yield the documented 404, not a handler crash."""
    class _SpanOnly:
        def span(self, *a, **k):
            return None
    srv = distributed_statement_server
    set_tracer(_SpanOnly())
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(f"{srv.url}/v1/trace/anything")
    assert ei.value.code == 404


def test_failed_query_still_stitches_completed_worker_spans():
    """The stitch runs in execute()'s finally, BEFORE task cleanup: a
    query that dies after some tasks completed still gets those tasks'
    spans into the trace -- the failed query is the one a post-mortem
    needs traced."""
    from presto_tpu.plan.distribute import add_exchanges
    from presto_tpu.server import Coordinator, TpuWorkerServer
    from presto_tpu.sql import plan_sql
    t = RecordingTracer()
    set_tracer(t)
    w = TpuWorkerServer(sf=0.01).start()
    try:
        coord = Coordinator([f"http://127.0.0.1:{w.port}"])
        root = add_exchanges(plan_sql(
            "SELECT custkey, count(*) AS c FROM orders GROUP BY custkey",
            max_groups=1 << 14))
        real = coord._execute_fragments

        def boom(*a, **k):
            real(*a, **k)  # all fragments produce, then the query dies
            raise RuntimeError("post-production failure")

        coord._execute_fragments = boom
        ctx = TraceContext(new_trace_id(), new_span_id())
        with pytest.raises(RuntimeError, match="post-production"):
            coord.execute(root, sf=0.01, trace_ctx=ctx)
        names = {s["name"] for s in t.spans(ctx.trace_id)}
        assert any(n.startswith("task.") for n in names), names
        assert "stage.execute" in names
        assert "coordinator.execute" in names
    finally:
        w.stop()


def test_per_trace_span_cap_bounds_hot_client_trace():
    """Trace ids are client-controlled: one traceparent reused across a
    whole session keeps its entry hot (never the LRU victim), so the
    per-trace cap is what bounds coordinator memory; overflow counts
    as dropped."""
    t = RecordingTracer(max_spans_per_trace=8)
    before = tracing_totals()["dropped"]
    for i in range(20):
        t.span("hot", f"s{i}", float(i), float(i) + 0.5)
    assert len(t.spans("hot")) == 8
    assert tracing_totals()["dropped"] - before == 12
    # shipped-home batches hit the same bound
    docs = [{"spanId": f"x{i:015d}", "name": "n", "startUs": 0, "endUs": 1}
            for i in range(5)]
    assert t.add_spans("hot", docs) == 0
    assert len(t.spans("hot")) == 8


# -- waterfall rendering + critical path --------------------------------

def _synthetic_doc():
    mk = lambda name, sid, pid, lo, hi: {  # noqa: E731
        "traceId": "tr", "spanId": sid, "parentId": pid, "name": name,
        "startUs": lo, "endUs": hi, "attributes": {}}
    return {"traceId": "tr", "spans": [
        mk("query", "r" * 16, None, 0, 1_000_000),
        mk("stage.compile", "c" * 16, "r" * 16, 0, 200_000),
        mk("stage.execute", "e" * 16, "r" * 16, 200_000, 950_000),
        mk("stage.fetch", "f" * 16, "r" * 16, 950_000, 980_000),
    ]}


def test_waterfall_renders_and_names_critical_path():
    from presto_tpu.traceview import (critical_path,
                                      critical_path_summary,
                                      render_waterfall)
    doc = _synthetic_doc()
    path = critical_path(doc["spans"])
    # every span owns its stretch; attribution sums to the root's wall
    assert {s["name"]: us for s, us in path} == {
        "query": 20_000, "stage.compile": 200_000,
        "stage.execute": 750_000, "stage.fetch": 30_000}
    assert sum(us for _, us in path) == 1_000_000
    # the chain reads start-ordered, the hot stage is execute (75%)
    summary = critical_path_summary(doc["spans"])
    assert "query > stage.compile > stage.execute > stage.fetch" \
        in summary
    assert "critical-path stage: stage.execute" in summary
    assert "75% of wall" in summary
    out = render_waterfall(doc)
    assert "query" in out and "stage.execute" in out
    assert "#" in out                          # bars drawn
    assert "1000.0ms wall" in out
    assert summary in out


def test_waterfall_orphan_renders_as_root():
    from presto_tpu.traceview import build_tree, render_waterfall
    doc = _synthetic_doc()
    doc["spans"].append({"traceId": "tr", "spanId": "o" * 16,
                         "parentId": "missing", "name": "task.lost",
                         "startUs": 100, "endUs": 200, "attributes": {}})
    roots, _ = build_tree(doc["spans"])
    assert {r["name"] for r in roots} == {"query", "task.lost"}
    assert "task.lost" in render_waterfall(doc)


def test_waterfall_survives_parent_cycle():
    """Stitch validates ids and timestamps, not edges: a buggy/foreign
    worker can ship mutually-parented spans. The renderer promotes one
    span per cycle and renders degraded -- never a crash, never a
    dropped span."""
    from presto_tpu.traceview import build_tree, render_waterfall
    doc = _synthetic_doc()
    doc["spans"] += [
        {"traceId": "tr", "spanId": "a" * 16, "parentId": "b" * 16,
         "name": "cyc.a", "startUs": 10, "endUs": 30, "attributes": {}},
        {"traceId": "tr", "spanId": "b" * 16, "parentId": "a" * 16,
         "name": "cyc.b", "startUs": 12, "endUs": 28, "attributes": {}},
    ]
    roots, children = build_tree(doc["spans"])
    assert {r["name"] for r in roots} == {"query", "cyc.a"}
    assert [k["name"] for k in children["a" * 16]] == ["cyc.b"]
    out = render_waterfall(doc)
    assert "cyc.a" in out and "cyc.b" in out


def test_trace_view_script_on_jsonl(tmp_path, capsys):
    import trace_view  # conftest puts scripts/ on sys.path
    t = RecordingTracer()
    for s in _synthetic_doc()["spans"]:
        t.span("tr", s["name"], s["startUs"] / 1e6, s["endUs"] / 1e6,
               span_id=s["spanId"], parent_id=s["parentId"])
    path = tmp_path / "spans.jsonl"
    t.export_jsonl(str(path))
    assert trace_view.main([str(path), "--trace", "tr"]) == 0
    out = capsys.readouterr().out
    assert "critical path:" in out and "query" in out
    assert trace_view.main([str(path), "--trace", "absent"]) == 1


def test_cli_trace_flag_embedded(capsys):
    from presto_tpu.cli import run_one
    set_tracer(RecordingTracer())
    assert run_one("SELECT count(*) FROM region", 0.01, trace=True) == 0
    out = capsys.readouterr().out
    assert "critical path:" in out
    assert "stage.execute" in out


def test_cli_trace_flag_remote(distributed_statement_server, capsys):
    from presto_tpu.cli import run_one_remote
    srv = distributed_statement_server
    set_tracer(RecordingTracer())
    assert run_one_remote("SELECT count(*) FROM nation", srv.url,
                          trace=True) == 0
    out = capsys.readouterr().out
    assert "critical path:" in out
    assert "query" in out and "client.fetch" in out


# -- tracer health on /v1/metrics (satellite) ---------------------------

def test_tracing_metric_families_on_both_tiers():
    from presto_tpu.server import TpuWorkerServer
    from presto_tpu.server.metrics import parse_prometheus
    from presto_tpu.server.statement import StatementServer
    w = TpuWorkerServer(sf=0.01).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{w.port}/v1/metrics") as r:
            worker_fams = parse_prometheus(r.read().decode())
    finally:
        w.stop()
    with StatementServer(sf=0.01) as srv:
        with urllib.request.urlopen(f"{srv.url}/v1/metrics") as r:
            coord_fams = parse_prometheus(r.read().decode())
    for fams in (worker_fams, coord_fams):
        assert "presto_tpu_trace_spans_total" in fams
        assert "presto_tpu_traces_evicted_total" in fams
        assert "presto_tpu_trace_spans_dropped_total" in fams
        assert "presto_tpu_flight_recorder_events_total" in fams
        dumps = fams["presto_tpu_flight_recorder_dumps_total"]
        assert any('reason="failed"' in k for k in dumps)
        assert any('reason="slow"' in k for k in dumps)


def test_scrape_metrics_diffs_tracing_families():
    # conftest puts scripts/ on sys.path
    from scrape_metrics import TRACING_FAMILIES, diff
    before = {f: {"": 0.0} for f in TRACING_FAMILIES}
    after = {f: {"": 2.0} for f in TRACING_FAMILIES}
    after["presto_tpu_flight_recorder_dumps_total"] = {
        '{reason="failed"}': 0.0, '{reason="slow"}': 1.0}
    d = diff(before, after)
    assert d["tracing"]["presto_tpu_trace_spans_total"] == 2.0
    # zero deltas stay visible in the tracing section
    assert d["tracing"][
        'presto_tpu_flight_recorder_dumps_total{reason="failed"}'] == 0.0
    assert d["tracing"][
        'presto_tpu_flight_recorder_dumps_total{reason="slow"}'] == 1.0
