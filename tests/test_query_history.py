"""Query history archive + perf regression sentinel (perfgate).

The contracts under test: the median+MAD comparator is deterministic
and warms up before it alarms; every terminal statement lands one
record in the archive (fingerprint, QueryStats rollup, trace id) and
on the JSONL ring with rotation + retention; GET /v1/history serves it
on both tiers (cluster-merged on the statement tier,
processId-deduped) and SELECT * FROM system.query_history serves it as
SQL; the end-to-end sentinel catches an injected exchange delay on a
warmed baseline (regression counter + flight event + auto dump) and
stays SILENT on the clean replay; and the offline gate
(scripts/perfgate.py) is byte-identical across runs over identical
artifacts with the tpulint 0/1/2 exit contract."""

import json
import logging
import os
import sys
import time
import urllib.request

import pytest

from presto_tpu.exec.perfgate import (BENCH_SPECS, MetricSpec,
                                      RollingBaseline, SENTINEL_SPECS,
                                      compare, mad, median, noise_band)
from presto_tpu.server.flight_recorder import (FlightRecorder,
                                               flight_recorder_totals,
                                               set_flight_recorder)
from presto_tpu.server.history import (QueryHistoryArchive,
                                       get_history_archive,
                                       merge_history_docs,
                                       perf_regression_totals,
                                       set_history_archive)

_SCRIPTS = os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "scripts")


def _wait_for(fn, timeout=8.0):
    """Terminal-path hooks (archive append, dumps) run on the query's
    execution thread AFTER the client sees the terminal state; poll."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = fn()
        if v:
            return v
        time.sleep(0.02)
    return fn()


@pytest.fixture
def recorder(tmp_path):
    r = FlightRecorder(capacity=256, dump_dir=str(tmp_path / "flight"))
    set_flight_recorder(r)
    yield r
    set_flight_recorder(None)


@pytest.fixture
def archive(tmp_path):
    a = QueryHistoryArchive(capacity=64,
                            history_dir=str(tmp_path / "hist"),
                            baseline=RollingBaseline(min_samples=3))
    set_history_archive(a)
    yield a
    set_history_archive(None)


# -- the comparator (exec/perfgate.py) ----------------------------------

def test_median_mad_basics():
    assert median([]) == 0.0
    assert median([3.0]) == 3.0
    assert median([1.0, 9.0, 2.0]) == 2.0
    assert median([1.0, 2.0, 3.0, 4.0]) == 2.5
    assert mad([5.0, 5.0, 5.0]) == 0.0
    assert mad([1.0, 2.0, 9.0]) == 1.0  # around median 2


def test_compare_breach_and_band():
    spec = MetricSpec("wall_us", rel_threshold=0.5, abs_floor=100.0)
    samples = [1000.0, 1010.0, 990.0, 1005.0, 995.0]
    # in-band (within rel threshold)
    assert compare(1400.0, samples, spec) is None
    v = compare(3000.0, samples, spec)
    assert v is not None and v["metric"] == "wall_us"
    assert v["median"] == 1000.0 and v["direction"] == "above"
    assert v["value"] > v["median"] + v["band"]
    # regressing in the GOOD direction never breaches
    assert compare(10.0, samples, spec) is None
    # empty baseline: warming, never a breach
    assert compare(99999.0, [], spec) is None


def test_compare_lower_is_worse_direction():
    spec = MetricSpec("rows_per_sec", higher_is_worse=False,
                      rel_threshold=0.5)
    samples = [100.0, 101.0, 99.0, 100.0]
    assert compare(150.0, samples, spec) is None      # faster: fine
    v = compare(10.0, samples, spec)
    assert v is not None and v["direction"] == "below"


def test_noise_band_three_way_max():
    spec = MetricSpec("m", rel_threshold=0.1, abs_floor=5.0, mad_k=5.0)
    # quiet samples: the rel term dominates
    assert noise_band([100.0] * 5, spec) == pytest.approx(10.0)
    # tiny values: the abs floor dominates
    assert noise_band([1.0] * 5, spec) == pytest.approx(5.0)
    # noisy samples: the MAD term dominates
    noisy = [100.0, 200.0, 50.0, 300.0, 150.0]
    assert noise_band(noisy, spec) > 0.1 * median(noisy)


def test_rolling_baseline_warmup_window_and_warm():
    rb = RollingBaseline(window=4, min_samples=3, max_keys=2)
    for i in range(3):  # warming: never breaches
        assert rb.observe("k", {"wall_us": 1e6 + i}) == []
    breaches = rb.observe("k", {"wall_us": 5e6})
    assert [b["metric"] for b in breaches] == ["wall_us"]
    # the regressed sample was absorbed (drift acceptance) and the
    # window is bounded
    assert len(rb.samples_of("k")["wall_us"]) == 4
    assert 5e6 in rb.samples_of("k")["wall_us"]
    # warm() absorbs without comparing (archive reload path)
    rb2 = RollingBaseline(window=4, min_samples=1)
    rb2.warm("x", {"wall_us": 1.0})
    assert rb2.samples_of("x")["wall_us"] == [1.0]
    # LRU key bound
    rb.observe("k2", {"wall_us": 1.0})
    rb.observe("k3", {"wall_us": 1.0})
    assert rb.key_count() == 2


# -- record construction + the JSONL ring -------------------------------

def test_record_of_real_query_rollup(recorder):
    from presto_tpu.sql import sql as run_sql
    res = run_sql("SELECT count(*) FROM lineitem WHERE quantity > 10",
                  sf=0.01, query_id="qh-rec-1")
    qs = res.query_stats
    rec = QueryHistoryArchive.record_of(
        "qh-rec-1", "FINISHED", "alice", "SELECT count(*) ...",
        qs.wall_us / 1000.0, "trace-abc", query_stats=qs)
    assert rec["queryId"] == "qh-rec-1" and rec["state"] == "FINISHED"
    assert rec["traceId"] == "trace-abc"
    assert len(rec["fingerprint"]) == 16
    st = rec["stats"]
    assert st["execute_us"] == qs.stage_us("execute")
    assert st["staged_bytes"] == qs.stages["staging"].bytes > 0
    assert st["output_rows"] == 1
    assert st["peak_memory_bytes"] == qs.peak_memory_bytes
    # kernel-mode envs ride the record (the A/B provenance)
    assert "PRESTO_TPU_NARROW" in rec["kernelModeEnvs"]


def _record_of_run(query_id, text, sf):
    from presto_tpu.sql import sql as run_sql
    res = run_sql(text, sf=sf, query_id=query_id)
    qs = res.query_stats
    return QueryHistoryArchive.record_of(
        query_id, "FINISHED", "alice", text, qs.wall_us / 1000.0,
        "trace-" + query_id, query_stats=qs, session={"sf": sf})


def test_baseline_key_is_the_statement_text_salted_with_sf():
    """Two runs of one text share a baseline key however the text is
    spaced or cased; the same text at another scale does not."""
    text = "SELECT count(*) FROM lineitem WHERE quantity > 11"
    a = _record_of_run("qh-key-1", text, 0.01)
    b = _record_of_run("qh-key-2", text.lower().replace(" ", "  "), 0.01)
    c = _record_of_run("qh-key-3", text, 0.02)
    assert a["fingerprint"] == b["fingerprint"]
    assert a["fingerprint"] != c["fingerprint"]


def test_record_carries_no_kernel_rows():
    """The record's account of time is its QueryStats rollup: no
    per-kernel rows, no fingerprints of a registry beside it."""
    rec = _record_of_run("qh-nokern-1",
                         "SELECT count(*) FROM lineitem", 0.01)
    assert "kernels" not in rec and "topKernels" not in rec
    assert rec["stats"]["execute_us"] > 0


def test_ring_rotation_retention_and_reload(tmp_path, recorder):
    d = str(tmp_path / "ring")
    a = QueryHistoryArchive(capacity=32, history_dir=d,
                            max_file_records=2, max_files=2,
                            baseline=RollingBaseline(min_samples=3))
    for i in range(7):
        a.add(QueryHistoryArchive.record_of(
            f"q{i}", "FINISHED", "u", "SELECT 1", 10.0 + i, f"t{i}"))
    files = sorted(os.listdir(d))
    assert len(files) == 2, "retention cap holds the ring at max_files"
    assert files == ["history-00000002.jsonl", "history-00000003.jsonl"]
    # reload: records + baselines survive a restart, alarms do NOT refire
    before = dict(perf_regression_totals())
    a2 = QueryHistoryArchive(capacity=32, history_dir=d,
                             baseline=RollingBaseline(min_samples=1))
    assert a2.size() == 3  # 2 full files ring, newest has 1 line
    assert perf_regression_totals() == before
    key = a2.records()[0]["fingerprint"]
    assert a2.baseline.samples_of(key)["wall_us"], \
        "reload warms the rolling baseline"
    # appends resume on the newest ring file index
    a2.add(QueryHistoryArchive.record_of(
        "q9", "FINISHED", "u", "SELECT 1", 50.0, "t9"))
    assert sorted(os.listdir(d))[-1] == "history-00000003.jsonl"


def test_ring_reload_terminates_torn_tail(tmp_path, recorder):
    """A crash mid-write leaves a partial line with no newline; reload
    must terminate it so the next append starts a fresh line instead
    of gluing onto the torn one (which would lose BOTH records)."""
    d = tmp_path / "ring"
    d.mkdir()
    good = json.dumps({"queryId": "q-ok", "state": "FINISHED",
                       "tsUs": 1, "fingerprint": "f", "stats": {}})
    (d / "history-00000000.jsonl").write_text(
        good + "\n" + '{"queryId": "q-torn", "sta')
    a = QueryHistoryArchive(capacity=8, history_dir=str(d),
                            baseline=RollingBaseline(min_samples=3))
    assert [r["queryId"] for r in a.records()] == ["q-ok"]
    a.add(QueryHistoryArchive.record_of(
        "q-after", "FINISHED", "u", "SELECT 1", 5.0, "t"))
    a2 = QueryHistoryArchive(capacity=8, history_dir=str(d),
                             baseline=RollingBaseline(min_samples=3))
    assert {r["queryId"] for r in a2.records()} == {"q-ok", "q-after"}


def test_failed_queries_archive_but_never_baseline(archive, recorder):
    for i in range(3):
        archive.add(QueryHistoryArchive.record_of(
            "qf%d" % i, "FINISHED", "u", "SELECT 2", 100.0, "t"))
    key = archive.records()[0]["fingerprint"]
    n_before = len(archive.baseline.samples_of(key)["wall_us"])
    before = dict(perf_regression_totals())
    # a FAILED query with a catastrophic wall: archived, not gated,
    # not absorbed
    archive.add(QueryHistoryArchive.record_of(
        "qf-fail", "FAILED", "u", "SELECT 2", 60_000.0, "t"))
    assert archive.records()[0]["queryId"] == "qf-fail"
    assert perf_regression_totals() == before
    assert len(archive.baseline.samples_of(key)["wall_us"]) == n_before


def test_sentinel_breach_counts_events_and_dumps(archive, recorder):
    before = dict(perf_regression_totals())
    for i in range(3):
        archive.add(QueryHistoryArchive.record_of(
            f"qs{i}", "FINISHED", "u", "SELECT 3", 1000.0, f"ts{i}"))
    breaches = archive.add(QueryHistoryArchive.record_of(
        "qs-slow", "FINISHED", "u", "SELECT 3", 60_000.0, "ts-slow"))
    assert [b["metric"] for b in breaches] == ["wall_us"]
    # counter
    assert perf_regression_totals().get("wall_us", 0) == \
        before.get("wall_us", 0) + 1
    # the archived record names its regressions
    assert archive.records()[0]["regressions"] == ["wall_us"]
    # flight event, trace-linked
    evts = recorder.events(kind="perf_regression")
    assert evts and evts[-1]["metric"] == "wall_us"
    assert evts[-1]["trace"] == "ts-slow"
    # auto dump, header cross-linking the trace
    path = recorder.dump_path("qs-slow")
    assert path is not None and path.endswith(".perf_regression.jsonl")
    head = json.loads(open(path).readline())["dump"]
    assert head["traceId"] == "ts-slow"
    assert head["regressions"] == "wall_us"


# -- live statement tier: endpoint, SQL surface, metrics ----------------

def test_statement_history_endpoint_sql_and_metrics(archive, recorder):
    from presto_tpu.client import execute
    from presto_tpu.server.statement import StatementServer
    with StatementServer(sf=0.01) as srv:
        r1 = execute(srv.url, "SELECT count(*) FROM region")
        assert r1.data == [[5]]
        r2 = execute(srv.url, "SELECT count(*) FROM nation")
        _wait_for(lambda: archive.size() >= 2)
        with urllib.request.urlopen(f"{srv.url}/v1/history") as resp:
            doc = json.loads(resp.read().decode())
        assert doc["cluster"] is True
        recs = {r["queryId"]: r for r in doc["records"]}
        assert r1.query_id in recs and r2.query_id in recs
        rec = recs[r1.query_id]
        assert rec["state"] == "FINISHED"
        assert rec["stats"]["output_rows"] == 1
        assert rec["stats"]["wall_us"] > 0
        assert rec["traceId"] and rec["fingerprint"]
        # newest-first ordering
        ts = [r["tsUs"] for r in doc["records"]]
        assert ts == sorted(ts, reverse=True)
        # the archive as SQL (system connector)
        rs = execute(srv.url, "SELECT query_id, state, wall_us FROM "
                              "system.query_history")
        by_id = {row[0]: row for row in rs.data}
        assert r1.query_id in by_id
        assert by_id[r1.query_id][1] == "FINISHED"
        assert int(by_id[r1.query_id][2]) > 0
        # /v1/metrics: archive gauge + zero-shaped regression counters
        from presto_tpu.server.metrics import parse_prometheus
        with urllib.request.urlopen(f"{srv.url}/v1/metrics") as resp:
            fams = parse_prometheus(resp.read().decode())
        assert fams["presto_tpu_query_history_entries"][""] >= 2
        reg = fams["presto_tpu_perf_regressions_total"]
        for spec in SENTINEL_SPECS:
            assert f'{{metric="{spec.name}"}}' in reg


def test_fingerprint_salted_with_effective_sf(archive, recorder):
    """The same SQL at different scale factors must not share a
    sentinel baseline -- including when sf comes from the SERVER
    constructor rather than a session property (a workload change is
    not a regression)."""
    from presto_tpu.client import execute
    from presto_tpu.server.statement import StatementServer
    text = "SELECT count(*) FROM supplier"
    ids = []
    for sf in (0.01, 0.05):
        with StatementServer(sf=sf) as srv:
            r = execute(srv.url, text)
            _wait_for(lambda: any(x["queryId"] == r.query_id
                                  for x in archive.records()))
            ids.append(r.query_id)
    by_id = {x["queryId"]: x for x in archive.records()}
    assert by_id[ids[0]]["fingerprint"] != by_id[ids[1]]["fingerprint"]


def test_worker_serves_history_slice(archive, recorder):
    from presto_tpu.server import TpuWorkerServer
    archive.add(QueryHistoryArchive.record_of(
        "qw1", "FINISHED", "u", "SELECT 1", 5.0, "tw1"))
    w = TpuWorkerServer(sf=0.01).start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{w.port}/v1/history") as resp:
            doc = json.loads(resp.read().decode())
        assert "processId" in doc
        assert any(r["queryId"] == "qw1" for r in doc["records"])
    finally:
        w.stop()


def test_merge_history_docs_dedups_process_and_query():
    r1 = {"queryId": "a", "tsUs": 2}
    r2 = {"queryId": "b", "tsUs": 1}
    merged = merge_history_docs([
        {"processId": "p1", "records": [r1, r2]},
        {"processId": "p1", "records": [r1]},          # same process
        {"processId": "p2", "records": [dict(r1), {"queryId": "c",
                                                   "tsUs": 3}]},
    ])
    assert [r["queryId"] for r in merged] == ["c", "a", "b"]


# -- end to end: the injected-regression acceptance criterion ----------

@pytest.fixture
def distributed_statement_server():
    """StatementServer fronting a 2-worker Coordinator (the
    test_trace_stitching topology): queries really cross the exchange
    seam, so an exchange.fetch failpoint lands on the query's wall."""
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


def test_e2e_sentinel_catches_exchange_delay_then_stays_silent(
        distributed_statement_server, archive, recorder):
    """The acceptance criterion end to end: warm a per-fingerprint
    baseline on a distributed group-by, arm a delay(ms) schedule at the
    exchange.fetch site, and the regression fires deterministically --
    counter + flight event + auto dump, visible on /v1/metrics -- then
    the clean replay (failpoint disarmed) raises nothing new."""
    from presto_tpu import failpoints
    from presto_tpu.client import execute
    srv = distributed_statement_server
    q = ("SELECT custkey, count(*) AS c FROM orders "
         "GROUP BY custkey")
    sizes = archive.size()
    # five warm-ups where min_samples is 3: the median then holds
    # against the cold first run and one more slow one (six workers
    # share this machine: with three samples, two slow ones widened the
    # band past the injected stall)
    for i in range(5):
        execute(srv.url, q)
        _wait_for(lambda: archive.size() >= sizes + i + 1)
    key = archive.records()[0]["fingerprint"]
    assert len(archive.baseline.samples_of(key)["wall_us"]) == 5
    before = dict(perf_regression_totals())

    # one 2500ms stall per exchange pull: far outside any warm band
    failpoints.configure("exchange.fetch=delay(2500)")
    try:
        slow = execute(srv.url, q)
        _wait_for(lambda: archive.records()[0]["queryId"] ==
                  slow.query_id)
    finally:
        failpoints.disarm_all()
    slow_rec = archive.records()[0]
    assert slow_rec["fingerprint"] == key, \
        "the regressed run gates against the warmed baseline"
    assert "wall_us" in slow_rec["regressions"]
    assert slow_rec["failpointHits"] >= 1, \
        "the record counts the trace-linked injected faults"
    # record visibility implies its alarms already landed (_add_inner
    # raises alarms BEFORE publishing the record)
    assert perf_regression_totals().get("wall_us", 0) > \
        before.get("wall_us", 0)
    evts = [e for e in recorder.events(kind="perf_regression")
            if e.get("queryId") == slow.query_id]
    assert evts and evts[0]["fingerprint"] == key
    dump = _wait_for(lambda: recorder.dump_path(slow.query_id))
    assert dump is not None and dump.endswith(".perf_regression.jsonl")
    head = json.loads(open(dump).readline())["dump"]
    assert head["traceId"] == slow_rec["traceId"]
    # the breach shows on the live tier's /v1/metrics
    from presto_tpu.server.metrics import parse_prometheus
    with urllib.request.urlopen(f"{srv.url}/v1/metrics") as resp:
        fams = parse_prometheus(resp.read().decode())
    assert fams["presto_tpu_perf_regressions_total"][
        '{metric="wall_us"}'] >= 1
    # ... and in system.query_history
    rs = execute(srv.url, "SELECT query_id, regressions FROM "
                          "system.query_history")
    by_id = dict(rs.data)
    assert "wall_us" in by_id[slow.query_id]

    # clean replay: no failpoint, no new alarm
    after_injected = dict(perf_regression_totals())
    clean = execute(srv.url, q)
    _wait_for(lambda: archive.records()[0]["queryId"] == clean.query_id)
    assert perf_regression_totals() == after_injected
    assert archive.records()[0]["regressions"] == []
    assert recorder.dump_path(clean.query_id) is None


# -- flight-recorder dump retention (satellite) -------------------------

def test_flight_dump_dir_retention_evicts_oldest(tmp_path):
    d = str(tmp_path / "dumps")
    r = FlightRecorder(capacity=16, dump_dir=d, max_dump_dir_files=2)
    paths = []
    for i in range(4):
        p = r.maybe_dump(f"k{i}", "slow")
        assert p is not None
        paths.append(p)
        time.sleep(0.02)  # distinct mtimes -> deterministic order
    left = sorted(os.listdir(d))
    assert len(left) == 2
    assert os.path.basename(paths[0]) not in left   # oldest evicted
    assert os.path.basename(paths[3]) in left       # newest kept
    assert flight_recorder_totals()["evicted"] >= 2
    from presto_tpu.server.metrics import (flight_recorder_families,
                                           parse_prometheus,
                                           render_prometheus)
    fams = parse_prometheus(
        render_prometheus(flight_recorder_families()).decode())
    assert fams["presto_tpu_flight_dumps_evicted_total"][""] >= 2
    # the perf_regression reason is part of the stable dump shape
    assert '{reason="perf_regression"}' in \
        fams["presto_tpu_flight_recorder_dumps_total"]


# -- structured log correlation (satellite) -----------------------------

def test_log_records_carry_ambient_trace_and_query_ids():
    from presto_tpu.server.tracing import TraceContext, trace_context
    from presto_tpu.utils.log import JsonFormatter, ensure_log_context
    ensure_log_context()
    captured = []

    class _Capture(logging.Handler):
        def emit(self, record):
            captured.append(record)

    logger = logging.getLogger("presto_tpu.test_history")
    h = _Capture()
    logger.addHandler(h)
    logger.setLevel(logging.DEBUG)
    try:
        with trace_context(TraceContext("trace-log-1", "span1")):
            logger.debug("inside")
        logger.debug("outside")
    finally:
        logger.removeHandler(h)
    inside, outside = captured
    assert inside.trace_id == "trace-log-1"
    assert outside.trace_id == ""
    doc = json.loads(JsonFormatter().format(inside))
    assert doc["trace_id"] == "trace-log-1"
    assert doc["message"] == "inside"
    assert doc["logger"] == "presto_tpu.test_history"


def test_log_json_handler_opt_in(monkeypatch):
    import presto_tpu.utils.log as L
    monkeypatch.setenv("PRESTO_TPU_LOG_JSON", "1")
    L.ensure_log_context()
    logger = logging.getLogger("presto_tpu")
    try:
        assert L._json_handler is not None
        assert L._json_handler in logger.handlers
        assert isinstance(L._json_handler.formatter, L.JsonFormatter)
        # propagation is off while the JSON handler owns the stream: a
        # configured root handler must not re-emit records as text
        assert logger.propagate is False
    finally:
        monkeypatch.setenv("PRESTO_TPU_LOG_JSON", "0")
        L.ensure_log_context()   # opt-out removes the handler
    assert L._json_handler is None
    assert logger.propagate is True


# -- scrape-side history section (satellite) ----------------------------

def test_scrape_history_section_always_present(archive, recorder):
    sys.path.insert(0, _SCRIPTS)
    import importlib
    diff = importlib.import_module("scrape_metrics").diff
    from presto_tpu.server.metrics import (parse_prometheus,
                                           query_history_families,
                                           render_prometheus)

    def scrape():
        return parse_prometheus(
            render_prometheus(query_history_families()).decode())

    before = scrape()
    out = diff(before, scrape())
    # zeros INCLUDED: every regression metric reports a 0 delta, the
    # gauge reports its current value
    for spec in SENTINEL_SPECS:
        assert out["history"][
            f'presto_tpu_perf_regressions_total{{metric="{spec.name}"}}'
        ] == 0.0
    assert "presto_tpu_query_history_entries" in \
        {k.split("{")[0] for k in out["history"]}
    # a breach in the window shows as a positive delta in the section
    for i in range(3):
        archive.add(QueryHistoryArchive.record_of(
            f"qd{i}", "FINISHED", "u", "SELECT 9", 100.0, "t"))
    archive.add(QueryHistoryArchive.record_of(
        "qd-slow", "FINISHED", "u", "SELECT 9", 60_000.0, "t"))
    out = diff(before, scrape())
    assert out["history"][
        'presto_tpu_perf_regressions_total{metric="wall_us"}'] >= 1.0


# -- the offline gate (scripts/perfgate.py) -----------------------------

def _perfgate():
    sys.path.insert(0, _SCRIPTS)
    import importlib
    return importlib.import_module("perfgate")


def _artifact(tmp_path, name, value, wall, staged=324.0,
              platform="cpu-fallback (test)"):
    doc = {"parsed": {"metric": "tpch_sf1_q1_rows_per_sec",
                      "value": value, "unit": "rows/s",
                      "detail": {"query_wall_s": wall,
                                 "staged_mb": staged,
                                 "platform": platform}}}
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def test_perfgate_cli_deterministic_and_clean(tmp_path, capsys):
    pg = _perfgate()
    arts = [_artifact(tmp_path, f"BENCH_r0{i}.json", 1000 + i * 10,
                      5.0 + i * 0.01) for i in range(1, 5)]
    base = str(tmp_path / "PERF_BASELINE.json")
    assert pg.main(["--update-baseline", "--baseline", base, *arts]) == 0
    capsys.readouterr()
    assert pg.main(["--json", "--baseline", base, *arts]) == 0
    out1 = capsys.readouterr().out
    assert pg.main(["--json", "--baseline", base, *arts]) == 0
    out2 = capsys.readouterr().out
    assert out1 == out2, "identical inputs -> byte-identical verdicts"
    doc = json.loads(out2)
    assert doc["version"] == 1 and doc["findings"] == []
    assert doc["candidates"] == ["BENCH_r04.json"]
    assert doc["metricsChecked"] == 3


def test_perfgate_cli_catches_regression(tmp_path, capsys):
    pg = _perfgate()
    arts = [_artifact(tmp_path, f"BENCH_r0{i}.json", 1000, 5.0)
            for i in range(1, 5)]
    base = str(tmp_path / "PERF_BASELINE.json")
    assert pg.main(["--update-baseline", "--baseline", base, *arts]) == 0
    capsys.readouterr()
    # the candidate: rows/s collapsed, wall 3x, staged bytes re-widened
    bad = _artifact(tmp_path, "BENCH_r09.json", 300, 15.0, staged=648.0)
    assert pg.main(["--json", "--baseline", base, *arts, bad]) == 1
    doc = json.loads(capsys.readouterr().out)
    got = {f["metric"] for f in doc["findings"]}
    assert got == {"rows_per_sec", "query_wall_s", "staged_mb"}
    # an unknown platform key is reported as unbaselined, never a FAIL
    foreign = _artifact(tmp_path, "BENCH_r10.json", 1.0, 99.0,
                        platform="tpu")
    assert pg.main(["--baseline", base, *arts, foreign]) == 0
    assert "no baseline entry" in capsys.readouterr().out


def test_perfgate_explicit_paths_keep_caller_order(tmp_path, capsys):
    """Explicit artifact arguments are oldest..newest IN THE CALLER'S
    ORDER: the last argument is the candidate, even when basenames
    sort the other way."""
    pg = _perfgate()
    old = _artifact(tmp_path, "zz_old_run.json", 1000, 5.0)
    new = _artifact(tmp_path, "aa_new_run.json", 200, 20.0)
    base = str(tmp_path / "PERF_BASELINE.json")
    assert pg.main(["--update-baseline", "--baseline", base,
                    old, old, old, old]) == 0
    capsys.readouterr()
    assert pg.main(["--json", "--baseline", base, old, new]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["candidates"] == ["aa_new_run.json"]
    assert doc["findings"]


def test_perfgate_excludes_candidate_from_its_own_baseline(tmp_path,
                                                           capsys):
    """A baseline rebuilt over artifacts INCLUDING the candidate must
    not let the candidate's own sample widen its acceptance band: a
    sustained two-round regression still breaches because the
    candidate's contribution is left out before comparing."""
    pg = _perfgate()
    arts = [_artifact(tmp_path, f"BENCH_r0{i}.json", 1000, w)
            for i, w in ((1, 5.0), (2, 5.0), (3, 15.0), (4, 15.0))]
    base = str(tmp_path / "PERF_BASELINE.json")
    # --update-baseline absorbs all four, then gates the newest
    # against the other three: median 5.0, not the self-diluted 10.0
    assert pg.main(["--json", "--update-baseline", "--baseline", base,
                    *arts]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert any(f["metric"] == "query_wall_s" and f["median"] == 5.0
               for f in doc["findings"])


def test_perfgate_cli_exit_2_on_bad_inputs(tmp_path, capsys):
    pg = _perfgate()
    assert pg.main([str(tmp_path / "missing.json")]) == 2
    junk = tmp_path / "junk.json"
    junk.write_text("{\"not\": \"an artifact\"}")
    assert pg.main([str(junk)]) == 2
    art = _artifact(tmp_path, "BENCH_r01.json", 1000, 5.0)
    badbase = tmp_path / "bad_baseline.json"
    badbase.write_text("[]")
    assert pg.main(["--baseline", str(badbase), art]) == 2


def test_perfgate_gates_committed_artifacts_clean(capsys):
    """The lint_all.sh invocation: the committed BENCH trajectory must
    pass against the committed PERF_BASELINE.json (a PR that regresses
    the trajectory updates the baseline consciously, like tpulint's)."""
    pg = _perfgate()
    assert pg.main(["--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["findings"] == []
    assert doc["artifacts"], "committed BENCH artifacts present"
