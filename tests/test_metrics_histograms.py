"""Histogram metrics (server/metrics.py).

Covers: the Histogram merge law (associative / commutative / identity,
the same contract QueryStats.merge carries), exposition-format
compliance (cumulative ``le`` ladder, ``+Inf`` == ``_count``,
exemplars, parse_prometheus round-trip), concurrent ``observe()``
under threads, exemplar -> trace linkage, and scrape-side histogram
quantile / counter-monotonicity analysis."""

import json
import threading
import urllib.request

import pytest

from presto_tpu.server.metrics import (DEFAULT_BUCKETS, Histogram,
                                       MetricFamily, histogram_families,
                                       observe_histogram,
                                       parse_prometheus,
                                       quantile_from_buckets,
                                       render_prometheus,
                                       reset_histograms)


@pytest.fixture(autouse=True)
def _clean_state():
    yield
    reset_histograms()
    from presto_tpu.server.tracing import set_tracer
    set_tracer(None)


# ---------------------------------------------------------------------------
# Histogram value type
# ---------------------------------------------------------------------------


def test_histogram_merge_law():
    a, b, c = Histogram(), Histogram(), Histogram()
    a.observe(0.003, trace_id="ta")
    a.observe(0.4)
    b.observe(7.0, trace_id="tb")
    c.observe(0.003, trace_id="tc")
    # associative
    assert a.merge(b).merge(c).to_json() == a.merge(b.merge(c)).to_json()
    # commutative
    assert a.merge(b).to_json() == b.merge(a).to_json()
    # identity
    ident = Histogram()
    assert a.merge(ident).to_json() == a.to_json()
    assert ident.merge(a).to_json() == a.to_json()
    m = a.merge(b).merge(c)
    assert m.count == 4
    assert abs(m.sum - 7.406) < 1e-9
    # exemplar law: per bucket, the max-latency observation survives
    snap = m.snapshot()
    kept = {e[0] for e in snap["exemplars"] if e}
    assert "tb" in kept
    # 0.003 landed twice (ta then tc at equal value): later >= wins
    assert "tc" in kept
    # different bucket schemes refuse to merge
    with pytest.raises(ValueError):
        Histogram((1.0, 2.0)).merge(Histogram((1.0, 3.0)))


def test_histogram_json_round_trip():
    h = Histogram()
    h.observe(0.02, trace_id="x")
    h.observe(50.0)
    rt = Histogram.from_json(json.loads(json.dumps(h.to_json())))
    assert rt.to_json() == h.to_json()


def test_concurrent_observe_under_threads():
    h = Histogram()
    n_threads, per_thread = 8, 500

    def worker(i):
        for k in range(per_thread):
            h.observe(0.001 * ((i + k) % 7 + 1), trace_id=f"t{i}")

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = h.snapshot()
    assert snap["count"] == n_threads * per_thread
    assert sum(snap["counts"]) == n_threads * per_thread
    assert snap["sum"] > 0


def test_quantile_estimation_from_buckets():
    h = Histogram()
    for _ in range(90):
        h.observe(0.003)   # -> (0.0025, 0.005] bucket
    for _ in range(10):
        h.observe(30.0)    # -> (25, 50] bucket
    p50 = h.quantile(0.5)
    assert 0.0025 <= p50 <= 0.005
    p99 = h.quantile(0.99)
    assert 25.0 <= p99 <= 50.0
    # empty histogram reports 0
    assert Histogram().quantile(0.99) == 0.0


# ---------------------------------------------------------------------------
# exposition format
# ---------------------------------------------------------------------------


def test_exposition_cumulative_le_and_inf_equals_count():
    h = Histogram()
    h.observe(0.0002, trace_id="small")
    h.observe(3.0, trace_id="big")
    h.observe(3.0)
    fam = MetricFamily("t_hist_seconds", "histogram", "test").\
        add_histogram(h)
    text = "\n".join(fam.render()) + "\n"
    parsed = parse_prometheus(text)
    buckets = parsed["t_hist_seconds_bucket"]
    # cumulative: monotone non-decreasing in le order
    by_le = sorted(((float("inf") if 'le="+Inf"' in k
                     else float(k.split('le="')[1].split('"')[0]), v)
                    for k, v in buckets.items()), key=lambda x: x[0])
    vals = [v for _, v in by_le]
    assert vals == sorted(vals)
    # +Inf bucket == _count; _sum matches
    assert by_le[-1][1] == parsed["t_hist_seconds_count"][""] == 3
    assert abs(parsed["t_hist_seconds_sum"][""] - 6.0002) < 1e-6
    # one bucket line per bound plus +Inf
    assert len(buckets) == len(DEFAULT_BUCKETS) + 1
    # exemplars rendered and stripped cleanly by the parser
    assert 'trace_id="big"' in text and 'trace_id="small"' in text


def test_registry_families_on_both_tiers_and_declared_shape():
    # declared families render zeros before any observation
    fams = {f.name for f in histogram_families()}
    assert {"presto_tpu_query_latency_seconds",
            "presto_tpu_dispatch_queue_wait_seconds",
            "presto_tpu_stage_seconds",
            "presto_tpu_task_seconds"} <= fams
    observe_histogram("presto_tpu_stage_seconds", 0.02,
                      labels={"stage": "execute"}, trace_id="tt")
    text = render_prometheus(histogram_families()).decode()
    parsed = parse_prometheus(text)
    key = '{le="+Inf",stage="execute"}'
    assert parsed["presto_tpu_stage_seconds_bucket"][key] == 1


def _hist_family_count(url):
    with urllib.request.urlopen(f"{url}/v1/metrics") as r:
        text = r.read().decode()
    names = [line.split()[2] for line in text.splitlines()
             if line.startswith("# TYPE")
             and line.rstrip().endswith("histogram")]
    parse_prometheus(text)  # must stay valid exposition text
    return names


def test_metrics_histograms_on_both_tiers():
    from presto_tpu.client import execute
    from presto_tpu.server import TpuWorkerServer
    from presto_tpu.server.statement import StatementServer
    with StatementServer(sf=0.01) as srv:
        execute(srv.url, "SELECT count(*) AS n FROM region",
                session={"sf": "0.01"})
        coord_names = _hist_family_count(srv.url)
        assert len(coord_names) >= 4
        assert "presto_tpu_query_latency_seconds" in coord_names
        assert "presto_tpu_dispatch_queue_wait_seconds" in coord_names
        # the executed query landed observations, exemplar'd
        with urllib.request.urlopen(f"{srv.url}/v1/metrics") as r:
            text = r.read().decode()
        parsed = parse_prometheus(text)
        lat = parsed["presto_tpu_query_latency_seconds_count"][""]
        assert lat >= 1
    w = TpuWorkerServer(sf=0.01).start()
    try:
        worker_names = _hist_family_count(f"http://127.0.0.1:{w.port}")
        assert len(worker_names) >= 4
        assert "presto_tpu_query_latency_seconds" in worker_names
        assert "presto_tpu_dispatch_queue_wait_seconds" in worker_names
    finally:
        w.stop()


def test_exemplar_links_to_trace():
    """A /v1/metrics exemplar's trace id resolves on GET /v1/trace.
    Exemplars render only under negotiated OpenMetrics (a classic
    0.0.4 scraper would reject the suffix); the default scrape stays
    exemplar-free and strictly valid."""
    from presto_tpu.client import execute
    from presto_tpu.server.statement import StatementServer
    from presto_tpu.server.tracing import RecordingTracer, set_tracer
    set_tracer(RecordingTracer())
    with StatementServer(sf=0.01) as srv:
        execute(srv.url, "SELECT count(*) AS n FROM nation",
                session={"sf": "0.01"})
        # default Accept: classic text format, NO exemplar suffixes
        with urllib.request.urlopen(f"{srv.url}/v1/metrics") as r:
            assert "0.0.4" in r.headers["Content-Type"]
            assert " # {" not in r.read().decode()
        req = urllib.request.Request(
            f"{srv.url}/v1/metrics",
            headers={"Accept": "application/openmetrics-text"})
        with urllib.request.urlopen(req) as r:
            assert "openmetrics" in r.headers["Content-Type"]
            text = r.read().decode()
        assert text.rstrip().endswith("# EOF")
        ex_lines = [l for l in text.splitlines()
                    if l.startswith("presto_tpu_query_latency_seconds_"
                                    "bucket") and " # {" in l]
        assert ex_lines, "query latency carried no exemplar"
        tid = ex_lines[0].split('trace_id="')[1].split('"')[0]
        with urllib.request.urlopen(f"{srv.url}/v1/trace/{tid}") as r:
            doc = json.loads(r.read().decode())
        assert doc["spans"]
        assert any(s["name"] == "query" for s in doc["spans"])


# ---------------------------------------------------------------------------
# scrape-side analysis (scripts/scrape_metrics.py)
# ---------------------------------------------------------------------------


def _scrape_diff():
    import importlib
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "scripts"))
    return importlib.import_module("scrape_metrics").diff


def test_scrape_diff_histogram_quantiles_and_violations():
    diff = _scrape_diff()
    h = Histogram()
    before_fams = histogram_families()
    before = parse_prometheus(
        render_prometheus(before_fams).decode())
    before["presto_tpu_queries_total"] = {'{state="FINISHED"}': 10.0}
    for _ in range(95):
        observe_histogram("presto_tpu_query_latency_seconds", 0.003)
    for _ in range(5):
        observe_histogram("presto_tpu_query_latency_seconds", 30.0)
    after = parse_prometheus(
        render_prometheus(histogram_families()).decode())
    # a counter that DECREASED between scrapes
    after["presto_tpu_queries_total"] = {'{state="FINISHED"}': 4.0}
    out = diff(before, after)
    win = out["histograms"]["presto_tpu_query_latency_seconds"][""]
    assert win["count_delta"] == 100
    assert 0.0025 <= win["p50"] <= 0.005
    assert 25.0 <= win["p99"] <= 50.0
    # the decrease is flagged, not silently diffed negative
    key = 'presto_tpu_queries_total{state="FINISHED"}'
    assert out["violations"][key] == -6
    assert key not in out["counters"]
    del h


def test_quantile_from_buckets_shared_helper():
    bounds = [0.001, 0.01, 0.1]
    # 10 obs in (0.001, 0.01], 10 in +Inf
    assert quantile_from_buckets(bounds, [0, 10, 0, 10], 0.25) <= 0.01
    assert quantile_from_buckets(bounds, [0, 10, 0, 10], 0.99) == 0.1
    assert quantile_from_buckets(bounds, [0, 0, 0, 0], 0.5) == 0.0
