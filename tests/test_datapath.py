"""Data-path waterfall (exec/datapath.py): hop-ledger merge law,
seeded ceilings-probe determinism, both tiers' /v1/datapath shape, the
EXPLAIN ANALYZE tail, the SIZE_BUCKETS ladder, the scrape/ptop/bench
surfaces, and the q1 end-to-end reconciliation of datapath byte totals
against QueryStats staged bytes (the acceptance criterion: within 1%).
"""

import json
import urllib.request

import pytest

from presto_tpu.exec.datapath import (CEILING_KEYS, HOP_CEILING, HOPS,
                                      DatapathLedger, HopStats,
                                      bottleneck_verdict, ceilings_cached,
                                      clear_datapath, datapath_doc,
                                      datapath_for_query,
                                      hop_map_from_json, hop_map_to_json,
                                      merge_datapath_docs, merge_hop_maps,
                                      note_query, probe_ceilings,
                                      process_totals, record_hop,
                                      recording)

# the official TPC-H q1 text (dialect-adapted exactly like bench.py)
TPCH_Q1 = """
SELECT returnflag, linestatus,
       sum(quantity) AS sum_qty,
       sum(extendedprice) AS sum_base_price,
       sum(extendedprice * (1 - discount)) AS sum_disc_price,
       sum(extendedprice * (1 - discount) * (1 + tax)) AS sum_charge,
       avg(quantity) AS avg_qty,
       avg(extendedprice) AS avg_price,
       avg(discount) AS avg_disc,
       count(*) AS count_order
FROM lineitem
WHERE shipdate <= date '1998-09-02'
GROUP BY returnflag, linestatus
ORDER BY returnflag, linestatus
"""


def _h(hop, b, w, i=1, m=None):
    return HopStats(hop, bytes=b, wall_us=w, invocations=i,
                    max_wall_us=w if m is None else m)


# -- merge law -----------------------------------------------------------


def test_hop_merge_identity():
    a = _h("device_put", 100, 10)
    z = HopStats("device_put")
    assert a.merge(z) == a
    assert z.merge(a) == a


def test_hop_merge_commutative_associative():
    a = _h("kernel", 100, 10, 1, 10)
    b = _h("kernel", 50, 40, 2, 30)
    c = _h("kernel", 7, 3, 1, 3)
    assert a.merge(b) == b.merge(a)
    assert a.merge(b).merge(c) == a.merge(b.merge(c))
    m = a.merge(b).merge(c)
    assert (m.bytes, m.wall_us, m.invocations, m.max_wall_us) == \
        (157, 53, 4, 30)


def test_hop_map_merge_and_json_round_trip():
    x = {"decode": _h("decode", 10, 1), "kernel": _h("kernel", 5, 2)}
    y = {"kernel": _h("kernel", 3, 4), "device_put": _h("device_put", 9, 9)}
    m = merge_hop_maps(x, y)
    assert merge_hop_maps(y, x) == m
    assert merge_hop_maps(x, {}) == x          # empty map is identity
    back = hop_map_from_json(hop_map_to_json(m))
    assert back == m


def test_query_stats_carries_datapath_through_json_and_merge():
    """The worker-slice stitching contract: QueryStats serializes the
    hop map through the task-status wire shape and folds it in
    merge() (so slices from any number of workers stitch in any
    order)."""
    from presto_tpu.exec.stats import QueryStats
    a = QueryStats(datapath={"device_put": _h("device_put", 100, 10)})
    b = QueryStats(datapath={"device_put": _h("device_put", 40, 5),
                             "decode": _h("decode", 7, 1)})
    m = a.merge(b)
    assert m.datapath["device_put"].bytes == 140
    assert m.datapath["decode"].bytes == 7
    rt = QueryStats.from_json(m.to_json())
    assert rt.datapath == m.datapath
    # old documents without the key parse to an empty map
    doc = m.to_json()
    doc.pop("datapath")
    assert QueryStats.from_json(doc).datapath == {}


# -- ambient recording + process registry --------------------------------


def test_record_hop_folds_ambient_and_process():
    clear_datapath()
    ledger = DatapathLedger()
    with recording(ledger):
        record_hop("exchange_fetch", 1000, 0.002)
        record_hop("exchange_fetch", 500, 0.001)
    record_hop("client_drain", 10, 0.0)  # outside: process-only
    hops = ledger.snapshot_hops()
    assert hops["exchange_fetch"].bytes == 1500
    assert hops["exchange_fetch"].invocations == 2
    assert "client_drain" not in hops
    totals = process_totals()
    assert totals["exchange_fetch"].bytes == 1500
    assert totals["client_drain"].invocations == 1
    # every catalog hop is present (stable zero shape)
    assert set(totals) == set(HOPS)


def test_note_query_cross_link():
    clear_datapath()
    note_query("qx", {"kernel": _h("kernel", 10, 2)})
    note_query("qx", {"kernel": _h("kernel", 5, 1)})
    doc = datapath_for_query("qx")
    assert doc["kernel"]["bytes"] == 15
    assert datapath_for_query("missing") == {}


# -- ceilings probe ------------------------------------------------------


def test_ceilings_probe_cached_and_complete():
    c1 = probe_ceilings()
    assert set(c1) == set(CEILING_KEYS)
    assert all(v > 0 for v in c1.values())
    # cached: a second call returns the identical measurement (no
    # re-probe, the determinism the verdict comparator stands on)
    assert probe_ceilings() == c1
    assert ceilings_cached() == c1
    # refresh re-measures but keeps the key set
    c2 = probe_ceilings(refresh=True)
    assert set(c2) == set(CEILING_KEYS)


def test_probe_does_not_pollute_the_ledger():
    clear_datapath()
    probe_ceilings(refresh=True)  # exercises serialize/deserialize
    totals = process_totals()
    assert totals["exchange_serialize"].invocations == 0
    assert totals["decode"].invocations == 0


def test_every_hop_maps_to_a_measured_ceiling():
    assert set(HOP_CEILING) == set(HOPS)
    assert set(HOP_CEILING.values()) <= set(CEILING_KEYS)


# -- verdict (pure function) ---------------------------------------------


def test_bottleneck_verdict_pure_and_named():
    ceilings = {"host_memcpy": 1e10, "device_put": 1e10,
                "page_serde": 1e9, "loopback_http": 1e9}
    hops = {
        # 80% of wall at 1% utilization: the bottleneck
        "device_put": _h("device_put", 8_000_000, 80_000),
        # 20% of wall at full ceiling: healthy
        "decode": _h("decode", 200_000_000, 20_000),
    }
    v = bottleneck_verdict(hops, ceilings)
    assert v["hop"] == "device_put"
    assert v["belowBand"] is True
    assert v["wallShare"] == pytest.approx(0.8)
    # pure: identical inputs, identical verdict
    assert bottleneck_verdict(hops, ceilings) == v
    # every hop at ceiling: largest wall share named, belowBand False
    fast = {"decode": _h("decode", 10**9, 100_000),
            "kernel": _h("kernel", 10**9, 50_000)}
    v2 = bottleneck_verdict(fast, ceilings)
    assert v2["hop"] == "decode" and v2["belowBand"] is False
    assert bottleneck_verdict({}, ceilings) is None


def test_merge_datapath_docs_dedups_process_slices():
    row = {"hops": {"kernel": _h("kernel", 10, 5).to_json()},
           "ceilings": {"device_put": 100.0}}
    docs = [{"processId": "p1", **row},
            {"processId": "p1", **row},     # same process twice
            {"processId": "p2", **row}]
    merged = merge_datapath_docs(docs)
    assert merged["hops"]["kernel"]["bytes"] == 20  # p1 once + p2
    assert set(merged["hops"]) == set(HOPS)         # zero shape


# -- SIZE_BUCKETS ladder -------------------------------------------------


def test_size_buckets_ladder_shape_and_merge_law():
    from presto_tpu.server.metrics import SIZE_BUCKETS, Histogram
    assert SIZE_BUCKETS[0] == 1024.0
    assert SIZE_BUCKETS[-1] == float(4 << 30)
    assert list(SIZE_BUCKETS) == sorted(SIZE_BUCKETS)
    a, b = Histogram(SIZE_BUCKETS), Histogram(SIZE_BUCKETS)
    a.observe(2048.0, trace_id="ta")
    b.observe(1 << 20)
    m = a.merge(b)
    snap = m.snapshot()
    assert snap["count"] == 2
    # merge is elementwise add and keeps the exemplar contract
    assert sum(snap["counts"]) == 2
    assert any(e is not None and e[0] == "ta" for e in snap["exemplars"])
    # a size ladder never merges with the time ladder
    from presto_tpu.server.metrics import DEFAULT_BUCKETS
    with pytest.raises(ValueError):
        Histogram(DEFAULT_BUCKETS).merge(a)


def test_datapath_histogram_declared_with_hop_vocabulary():
    """The literal label vocabulary in metrics.py must track the hop
    catalog (the closed-vocab convention every declared family
    uses)."""
    from presto_tpu.server.metrics import (_BUCKET_SCHEMES,
                                           _DECLARED_HISTOGRAMS,
                                           SIZE_BUCKETS)
    help_, presets = _DECLARED_HISTOGRAMS["presto_tpu_datapath_bytes"]
    assert {p["hop"] for p in presets} == set(HOPS)
    assert _BUCKET_SCHEMES["presto_tpu_datapath_bytes"] == SIZE_BUCKETS


def test_record_hop_observes_size_histogram():
    from presto_tpu.server.metrics import get_histogram
    clear_datapath()
    record_hop("exchange_fetch", 5000, 0.001)
    h = get_histogram("presto_tpu_datapath_bytes",
                      {"hop": "exchange_fetch"})
    assert h.buckets[0] == 1024.0      # size ladder, not time ladder
    assert h.snapshot()["count"] >= 1


# -- both tiers' /v1/datapath --------------------------------------------


def test_v1_datapath_worker_slice_and_cluster_merge():
    from presto_tpu.server import TpuWorkerServer
    from presto_tpu.server.statement import StatementServer
    w = TpuWorkerServer(sf=0.01).start()
    url = f"http://127.0.0.1:{w.port}"
    try:
        with urllib.request.urlopen(f"{url}/v1/datapath") as r:
            doc = json.loads(r.read().decode())
        # stable zero shape: every hop + every ceiling, always
        assert set(doc["hops"]) == set(HOPS)
        assert set(doc["ceilings"]) == set(CEILING_KEYS)
        assert doc["processId"]
        for row in doc["hops"].values():
            assert {"bytes", "wall_us", "invocations", "achievedBPerS",
                    "ceilingBPerS", "utilization"} <= set(row)
        with StatementServer(sf=0.01,
                             profile_workers=lambda: [url]) as srv:
            with urllib.request.urlopen(f"{srv.url}/v1/datapath") as r:
                cdoc = json.loads(r.read().decode())
        assert cdoc["cluster"] is True
        assert cdoc["workersPulled"] == 1
        assert set(cdoc["hops"]) == set(HOPS)
    finally:
        w.stop()


def test_cluster_doc_carries_staging_summary():
    from presto_tpu.server.statement import StatementServer
    with StatementServer(sf=0.01) as srv:
        doc = srv.cluster_doc()
    assert "datapath" in doc
    assert "stagingGbPerS" in doc["datapath"]


# -- EXPLAIN ANALYZE tail + q1 reconciliation ----------------------------


def test_explain_analyze_names_a_bottleneck_hop():
    from presto_tpu.plan import explain_analyze
    from presto_tpu.sql import plan_sql
    text = explain_analyze(plan_sql(TPCH_Q1), sf=0.01)
    assert "-- datapath --" in text
    tail = text[text.index("-- datapath --"):]
    assert "bottleneck: " in tail
    named = tail.split("bottleneck: ")[1].split()[0]
    assert named in HOPS
    # per-hop lines carry bytes/wall/utilization
    assert "device_put: bytes=" in tail
    assert "util=" in tail and "GB/s" in tail


def test_q1_datapath_reconciles_with_query_stats():
    """Acceptance criterion: the datapath device_put byte total (the
    host->HBM staging rung) reconciles with QueryStats' staged bytes
    within 1% on TPC-H q1."""
    from presto_tpu.sql import sql
    res = sql(TPCH_Q1, sf=0.01)
    qs = res.query_stats
    staged = qs.stages["staging"].bytes
    assert staged > 0
    put = qs.datapath["device_put"].bytes
    assert abs(put - staged) / staged < 0.01
    # the waterfall covered the host read and the kernel too
    assert qs.datapath["connector_read"].bytes > 0
    assert qs.datapath["kernel"].wall_us > 0


def test_system_datapath_sql():
    from presto_tpu.sql import sql
    sql("SELECT count(*) AS n FROM region", sf=0.01)
    res = sql("SELECT hop, bytes, wall_us, achieved_b_per_s, "
              "ceiling_b_per_s, utilization FROM system.datapath")
    rows = res.rows()
    assert {r[0] for r in rows} == set(HOPS)
    by_hop = {r[0]: r for r in rows}
    assert by_hop["device_put"][1] > 0          # bytes moved
    assert by_hop["device_put"][4] > 0          # ceiling measured


def test_flight_dump_embed_shape():
    clear_datapath()
    from presto_tpu.sql import sql
    sql("SELECT count(*) AS n FROM region", sf=0.01)
    doc = datapath_for_query("query")
    assert doc and "device_put" in doc


# -- scripts + gate surfaces ---------------------------------------------


def test_scrape_metrics_datapath_section():
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    import scrape_metrics
    from presto_tpu.server.metrics import (datapath_families,
                                           histogram_families,
                                           parse_prometheus,
                                           render_prometheus)
    text = render_prometheus(datapath_families()
                             + histogram_families()).decode()
    snap = parse_prometheus(text)
    d = scrape_metrics.diff(snap, snap)
    assert "datapath" in d
    # per-hop byte deltas, zeros included
    for hop in HOPS:
        key = f'presto_tpu_datapath_bytes_total{{hop="{hop}"}}'
        assert key in d["datapath"]
    # the size histogram's bucket-delta quantiles ride the section
    assert "presto_tpu_datapath_bytes" in d["datapath"]


def test_ptop_renders_staging_rate_and_per_query_gbps():
    import os
    import sys
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    import ptop
    doc = {"uptimeSeconds": 1.0, "queries": {},
           "datapath": {"stagingGbPerS": 0.25,
                        "bottleneck": "device_put"},
           "runningQueries": [
               {"queryId": "q1", "state": "RUNNING", "elapsedMs": 1000,
                "query": "SELECT 1",
                "progress": {"progressPercent": 10.0, "rows": 5,
                             "bytes": 500_000_000,
                             "stage": "staging"}}],
           "workers": []}
    out = ptop.render(doc)
    assert "staging 0.250 GB/s" in out
    assert "bottleneck device_put" in out
    assert "0.500GB/s" in out          # per-query achieved column


def test_perfgate_gates_staging_rate(tmp_path):
    from presto_tpu.exec.perfgate import BENCH_SPECS
    spec = {s.name: s for s in BENCH_SPECS}["staging_gb_per_s"]
    assert spec.higher_is_worse is False   # a staging rate regresses DOWN
    # load_artifact lifts the metric out of a BENCH detail document
    import perfgate as perfgate_cli
    art = tmp_path / "BENCH_rX.json"
    art.write_text(json.dumps({
        "parsed": {"metric": "tpch_sf1_q1_rows_per_sec", "value": 10,
                   "detail": {"platform": "cpu", "query_wall_s": 1.0,
                              "staging_gb_per_s": 0.21}}}))
    key, metrics, _meta = perfgate_cli.load_artifact(str(art))
    assert metrics["staging_gb_per_s"] == pytest.approx(0.21)
    assert key == "tpch_sf1_q1_rows_per_sec|cpu"


def test_a_resident_scan_puts_nothing_and_stages_the_same_bytes():
    """A whole-table scan of a memory table through the resident tier
    (a budget known: here the statement's `hbm_budget_bytes`): the
    first run stages and keeps its columns, the put reconciling with the
    staged bytes as above; the second takes them from HBM, so its
    waterfall has no `device_put` and no `narrow_cast`, its
    `connector_read` (the store's snapshot) carries no bytes, and the
    staged bytes and the kernel's are the first run's."""
    from presto_tpu.connectors import memory
    from presto_tpu.exec.resident import tier
    from presto_tpu.sql import sql
    sql("DROP TABLE IF EXISTS memory.dp_lineitem", sf=0.01)
    sql("CREATE TABLE memory.dp_lineitem AS SELECT returnflag, linestatus, "
        "quantity, extendedprice, discount, tax, shipdate "
        "FROM tpch.tiny.lineitem", sf=0.01)
    text = TPCH_Q1.replace("FROM lineitem", "FROM memory.dp_lineitem")
    tier().clear()
    try:
        first, again = (sql(text, sf=0.01, hbm_budget_bytes=1 << 30)
                        for _ in range(2))
    finally:
        tier().clear()
        memory.drop_table("dp_lineitem", if_exists=True)
    assert first.rows() == again.rows()
    staged = first.query_stats.stages["staging"].bytes
    put = first.query_stats.datapath["device_put"].bytes
    assert staged > 0 and abs(put - staged) / staged < 0.01
    hops = again.query_stats.datapath
    assert "device_put" not in hops and "narrow_cast" not in hops
    assert hops["connector_read"].bytes == 0
    assert again.query_stats.stages["staging"].bytes == staged
    assert hops["kernel"].bytes == first.query_stats.datapath["kernel"].bytes
