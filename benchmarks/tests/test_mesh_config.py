"""`tpch_sf30_memory_x4` and its cell `mesh4_sf30.q3`: the files, the
cell rehearsed on four virtual CPU devices (the load's schema replaced
by `tiny`, which is what the rehearsal's server serves), and the six
readers on made-up runs. CPU, no chip."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import layers, traffic  # noqa: E402

BENCH = bench_run.manifest()
CELL = {w["name"]: w for w in BENCH["workloads"]}["mesh4_sf30.q3"]
NEW = ("mesh_chips", "exchange_mb", "exchange_fill_pct", "device_put_ms",
       "exchange_collective_ms", "exchange_ici_roofline")


@pytest.fixture()
def config():
    return traffic.read_json("configs", "tpch_sf30_memory_x4")


def test_the_file_has_every_key_the_readme_lists(config):
    for key in ("source", "sf", "catalog", "columns", "load", "guarantees",
                "chips", "reduced", "reduced_why", "assumed"):
        assert config[key], key
    entry = {c["name"]: c for c in BENCH["configs"]}["tpch_sf30_memory_x4"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert 1 <= len(entry["why"]) <= 200 and entry["why"].isprintable()
    assert entry["file"] == "benchmarks/configs/tpch_sf30_memory_x4.json"
    assert entry["reduced"] == config["reduced"]
    assert set(config["reduced_why"]) == set(config["reduced"])
    # a cut of scale is listed; the spec's scale is not a cut
    assert ("sf" in config["reduced"]) == (config["sf"] != 30.0)
    assert config["lineitem_rows"] == int(6_000_000 * config["sf"])
    assert "WITH (workers = 4)" in config["load"]
    assert f"tpch.sf{int(config['sf'])}." in config["load"]
    sf10 = traffic.read_json("configs", "tpch_sf10_memory")
    assert config["guarantees"][:len(sf10["guarantees"])] == \
        sf10["guarantees"]
    assert "every shard" in config["guarantees"][-1]
    assert {"placement", "cluster", "workers_property"} <= \
        set(config["assumed"])


@pytest.mark.parametrize("table", ["lineitem", "orders", "customer"])
def test_whole_records_of_the_three_tables_q3_reads(config, table):
    from presto_tpu.connectors.tpch.generator import TPCH_SCHEMA
    assert config["columns"][table] == [c for c, _ in TPCH_SCHEMA[table]]
    assert sorted(config["columns"]) == ["customer", "lineitem", "orders"]


def test_the_cell_and_its_traffic():
    assert CELL == {"name": "mesh4_sf30.q3", "config": "tpch_sf30_memory_x4",
                    "traffic": "q3_stream", "chips": 4, "why": CELL["why"]}
    assert len(CELL["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    stream = traffic.read_json("traffic", "q3_stream")
    assert stream["clients"] == 1 and stream["trace_seconds"] == 8
    (template,) = stream["templates"]
    assert template["name"] == "q3" and template["weight"] == 1
    assert sorted(template["tables"]) == ["customer", "lineitem", "orders"]
    assert template["sets"] == [{"SEGMENT": "BUILDING",
                                 "DATE": "1995-03-15"}]


def test_the_six_metrics_list_the_new_cell_alone():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW:
        m = by_name[name]
        assert m["workloads"] == ["mesh4_sf30.q3"] and m["moves"] == "stmt_ms"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics",
            name + (".json" if name in ("mesh_chips", "exchange_mb",
                                        "device_put_ms") else ".py")))
    assert {by_name[n]["layer"] for n in NEW} == {
        "device", "exchange (mesh)", "split staging"}
    assert by_name["exchange_ici_roofline"]["unit"] == "%"


REHEARSAL = """
import io, json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {root!r})
from benchmarks import run as bench_run
from benchmarks.harness import traffic
config = traffic.read_json("configs", "tpch_sf30_memory_x4")
assert "tpch.sf30." in config["load"]
config["load"] = config["load"].replace("tpch.sf30.", "tpch.tiny.")
cell = {{w["name"]: w for w in bench_run.manifest()["workloads"]}}[
    "mesh4_sf30.q3"]
(line,) = bench_run.run_cell(cell, [(2**31 + 34, True, False)], seconds=1.0,
                             rehearse=True, out=io.StringIO(), config=config)
print("LINE " + json.dumps(bench_run._rehearsal(line)))
"""


def test_the_cell_rehearsed_on_four_virtual_devices_agrees():
    """A subprocess, so that the device count is set before JAX is
    imported: load WITH (workers = 4), warm-up, window and judge as the
    harness runs them, unedited."""
    done = subprocess.run(
        [sys.executable, "-c", REHEARSAL.format(root=ROOT)], cwd=ROOT,
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    (text,) = [ln[5:] for ln in done.stdout.splitlines()
               if ln.startswith("LINE ")]
    line = json.loads(text)
    assert line["answers_agree"] is True and line["statements"] >= 1
    numbers = line["numbers"]
    assert numbers["q3_gap"]["value"] == 0
    assert numbers["rows_loaded_gap"]["value"] == 0
    assert numbers["unanswered"]["value"] == 0
    # the counter-fed readers find their counters; the two that read
    # the device trace have none in a rehearsal
    assert {"mesh_chips", "exchange_mb", "exchange_fill_pct",
            "device_put_ms"} <= set(line["metric_names"])


def test_the_load_as_written_is_refused_by_a_server_of_another_scale(config):
    """The rehearsal's server serves 0.01: `tpch.sf30` stops the load at
    once. (The parent of PR 34 stops a step earlier, at the property.)"""
    import io
    from presto_tpu.client import QueryError
    with pytest.raises(QueryError, match="sf30"):
        bench_run.run_cell(CELL, [(1, False, False)], seconds=0.2,
                           rehearse=True, out=io.StringIO(), config=config)


def _statement(traced=True, **counters):
    return {"template": "q3", "set": 0, "wall_s": 1.0, "traced": traced,
            "stats": {"queryStats": {
                "counters": counters,
                "datapath": {"device_put": {"wall_us": 250_000}}}}}


def _run(statements, trace=None):
    return {"statements": statements, "trace": trace,
            "device_kind": "TPU v5 lite", "cache_misses_in_window": 0}


MESHED = dict(mesh_chips=4, exchange_bytes=3_000_000_000,
              exchange_slot_bytes=2_000_000_000,
              exchange_row_bytes=500_000_000)
TRACE = {"busy_s": 10.0, "window_s": 12.0, "idle_pct": 16.7,
         "device_ops": [["fusion.1", 4.0], ["all-to-all.3", 0.25],
                        ["all-gather.7", 0.05], ["all-reduce.2", 0.1],
                        ["collective-permute.1", 0.1], ["sort.4", 1.0]],
         "idle_gaps": []}


@pytest.mark.parametrize("name,want", [
    ("mesh_chips", 4.0), ("exchange_mb", 3000.0),
    ("exchange_fill_pct", 25.0), ("device_put_ms", 250.0),
    ("exchange_collective_ms", 250.0),
    # two statements' 1 GB at 200 GB/s is 5 ms of the 10 s busy
    ("exchange_ici_roofline", 0.05)])
def test_a_reader_finds_what_a_meshed_run_reports(name, want):
    run = _run([_statement(**MESHED), _statement(**MESHED)], TRACE)
    assert layers.read_metric(name, run) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_one_chip_run(name):
    """The parent's statements carry none of the counters, and an
    untraced run no trace: `None`, and no raise."""
    plain = {"template": "q3", "set": 0, "wall_s": 1.0, "traced": False,
             "stats": {"queryStats": {"counters": {"capacity_reruns": 0},
                                      "datapath": {}}}}
    assert layers.read_metric(name, _run([plain])) is None
    if name in ("exchange_collective_ms", "exchange_ici_roofline"):
        traced = dict(plain, traced=True)
        found = layers.read_metric(name, _run([traced], TRACE))
        # no routed rows reported: no roofline; the trace's collectives
        # are still the trace's
        assert found is None or name == "exchange_collective_ms"


def test_collectives_outside_the_ten_listed_read_zero():
    trace = dict(TRACE, device_ops=[["fusion.1", 4.0], ["sort.4", 1.0]])
    run = _run([_statement(**MESHED)], trace)
    assert layers.read_metric("exchange_collective_ms", run) == 0.0


def test_the_roofline_cannot_pass_100():
    """Rows really routed over the whole busy time: even a program that
    did nothing but move its rows at the published rate reads 100."""
    routed = 200e9 * 2.0  # two seconds' worth at 200 GB/s
    run = _run([_statement(**dict(MESHED, exchange_row_bytes=int(routed)))],
               dict(TRACE, busy_s=2.0))
    assert layers.read_metric("exchange_ici_roofline", run) == \
        pytest.approx(100.0)
    slower = _run([_statement(**dict(MESHED,
                                     exchange_row_bytes=int(routed)))],
                  dict(TRACE, busy_s=2.5))
    assert layers.read_metric("exchange_ici_roofline", slower) < 100.0
    with pytest.raises(KeyError, match="interconnect"):
        layers.read_metric("exchange_ici_roofline",
                           dict(run, device_kind="TPU v9"))
