"""The seven metrics that read the seam's new spans and counters
(PR 36): each entry found by its name, each reader on canned stats, and
the two cells that differ most rehearsed: which names a traced line of
`lake_sf10.scan` and of `mem_sf1.join` would carry. CPU, no chip."""

import copy
import io
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import layers, traffic  # noqa: E402

BENCH = bench_run.manifest()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
# name: (source, layer, the cells it lists or None for every cell, path)
SEAM = {
    "scan_count_ms": ("program_span", "split staging", None,
                      "stages.scan_count.wall_us"),
    "finish_ms": ("program_span", "client protocol + parse/plan", None,
                  "stages.finish.wall_us"),
    "prune_ms": ("program_span", "split staging", ["mem_sf1.join"],
                 "stages.prune.wall_us"),
    "dynfilter_ms": ("program_span", "client protocol + parse/plan",
                     ["mem_sf1.join", "mem_sf10.join"],
                     "stages.dynfilter.wall_us"),
    "lake_read_thread_ms": ("program_counter", "split staging",
                            ["lake_sf10.scan"],
                            "counters.lake_read_thread_us"),
    "lake_decode_thread_ms": ("program_counter", "split staging",
                              ["lake_sf10.scan"],
                              "counters.lake_decode_thread_us"),
    "lake_consumer_wait_ms": ("program_counter", "split staging",
                              ["lake_sf10.scan"],
                              "counters.lake_consumer_wait_us"),
}
LAKE_ONLY = {n for n, spec in SEAM.items() if spec[2] == ["lake_sf10.scan"]}


@pytest.mark.parametrize("name", sorted(SEAM))
def test_each_entry_is_found_by_its_name(name):
    source, layer, cells, _path = SEAM[name]
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    want = {"name": name, "unit": "ms", "better": "lower", "source": source,
            "layer": layer, "moves": "stmt_ms"}
    if cells is not None:
        want["workloads"] = cells
    assert entry == want
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", name + ".json"))
    # a layer the manifest already names, and cells it already has
    assert layer in {m["layer"] for m in BENCH["per_layer"]
                     if m["name"] not in SEAM}
    assert set(cells or ()) <= set(CELLS)


def _stats(path, value):
    doc = at = {}
    keys = ["queryStats"] + path.split(".")
    for key in keys[:-1]:
        at[key] = {}
        at = at[key]
    at[keys[-1]] = value
    return {"stats": doc}


@pytest.mark.parametrize("name", sorted(SEAM))
def test_each_reader_means_the_statements_that_carry_it(name):
    path = SEAM[name][3]
    run = {"statements": [_stats(path, 3000), _stats(path, 1000),
                          {"stats": {"queryStats": {"stages": {},
                                                    "counters": {}}}}]}
    assert layers.read_metric(name, run) == pytest.approx(2.0)
    # the parent's program: no such span or counter, so no such metric
    silent = {"statements": [
        {"stats": {"queryStats": {
            "stages": {"staging": {"wall_us": 5}},
            "counters": {"lake_file_bytes": 7}}}}, {"stats": {}}]}
    assert layers.read_metric(name, silent) is None
    assert layers.read_metric(name, {"statements": []}) is None


def _rehearsed_names(cell, config=None):
    (line,) = bench_run.run_cell(
        CELLS[cell], [(2**31 + 36, True, False)], seconds=0.5,
        rehearse=True, out=io.StringIO(), config=config)
    assert line["correct"] is True and line["failed"] == 0
    return set(line["metrics"]), line["metrics"]


def test_a_rehearsed_lake_scan_lists_its_names_and_not_the_joins(
        tmp_path, monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_WAREHOUSE", str(tmp_path))
    config = copy.deepcopy(traffic.read_json("configs", "tpch_sf10_parquet"))
    config["load"] = config["load"].replace("tpch.sf10.", "tpch.tiny.")
    try:
        names, metrics = _rehearsed_names("lake_sf10.scan", config)
    finally:
        from presto_tpu.connectors import parquet
        parquet.drop_table("lineitem", if_exists=True)
    assert LAKE_ONLY | {"scan_count_ms", "finish_ms"} <= names
    assert not {"prune_ms", "dynfilter_ms"} & names
    # thread time is summed over pieces; the wait lies inside staging
    assert metrics["lake_consumer_wait_ms"]["value"] <= \
        metrics["staging_ms"]["value"]
    assert metrics["lake_read_thread_ms"]["value"] > 0
    assert metrics["lake_decode_thread_ms"]["value"] > 0
    assert metrics["scan_count_ms"]["value"] <= \
        metrics["staging_ms"]["value"]


def test_a_rehearsed_sf1_join_lists_its_names_and_not_the_lakes():
    names, metrics = _rehearsed_names("mem_sf1.join")
    assert {"scan_count_ms", "finish_ms", "prune_ms",
            "dynfilter_ms"} <= names
    assert not LAKE_ONLY & names
    assert metrics["prune_ms"]["value"] + metrics["scan_count_ms"]["value"] \
        <= metrics["staging_ms"]["value"]
    # `finish` is a top-level span the older reader does not subtract
    assert metrics["finish_ms"]["value"] <= \
        metrics["unattributed_ms"]["value"]
