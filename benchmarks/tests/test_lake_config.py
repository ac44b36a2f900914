"""`tpch_sf10_parquet` and its cell `lake_sf10.scan`, rehearsed: the
configuration's file as it is but for the schema its load names (the
rehearsal's server serves scale 0.01, which upstream calls `tiny`).
CPU, no chip."""

import copy
import io
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import traffic  # noqa: E402

BENCH = bench_run.manifest()
CELL = {w["name"]: w for w in BENCH["workloads"]}["lake_sf10.scan"]
METRICS = {"decode_ms", "lake_file_mb", "decode_mb_s", "row_groups_read_pct"}


@pytest.fixture()
def config(tmp_path, monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_WAREHOUSE", str(tmp_path))
    config = copy.deepcopy(traffic.read_json("configs", "tpch_sf10_parquet"))
    assert "tpch.sf10." in config["load"]
    config["load"] = config["load"].replace("tpch.sf10.", "tpch.tiny.")
    yield config
    from presto_tpu.connectors import parquet
    parquet.drop_table("lineitem", if_exists=True)


def test_the_file_has_every_key_the_readme_lists():
    config = traffic.read_json("configs", "tpch_sf10_parquet")
    for key in ("source", "sf", "catalog", "columns", "load", "guarantees",
                "chips", "reduced", "reduced_why", "assumed"):
        assert config[key], key
    entry = {c["name"]: c for c in BENCH["configs"]}["tpch_sf10_parquet"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["file"] == "benchmarks/configs/tpch_sf10_parquet.json"
    assert entry["reduced"] == config["reduced"] == ["lineitem_rows"]
    assert config["sf"] == 10.0 and config["lineitem_rows"] == 60_000_000
    assert config["catalog"] == "hive."
    assert "WITH (format = 'PARQUET')" in config["load"]
    # the same whole records as the memory deployment of the scale
    assert config["columns"] == \
        traffic.read_json("configs", "tpch_sf10_memory")["columns"]
    for key in ("row_group_rows", "codec", "decimal_physical_type", "store",
                "layout"):
        assert config["assumed"][key], key
    assert CELL == {"name": "lake_sf10.scan", "config": "tpch_sf10_parquet",
                    "traffic": "q6_stream", "chips": 1, "why": CELL["why"]}


def test_the_assumed_writer_defaults_are_the_programs():
    from presto_tpu.connectors import parquet
    assumed = traffic.read_json("configs", "tpch_sf10_parquet")["assumed"]
    assert f"{parquet.ROW_GROUP_ROWS:,} rows" in assumed["row_group_rows"]
    assert assumed["codec"].startswith(parquet.CODEC.upper())


def test_the_four_metrics_are_the_new_cells_alone():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in METRICS:
        assert by_name[name]["workloads"] == ["lake_sf10.scan"]
        assert by_name[name]["moves"] == "stmt_ms"
    for m in BENCH["per_layer"]:
        if m["name"] not in METRICS:
            assert "lake_sf10.scan" not in m.get("workloads", [])


def test_the_cell_rehearsed_agrees_and_the_float32_control_does_not(config):
    sound, control = bench_run.run_cell(
        CELL, [(2**31 + 17, True, False), (5, False, True)],
        seconds=0.5, rehearse=True, out=io.StringIO(), config=config)
    printed = bench_run._rehearsal(sound)
    assert printed["answers_agree"] is True and sound["failed"] == 0
    assert METRICS | {"connector_read_ms", "staging_ms", "staged_mb"} <= \
        set(printed["metric_names"])
    assert "scan_hbm_roofline" not in printed["metric_names"]
    numbers = sound["numbers"]
    assert numbers["q6_gap"] == {"value": 0, "limit": 0}
    assert numbers["rows_loaded_gap"] == {"value": 0, "limit": 0}
    assert numbers["unanswered"]["value"] == 0
    metrics = sound["metrics"]
    assert metrics["row_groups_read_pct"]["value"] == 100.0
    assert metrics["lake_file_mb"]["value"] > 0
    assert metrics["decode_ms"]["value"] > 0
    # 60,000 rows: three int64 lanes, one int32, four null masks
    assert metrics["decode_mb_s"]["value"] * metrics["decode_ms"]["value"] \
        == pytest.approx(60000 * 32 / 1e3)
    assert metrics["staged_mb"]["value"] == 60000 * 14 / 1e6
    assert not control["correct"]
    assert [k for k, n in control["numbers"].items()
            if n["value"] > n["limit"]] == ["q6_gap"]


def test_the_new_readers_find_nothing_in_a_cell_that_reads_no_file():
    """On another cell's statements (and on the parent's program, which
    has neither the hop's bytes nor the counters) they return None."""
    from benchmarks.harness import layers
    run = {"statements": [{"stats": {"queryStats": {
        "counters": {"plan_cache_hits": 1},
        "datapath": {"connector_read": {"wall_us": 5, "bytes": 7}}}}}]}
    for name in METRICS:
        assert layers.read_metric(name, run) is None


def test_a_server_without_the_hive_catalog_fails_the_first_load_at_once(
        config, monkeypatch):
    """The parent commit: `hive` is no catalog, the DROP TABLE IF EXISTS
    before the first load is refused and nothing is generated."""
    from presto_tpu import connectors
    from presto_tpu.client import QueryError
    monkeypatch.delitem(connectors.catalogs(), "hive")
    with pytest.raises(QueryError, match="catalog 'hive' is read-only"):
        bench_run.run_cell(CELL, [(1, False, False)], seconds=0.2,
                           rehearse=True, out=io.StringIO(), config=config)


def test_half_of_the_rows_left_out_of_the_load(config):
    config["load"] += " WHERE orderkey % 2 = 0"
    (line,) = bench_run.run_cell(CELL, [(4, False, False)], seconds=0.5,
                                 rehearse=True, out=io.StringIO(),
                                 config=config)
    assert not line["correct"]
    assert sorted(k for k, n in line["numbers"].items()
                  if n["value"] > n["limit"]) == ["q6_gap", "rows_loaded_gap"]
