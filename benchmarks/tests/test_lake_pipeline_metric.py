"""`row_groups_pipelined_pct` (PR 33): its reader and its entry. CPU, no
chip."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import layers  # noqa: E402

NAME = "row_groups_pipelined_pct"


def _run(*counters):
    return {"statements": [{"stats": {"queryStats": {"counters": c}}}
                           for c in counters]}


def test_the_reader_is_silent_without_the_counters():
    # a cell that reads no file; the parent's program over a file
    assert layers.read_metric(NAME, _run({"plan_cache_hits": 1})) is None
    assert layers.read_metric(NAME, _run(
        {"lake_row_groups_read": 60, "lake_row_groups_total": 60})) is None
    assert layers.read_metric(NAME, {"statements": []}) is None
    assert layers.read_metric(NAME, {"statements": [{"stats": {}}]}) is None


def test_a_full_pipeline_reads_100_and_a_mixed_window_its_share():
    q6 = {"lake_row_groups_read": 60, "lake_row_groups_pipelined": 60}
    assert layers.read_metric(NAME, _run(q6, q6, q6)) == 100.0
    # a statement whose scan assembled on the host beside two that did not
    host = {"lake_row_groups_read": 60, "lake_row_groups_pipelined": 0}
    assert layers.read_metric(NAME, _run(q6, host, q6)) == \
        100.0 * 120 / 180
    # a join: one of its two scans pipelined
    join = {"lake_row_groups_read": 75, "lake_row_groups_pipelined": 15}
    assert layers.read_metric(NAME, _run(join)) == 20.0
    # a statement that reads no file weighs nothing
    assert layers.read_metric(NAME, _run(q6, {"plan_cache_hits": 1})) == 100.0


def test_the_metric_is_listed_once_for_the_lake_cell_only():
    entries = [m for m in bench_run.manifest()["per_layer"]
               if m["name"] == NAME]
    assert entries == [{
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "split staging",
        "moves": "stmt_ms", "workloads": ["lake_sf10.scan"]}]
    assert os.path.exists(os.path.join(
        ROOT, "benchmarks", "layer_metrics", NAME + ".py"))
