"""`join_lookup_direct`, a metric that is data alone: its `.json` names
the counter in the protocol's `stats` document."""

import json
import os

import pytest

from benchmarks.harness import layers


def _run(*counters):
    return {"statements": [
        {"template": "q", "wall_s": 1.0, "traced": True,
         "stats": {"state": "FINISHED", "queryStats": {
             "stages": {"execute": {"wall_us": 900_000, "invocations": 1}},
             "counters": c}}} for c in counters],
        "trace": None, "device_kind": "TPU v5 lite",
        "cache_misses_in_window": 0}


@pytest.mark.parametrize("counters,expected", [
    # a Q14 (part's directory answers) and a Q3 (customer's, orders')
    (({"join_lookup_direct": 1, "join_search_steps": 0},
      {"join_lookup_direct": 2, "join_search_steps": 0}), 1.5),
    # a build whose span the directory does not cover: 0 is a reading
    (({"join_lookup_direct": 0, "join_search_steps": 3},), 0.0),
    # the parent's shape: a join's other counters, not this one
    (({"join_search_steps": 1, "join_probe_compacted": 1},
      {"join_search_steps": 2, "join_probe_compacted": 0}), None),
    # a mix with a join-free statement: the mean of those that carry it
    (({"join_lookup_direct": 2}, {"plan_cache_hits": 1}), 2.0),
])
def test_join_lookup_direct_reads_the_counter(counters, expected):
    got = layers.read_metric("join_lookup_direct", _run(*counters))
    assert got == (pytest.approx(expected) if expected is not None else None)


def test_listed_once_for_the_three_join_cells():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    found = [m for m in per_layer if m["name"] == "join_lookup_direct"]
    assert found == [{
        "name": "join_lookup_direct", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "kernels", "moves": "stmt_ms",
        "workloads": ["mem_sf1.join", "mem_sf10.join", "mesh4_sf30.q3"]}]
