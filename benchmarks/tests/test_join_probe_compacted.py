"""`join_probe_compacted`, a metric that is data alone: its `.json`
names the counter in the protocol's `stats` document."""

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
    # a Q14 (its probe fits) and a Q3 (neither of its two does)
    (({"join_probe_compacted": 1, "join_expand_steps": 6},
      {"join_probe_compacted": 0, "join_expand_steps": 4}), 0.5),
    # a rerun after an overflow adds its dispatch's joins
    (({"join_probe_compacted": 2},), 2.0),
    # shapes that rule the form out: 0 is a reading too
    (({"join_probe_compacted": 0}, {"join_probe_compacted": 0}), 0.0),
    # the parent's shape: a join's other counters, not this one
    (({"join_expand_steps": 6, "join_search_steps": 1},
      {"join_expand_steps": 4, "join_search_steps": 2}), None),
    # a mix with a join-free statement: the mean of those that carry it
    (({"join_probe_compacted": 1}, {"plan_cache_hits": 1}), 1.0),
])
def test_join_probe_compacted_reads_the_counter(counters, expected):
    got = layers.read_metric("join_probe_compacted", _run(*counters))
    assert got == (pytest.approx(expected) if expected is not None else None)


def test_silent_on_a_failed_statement_and_listed_once():
    run = _run({"join_probe_compacted": 1})
    run["statements"][0]["stats"] = {"state": "FAILED"}
    assert layers.read_metric("join_probe_compacted", run) is None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert per_layer[-1] == {
        "name": "join_probe_compacted", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "kernels", "moves": "stmt_ms",
        "workloads": ["mem_sf1.join", "mem_sf10.join"]}  # Q6 has no join
    assert [m["name"] for m in per_layer].count("join_probe_compacted") == 1
