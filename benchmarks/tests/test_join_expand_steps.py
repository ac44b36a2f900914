"""`join_expand_steps`, a metric that is data alone: its `.json` names
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
    # a Q14 (one join) and a Q3 (two) at SF10: the mean a statement
    (({"join_expand_steps": 6, "join_search_steps": 1},
      {"join_expand_steps": 4, "join_search_steps": 2}), 5.0),
    # a rerun after an overflow adds its trips to the statement's sum
    (({"join_expand_steps": 24},), 24.0),
    # probes no longer than their outputs: no trips is a reading too
    (({"join_expand_steps": 0}, {"join_expand_steps": 0}), 0.0),
    # the parent's shape: a join's other counter, not this one
    (({"join_search_steps": 1}, {"join_search_steps": 2}), None),
    # a mix with a join-free statement: the mean of those that carry it
    (({"join_expand_steps": 6}, {"plan_cache_hits": 1}), 6.0),
])
def test_join_expand_steps_reads_the_counter(counters, expected):
    got = layers.read_metric("join_expand_steps", _run(*counters))
    assert got == (pytest.approx(expected) if expected is not None else None)


def test_silent_on_a_failed_statement_and_listed_once():
    run = _run({"join_expand_steps": 8})
    run["statements"][0]["stats"] = {"state": "FAILED"}
    assert layers.read_metric("join_expand_steps", run) is None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        found = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "join_expand_steps"]
    assert found == [{
        "name": "join_expand_steps", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "kernels", "moves": "stmt_ms",
        "workloads": ["mem_sf1.join", "mem_sf10.join"]}]  # Q6 has no join
