"""`join_search_steps`, a metric that is data alone: its `.json` names
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
    # a Q14 (one join) and a Q3 (two): the mean a statement
    (({"join_search_steps": 1, "xla_compiles": 1},
      {"join_search_steps": 2, "xla_compiles": 1}), 1.5),
    # a rerun after an overflow adds its trips to the statement's sum
    (({"join_search_steps": 42},), 42.0),
    # the parent's shape: counters, none of them this one
    (({"xla_compiles": 1, "plan_cache_hits": 2}, {}), None),
    # a mix with a join-free statement: the mean of those that carry it
    (({"join_search_steps": 3}, {"plan_cache_hits": 1}), 3.0),
])
def test_join_search_steps_reads_the_counter(counters, expected):
    got = layers.read_metric("join_search_steps", _run(*counters))
    assert got == (pytest.approx(expected) if expected is not None else None)


def test_silent_on_a_failed_statement_and_listed_once():
    run = _run({"join_search_steps": 2})
    run["statements"][0]["stats"] = {"state": "FAILED"}
    assert layers.read_metric("join_search_steps", run) is None
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        found = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "join_search_steps"]
    assert len(found) == 1
    assert found[0]["workloads"] == ["mem_sf1.join"]  # Q6 has no join
    assert found[0]["moves"] == "stmt_ms"
    assert found[0]["source"] == "program_counter"
