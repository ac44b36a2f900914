"""`capacity_fill_pct`: what a statement's counted nodes needed over the
capacities they ran at, from two counters of the protocol's `stats`
document; silent where the program reports none."""

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
    # Q3 at SF1 under the one scale: three nodes at 262,144
    (({"capacity_rows": 786_432, "capacity_live_rows": 191_261},),
     100 * 191_261 / 786_432),
    # fitted, and a Q14 beside it: the mean of the statements' shares
    (({"capacity_rows": 311_296, "capacity_live_rows": 191_261},
      {"capacity_rows": 131_072, "capacity_live_rows": 75_983}),
     50 * (191_261 / 311_296 + 75_983 / 131_072)),
    # an empty join is a reading: nothing live in a capacity
    (({"capacity_rows": 1024, "capacity_live_rows": 0},), 0.0),
    # the parent's program, a Q6, a failed statement: no counters
    (({"capacity_reruns": 0, "join_expand_steps": 4},
      {"plan_cache_hits": 1}), None),
    # a Q6 between two Q3s does not dilute them
    (({"capacity_rows": 200, "capacity_live_rows": 100},
      {"capacity_reruns": 0},
      {"capacity_rows": 400, "capacity_live_rows": 300}), 62.5),
    # one counter without the other reads nothing
    (({"capacity_live_rows": 5}, {"capacity_rows": 0,
                                  "capacity_live_rows": 0}), None),
], ids=["one-scale", "fitted-mix", "empty", "absent", "q6-between",
        "half-a-pair"])
def test_capacity_fill_pct_is_live_over_capacity(counters, expected):
    got = layers.read_metric("capacity_fill_pct", _run(*counters))
    assert got == (pytest.approx(expected) if expected is not None else None)


def test_silent_on_a_failed_statement():
    run = _run({"capacity_rows": 8, "capacity_live_rows": 4})
    run["statements"][0]["stats"] = {"state": "FAILED"}
    assert layers.read_metric("capacity_fill_pct", run) is None


def test_listed_once_for_the_three_join_cells_only():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "capacity_fill_pct"]
    assert entry == {
        "name": "capacity_fill_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "region dispatch + operators",
        "moves": "stmt_ms",
        "workloads": ["mem_sf1.join", "mem_sf10.join", "mesh4_sf30.q3"]}
    # the cells whose statements hold a join: a Q6 has no counted node
    joins = {w["name"] for w in bench["workloads"]
             if w["traffic"] != "q6_stream"}
    assert set(entry["workloads"]) == joins
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] == "capacity_reruns"}
