"""The readers of the program's own spans and counters, on canned runs."""

import pytest

from benchmarks.harness import layers

SPAN_METRICS = ("queue_ms", "plan_ms", "dispatch_ms", "device_wait_ms",
                "unattributed_ms", "plan_cache_hit_pct", "xla_compiles",
                "compile_cache_reads")


def _stage(wall_us, invocations=1):
    return {"wall_us": wall_us, "invocations": invocations}


def _stats(stages, counters):
    return {"state": "FINISHED", "queryStats": {
        "stages": {k: _stage(*v) if isinstance(v, tuple) else _stage(v)
                   for k, v in stages.items()},
        "counters": counters}}


def _run():
    """Two statements: a select whose `compile` overlaps its `execute`
    (a first run) and a warm one that went through the batch window."""
    first = _stats({"queue": 1_000, "plan": (40_000, 4),
                    "plan.sql": 15_000, "plan.prepare": 20_000,
                    "dynfilter": 2_000, "staging": 100_000,
                    "execute": 500_000, "compile": 450_000,
                    "dispatch": 460_000, "device_wait": 39_000,
                    "fetch": 3_000, "render": 1_000},
                   {"plan_cache_misses": 1, "xla_compiles": 3,
                    "compile_cache_reads": 1})
    warm = _stats({"queue": 3_000, "batch": 6_000, "batch.wait": 5_000,
                   "plan": (20_000, 4), "plan.sql": 8_000,
                   "plan.prepare": 9_000, "dynfilter": 2_000,
                   "staging": 100_000, "execute": 60_000,
                   "dispatch": 1_000, "device_wait": 58_000,
                   "fetch": 3_000, "render": 1_000},
                  {"plan_cache_hits": 3})
    return {"statements": [
        {"template": "q", "wall_s": 0.650, "stats": first, "traced": True},
        {"template": "q", "wall_s": 0.200, "stats": warm, "traced": True}],
        "trace": None, "device_kind": "TPU v5 lite",
        "cache_misses_in_window": 0}


@pytest.mark.parametrize("name,expected", [
    ("queue_ms", 2.0),            # (1 + 3) / 2
    ("plan_ms", 30.0),            # (40 + 20) / 2, children not added
    ("dispatch_ms", 230.5),       # (460 + 1) / 2
    ("device_wait_ms", 48.5),     # (39 + 58) / 2
    # 650 - (1+40+2+100+500+3+1) = 3; 200 - (3+6+20+2+100+60+3+1) = 5
    ("unattributed_ms", 4.0),
    ("plan_cache_hit_pct", 75.0),  # 3 hits of 4 lookups
    ("xla_compiles", 1.5),        # 3 and none: the warm one counts as 0
    ("compile_cache_reads", 0.5),
])
def test_reader_on_a_canned_run(name, expected):
    assert layers.read_metric(name, _run()) == pytest.approx(expected)


def test_unattributed_never_negative_when_compile_overlaps_execute():
    """`compile` is carved out of `execute`: a reader that subtracted
    both would go below zero on the first statement (650 ms of wall,
    647 covered, 450 of compile inside execute)."""
    run = _run()
    run["statements"] = run["statements"][:1]
    assert layers.read_metric("unattributed_ms", run) == pytest.approx(3.0)
    run["statements"][0]["stats"]["queryStats"]["stages"]["compile"] = \
        _stage(499_000)
    assert layers.read_metric("unattributed_ms", run) >= 0


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_reader_is_silent_on_the_parents_stats(name):
    """The parent of the PR that brought the spans reports staging,
    execute, fetch and no counters of the plan cache: nothing to read,
    no metric, no error."""
    run = _run()
    for s in run["statements"]:
        s["stats"] = _stats({"staging": 100_000, "execute": 60_000,
                             "compile": 10_000, "fetch": 3_000},
                            {"narrowed_columns": 2})
    assert layers.read_metric(name, run) is None
    run["statements"][0]["stats"] = {"state": "FAILED"}
    assert layers.read_metric(name, run) is None


def test_the_manifest_names_each_reader_once():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in SPAN_METRICS:
        assert per_layer[name]["moves"] == "stmt_ms"
        assert "workloads" not in per_layer[name]  # every cell
