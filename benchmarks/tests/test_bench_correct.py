"""`correct` has to come out false: for the control, and for a timed
path broken underneath. CPU, sf 0.01, the harness's look for a chip
skipped (`rehearse=True`); everything else is the run the chip makes."""

import copy
import io
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.harness import traffic  # noqa: E402

CELLS = {w["name"]: w for w in bench_run.manifest()["workloads"]}
# the Q6 stream on the memory tables waits under PERF.md's Open questions:
# it is the cheapest cell that has a load, so the planted faults use it
CELLS.setdefault("mem_sf1.scan", {
    "name": "mem_sf1.scan", "config": "tpch_sf1_memory",
    "traffic": "q6_stream", "chips": 1})


def _run(cell, windows, **kw):
    return bench_run.run_cell(CELLS[cell], windows, seconds=0.5,
                              rehearse=True, out=io.StringIO(), **kw)


def _over(line):
    return sorted(k for k, n in line["numbers"].items()
                  if n["value"] > n["limit"])


@pytest.mark.parametrize("cell,failing", [
    ("mem_sf1.scan", ["q6_gap"]),
    ("mem_sf1.join", ["q14_gap", "q3_gap"]),
    ("gen_sf1.scan", ["q6_gap"])])
def test_program_is_correct_and_the_float32_control_is_not(cell, failing):
    sound, control = _run(cell, [(2**31 + 5, False, False), (7, False, True)])
    assert sound["correct"] and _over(sound) == []
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert not control["correct"] and _over(control) == failing


def test_an_answer_altered_where_it_is_produced():
    from presto_tpu.client import execute

    def altered(url, text, **kw):
        done = execute(url, text, **kw)
        if "sum(" in text and done.data:  # one unit in the last place
            cell = done.data[0][0]
            done.data[0][0] = cell[:-1] + str((int(cell[-1]) + 1) % 10)
        return done
    (line,) = _run("mem_sf1.scan", [(3, False, False)], execute=altered)
    assert not line["correct"] and _over(line) == ["q6_gap"]


def test_half_of_the_rows_left_out_of_the_load():
    config = copy.deepcopy(traffic.read_json("configs", "tpch_sf1_memory"))
    config["load"] += " WHERE orderkey % 2 = 0"
    (line,) = _run("mem_sf1.scan", [(4, False, False)], config=config)
    assert not line["correct"]
    assert _over(line) == ["q6_gap", "rows_loaded_gap"]


def test_a_statement_that_never_answers():
    from presto_tpu.client import QueryError, execute
    seen = []

    def failing(url, text, **kw):
        seen.append(text)
        if "sum(" in text and len(seen) % 7 == 0:
            raise QueryError({"message": "planted"})
        return execute(url, text, **kw)
    (line,) = _run("mem_sf1.scan", [(5, False, False)], execute=failing)
    assert not line["correct"] and "unanswered" in _over(line)
    assert line["failed"] >= 1 or line["numbers"]["unanswered"]["value"] >= 1


def test_a_rehearsal_never_prints_the_contract_line():
    out = io.StringIO()
    bench_run.run_cell(CELLS["mem_sf1.scan"], [(6, False, False)],
                       seconds=0.2, rehearse=True, out=out)
    printed = json.loads(out.getvalue().strip().splitlines()[-1])
    assert printed["rehearsal"] is True
    assert not {"correct", "metrics", "device"} & set(printed)


@pytest.mark.parametrize("table", ["lineitem", "orders", "customer", "part"])
def test_the_memory_tables_hold_every_column_of_the_source(table):
    """A record's width is the source's: the load copies whole tables."""
    from presto_tpu.connectors.tpch.generator import TPCH_SCHEMA
    config = traffic.read_json("configs", "tpch_sf1_memory")
    assert config["columns"][table] == [c for c, _ in TPCH_SCHEMA[table]]
