"""A capacity per plan node, from the device's own counts.

A program reports what each join and each keyed aggregation needed
(`CompiledPlan.counted`, `split_status`); the ladder sizes an
overflowed node from its count, the nodes above it four times, and
keeps a plan that fitted at the power of two at or above each node's
need (`plan/stats.py`, `exec/runner._dispatch_ladder`). The overflow
flags and the exact rerun are as they were. CPU, sf 0.01; the suite's
virtual devices stand for the chips.
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import judge, traffic  # noqa: E402
from presto_tpu import types as T  # noqa: E402
from presto_tpu.connectors import memory  # noqa: E402
from presto_tpu.connectors.tpch import generator as g  # noqa: E402
from presto_tpu.exec import runner  # noqa: E402
from presto_tpu.exec.plan_cache import plan_fingerprint  # noqa: E402
from presto_tpu.plan import nodes as N  # noqa: E402
from presto_tpu.plan import stats as S  # noqa: E402
from presto_tpu.sql import plan_sql, sql  # noqa: E402

SF = 0.01
Q3 = {"SEGMENT": "BUILDING", "DATE": "1995-03-15"}
Q6 = {"DATE_LO": "1994-01-01", "DATE_HI": "1995-01-01",
      "DISCOUNT_LO": "0.05", "DISCOUNT_HI": "0.07", "QUANTITY": "24"}
TIGHT = {"join_capacity": 64, "max_groups": 128}


def pow2_at_or_above(n):
    return 1 << max(n - 1, 0).bit_length()


@pytest.fixture
def dispatches(monkeypatch):
    """Every dispatch of the test, as the ladder read it: (the
    capacities the program was built with, by pre-order index; the
    needs it reported; its flags)."""
    seen = []
    real = runner._read_status

    def spy(status, plan, expand_steps):
        flags, routed, needs = real(status, plan, expand_steps)
        seen.append((dict(plan.counted), dict(needs), flags))
        return flags, routed, needs

    monkeypatch.setattr(runner, "_read_status", spy)
    return seen


def counters(res):
    return res.query_stats.counters


# -- the statement the cells send ------------------------------------------

def test_q3s_next_dispatch_runs_at_what_each_node_needed(dispatches,
                                                         tmp_path):
    """Q3 with capacities far too small climbs, fits and is built once
    more at what its nodes needed; the next statement of the
    fingerprint runs that plan and nothing else: every join and the
    aggregation at the power of two at or above its count, the rows the
    first run's, the roomy plan's and numpy's."""
    text = traffic.statement_text("q3", "tpch.", Q3)
    first = sql(text, sf=SF, **TIGHT)
    ran = len(dispatches)
    assert counters(first)["capacity_reruns"] >= 1
    assert counters(first)["capacity_refits"] == 1
    assert ran == first.query_stats.stages["dispatch"].invocations
    fitting = [d for d in dispatches if d[2] == 0]
    needs = fitting[0][1]  # the first dispatch that fitted: exact counts
    assert sorted(needs) == [4, 6, 7]  # the groups, x customer, x orders
    want = {k: pow2_at_or_above(n) for k, n in needs.items()}
    assert fitting[-1][0] == want and fitting[-1][1] == needs
    again = sql(text, sf=SF, **TIGHT)
    assert len(dispatches) == ran + 1
    assert dispatches[-1] == (want, needs, 0)
    assert counters(again)["capacity_reruns"] == 0
    assert "capacity_refits" not in counters(again)
    assert "plan_cache_misses" not in counters(again)
    assert counters(again)["capacity_rows"] == sum(want.values())
    assert counters(again)["capacity_live_rows"] == sum(needs.values())
    roomy = sql(text, sf=SF)
    assert first.rows() == again.rows() == roomy.rows()
    reference = judge.Reference(SF, str(tmp_path))
    module = reference.module("q3")
    epoch = np.datetime64("1970-01-01")
    got = [(int(k), int(rev), str(epoch + int(day)), int(prio))
           for k, rev, day, prio in again.rows()]
    assert module.gap(got, reference.answer("q3", Q3)) == 0


def test_a_plan_that_fits_as_planned_is_left_as_planned(dispatches):
    """No overflow, no second program: the plan's own capacities run,
    however roomy, and nothing is remembered for the fingerprint."""
    text = traffic.statement_text("q3", "tpch.", Q3)
    root = runner.prepare_plan(plan_sql(text), sf=SF)
    done = sql(text, sf=SF)
    assert len(dispatches) == 1 and dispatches[0][2] == 0
    assert set(dispatches[0][0].values()) == {1 << 16}
    assert "capacity_refits" not in counters(done)
    assert plan_fingerprint(root) not in runner._CAPACITY_FEEDBACK
    assert counters(done)["capacity_rows"] == 3 << 16
    assert 0 < counters(done)["capacity_live_rows"] < 1 << 12


def test_q6_has_no_counted_node_and_no_counter(dispatches):
    text = traffic.statement_text("q6", "tpch.", Q6)
    done = sql(text, sf=SF)
    assert dispatches == [({}, {}, 0)]
    assert "capacity_rows" not in counters(done)
    assert "capacity_live_rows" not in counters(done)
    assert counters(done)["capacity_reruns"] == 0
    # the program's status is the word alone, as it always was
    from presto_tpu.exec.planner import compile_plan
    plan = compile_plan(runner.prepare_plan(plan_sql(text), sf=SF))
    assert plan.counted == {} and not plan.distributed
    word, routed, needs = plan.split_status(np.int32(5 + (3 << 8)))
    assert (int(word), routed, needs) == (5 + (3 << 8), 0, {})


# -- a case per node kind ---------------------------------------------------

JOIN = ("SELECT count(*), sum(o.totalprice) FROM orders o "
        "JOIN customer c ON o.custkey = c.custkey WHERE c.nationkey < 20")
SINGLE = ("SELECT custkey, count(*) AS n, sum(totalprice) AS s FROM orders "
          "GROUP BY custkey")


@pytest.mark.parametrize("text,kind,hints", [
    (JOIN, N.JoinNode, {"join_capacity": 256}),
    (SINGLE, N.AggregationNode, {"max_groups": 128}),
], ids=["join", "single"])
def test_an_overflowed_node_is_sized_from_its_count(dispatches, text, kind,
                                                    hints):
    """One rerun, not a climb by fours: the dispatch that overflowed
    counted what the node needs, exactly, and the rerun has the power
    of two at or above it."""
    roomy = sql(text, sf=SF)
    del dispatches[:]
    tight = sql(text, sf=SF, **hints)
    assert sorted(tight.rows()) == sorted(roomy.rows())
    assert [d[2] for d in dispatches] == [1, 0]
    (built, needs, _), (rebuilt, fitted_needs, _) = dispatches
    (k,) = built
    root = runner.prepare_plan(plan_sql(text, **hints), sf=SF)
    assert isinstance(S.preorder(root)[k], kind)
    assert built[k] == next(iter(hints.values())) < needs[k]
    assert rebuilt[k] == pow2_at_or_above(needs[k])
    assert fitted_needs == needs
    assert counters(tight)["capacity_reruns"] == 1
    assert "capacity_refits" not in counters(tight)
    assert runner._CAPACITY_FEEDBACK[plan_fingerprint(root)] == rebuilt


@pytest.fixture
def spread_orders():
    name = "cap4_orders"
    sql(f"DROP TABLE IF EXISTS memory.{name}", sf=SF)
    sql(f"CREATE TABLE memory.{name} WITH (workers = 4) AS SELECT "
        "orderkey, custkey, totalprice FROM tpch.tiny.orders", sf=SF)
    yield name
    memory.drop_table(name, if_exists=True)


def test_partial_and_final_are_sized_from_the_largest_shard(dispatches,
                                                            spread_orders):
    """Under the four-device mesh a group-by is a PARTIAL and a FINAL
    step with an exchange between: each reports the largest shard's
    groups (a capacity is a shard's shape), the fitted plan has the
    power of two at or above each, and the rows are one chip's."""
    text = SINGLE.replace("orders", f"memory.{spread_orders}")
    meshed = sql(text, sf=SF, max_groups=64)
    assert counters(meshed)["mesh_chips"] == 4
    root = runner.prepare_plan(plan_sql(text, max_groups=64), sf=SF,
                               mesh=runner.placement_mesh(
                                   runner.prepare_plan(plan_sql(text), sf=SF)))
    steps = {k: n.step for k, n in enumerate(S.preorder(root))
             if S.is_counted(n)}
    assert sorted(steps.values()) == ["FINAL", "PARTIAL"]
    built, needs, flags = dispatches[-1]
    assert flags == 0 and sorted(built) == sorted(steps)
    partial = next(k for k, step in steps.items() if step == "PARTIAL")
    final = next(k for k, step in steps.items() if step == "FINAL")
    # 1,000 customers order (every third has none): a quarter of the
    # orders holds most of them, a quarter of the hash space a quarter
    custkeys = g.table_row_count("customer", SF)
    assert custkeys // 8 < needs[final] < custkeys // 2 < needs[partial] \
        <= custkeys
    assert built == {k: pow2_at_or_above(n) for k, n in needs.items()}
    assert counters(meshed)["capacity_reruns"] >= 1
    single = sql(SINGLE, sf=SF)
    assert sorted(meshed.rows()) == sorted(single.rows())
    again = sql(text, sf=SF, max_groups=64)
    assert dispatches[-1] == (built, needs, 0)
    assert counters(again)["capacity_reruns"] == 0
    assert sorted(again.rows()) == sorted(single.rows())


# -- the flags stay what they were -------------------------------------------

def test_a_table_that_grew_overflows_the_fitted_node_and_answers(dispatches):
    """The fitted capacity is what the data needed when it ran. The
    table re-created larger under the same statement overflows it: the
    flag reruns the statement, the node is sized from its new count,
    and the rows are the roomy plan's."""
    def load(limit):
        sql("DROP TABLE IF EXISTS memory.grow_orders", sf=SF)
        sql("CREATE TABLE memory.grow_orders AS SELECT orderkey, custkey, "
            f"totalprice FROM tpch.tiny.orders WHERE orderkey <= {limit}",
            sf=SF)
    text = ("SELECT custkey, count(*) AS n FROM memory.grow_orders "
            "GROUP BY custkey")
    try:
        load(200)  # 181 customers
        small = sql(text, sf=SF, max_groups=128)
        assert counters(small)["capacity_reruns"] == 1
        root = runner.prepare_plan(plan_sql(text, max_groups=128), sf=SF)
        kept = dict(runner._CAPACITY_FEEDBACK[plan_fingerprint(root)])
        (agg,) = kept
        assert isinstance(S.preorder(root)[agg], N.AggregationNode)
        assert kept[agg] == pow2_at_or_above(len(small.rows())) == 256
        load(60000)  # every order: 1,000 customers
        del dispatches[:]
        grown = sql(text, sf=SF, max_groups=128)
        assert [d[2] for d in dispatches] == [1, 0]
        (built, needs, _), (rebuilt, fitted_needs, _) = dispatches
        assert built == kept and needs[agg] > kept[agg]
        assert rebuilt == {agg: pow2_at_or_above(needs[agg])} == {agg: 1024}
        assert fitted_needs == needs
        assert counters(grown)["capacity_reruns"] == 1
        assert "capacity_refits" not in counters(grown)
        roomy = sql(text, sf=SF)
        assert sorted(grown.rows()) == sorted(roomy.rows())
        assert len(grown.rows()) == needs[agg] > len(small.rows())
        assert runner._CAPACITY_FEEDBACK[plan_fingerprint(root)] == rebuilt
    finally:
        memory.drop_table("grow_orders", if_exists=True)


def test_a_plan_with_only_a_distinct_climbs_by_four_as_before(dispatches):
    """A DistinctNode reports a flag and no count: 1,000 custkeys into
    16 slots climbs 16, 64, 256, 1,024 as the one scale did, and the
    capacity that fitted is remembered as it is."""
    text = "SELECT DISTINCT custkey FROM orders"
    tight = sql(text, sf=SF, max_groups=16)
    assert [d for d in dispatches] == [({}, {}, 1)] * 3 + [({}, {}, 0)]
    assert counters(tight)["capacity_reruns"] == 3
    assert "capacity_refits" not in counters(tight)
    assert "capacity_rows" not in counters(tight)
    root = runner.prepare_plan(plan_sql(text, max_groups=16), sf=SF)
    (k,) = [k for k, n in enumerate(S.preorder(root))
            if isinstance(n, N.DistinctNode)]
    assert runner._CAPACITY_FEEDBACK[plan_fingerprint(root)] == {k: 1024}
    assert sorted(tight.rows()) == sorted(sql(text, sf=SF).rows())
    del dispatches[:]
    assert sorted(sql(text, sf=SF, max_groups=16).rows()) \
        == sorted(tight.rows())
    assert dispatches == [({}, {}, 0)]


def test_adaptive_capacity_off_neither_reruns_nor_refits(dispatches):
    """The plan runs as planned: an overflow raises after its one
    dispatch, a fit is left alone, the feedback is neither read nor
    written."""
    off = {"adaptive_capacity": False}
    with pytest.raises(RuntimeError, match="overflowed a static bucket"):
        sql(JOIN, sf=SF, join_capacity=128, session=off)
    assert [d[2] for d in dispatches] == [1]
    root = runner.prepare_plan(plan_sql(JOIN, join_capacity=128), sf=SF)
    assert plan_fingerprint(root) not in runner._CAPACITY_FEEDBACK
    on = sql(JOIN, sf=SF, join_capacity=128)
    kept = runner._CAPACITY_FEEDBACK[plan_fingerprint(root)]
    assert counters(on)["capacity_reruns"] == 1 and min(kept.values()) > 128
    del dispatches[:]
    with pytest.raises(RuntimeError, match="overflowed a static bucket"):
        sql(JOIN, sf=SF, join_capacity=128, session=off)
    assert [set(d[0].values()) for d in dispatches] == [{128}]
    assert runner._CAPACITY_FEEDBACK[plan_fingerprint(root)] == kept
    del dispatches[:]
    roomy = sql(JOIN, sf=SF, join_capacity=1 << 15, session=off)
    assert [d[2] for d in dispatches] == [0]
    assert "capacity_refits" not in counters(roomy)
    assert roomy.rows() == on.rows()


# -- the arithmetic, on plans built by hand ----------------------------------

def _chain():
    """distinct over (agg over (join of (join of a, b), c)) beside an
    unnest: one of every capacity-bearing kind."""
    a, b, c = (N.ValuesNode([T.BIGINT], [(1,)]) for _ in range(3))
    inner = N.JoinNode(a, b, [0], [0])
    outer = N.JoinNode(inner, c, [0], [0], out_capacity=4096)
    agg = N.AggregationNode(outer, [0], [], max_groups=24)
    return N.OutputNode(N.DistinctNode(agg, max_groups=512), ["k"])


def test_capacities_name_every_node_by_its_preorder_index():
    root = _chain()
    kinds = [type(n).__name__ for n in S.preorder(root)]
    assert kinds == ["OutputNode", "DistinctNode", "AggregationNode",
                     "JoinNode", "JoinNode", "ValuesNode", "ValuesNode",
                     "ValuesNode"]
    assert S.capacities(root, 1 << 16) == {1: 512, 2: 24, 3: 4096,
                                           4: 1 << 16}
    assert [S.is_counted(n) for n in S.preorder(root)[:5]] \
        == [False, False, True, True, True]
    assert S.with_capacities(root, S.capacities(root, 1 << 16)) is not root
    same = S.with_capacities(root, {1: 512, 2: 24, 3: 4096})
    assert same is root
    rebuilt = S.with_capacities(root, {1: 8, 2: 16, 3: 32, 4: 64})
    assert S.capacities(rebuilt, 1 << 16) == {1: 8, 2: 16, 3: 32, 4: 64}
    assert plan_fingerprint(rebuilt) != plan_fingerprint(root)
    assert [type(n) for n in S.preorder(rebuilt)] \
        == [type(n) for n in S.preorder(root)]


@pytest.mark.parametrize("needs,grown", [
    # the inner join overflowed: it is sized from its count, the join
    # and the aggregation above it and the distinct grow four times
    ({4: 100_000, 3: 4096, 2: 24}, {4: 131_072, 3: 16_384, 2: 96, 1: 2048}),
    # the outer join alone: the inner join's count is exact and fits
    ({4: 50_000, 3: 5000, 2: 20}, {4: 65_536, 3: 8192, 2: 96, 1: 2048}),
    # the aggregation alone: both joins stay
    ({4: 50_000, 3: 4000, 2: 25}, {4: 65_536, 3: 4096, 2: 32, 1: 2048}),
    # no count passed its capacity (the distinct, or a probe budget):
    # every capacity grows four times, as the one scale did
    ({4: 50_000, 3: 4000, 2: 20}, {4: 262_144, 3: 16_384, 2: 96, 1: 2048}),
], ids=["inner-join", "outer-join", "aggregation", "flag-alone"])
def test_grown_capacities(needs, grown):
    root = _chain()
    ran = S.capacities(root, 1 << 16)
    assert S.grown_capacities(root, ran, needs) == grown


def test_grown_capacities_stop_at_the_ceilings():
    root = _chain()
    ran = {1: S._MAX_GROUPS_CEILING, 2: S._MAX_GROUPS_CEILING,
           3: S._CAPACITY_CEILING, 4: S._CAPACITY_CEILING}
    needs = {4: 1 << 30, 3: 1 << 30, 2: 1 << 30}
    assert S.grown_capacities(root, ran, needs) == ran
    assert S.scaled_capacities(root, ran, 4) == ran
    assert S.scaled_capacities(root, {2: 24, 4: 1 << 16}, 64) \
        == {2: 24 * 64, 4: 1 << 22}


@pytest.mark.parametrize("needs,fitted", [
    # the power of two at or above the need, an exact power kept
    ({4: 149_165, 3: 30_108, 2: 11_988}, {4: 262_144, 3: 32_768, 2: 16_384}),
    ({4: 131_072, 3: 131_073, 2: 4096}, {4: 131_072, 3: 262_144, 2: 4096}),
    # not under a thousand rows, nor under the plan's own where that is
    # smaller still (the aggregation's 24 is the plan's kernel choice)
    ({4: 0, 3: 700, 2: 3}, {4: 1024, 3: 1024, 2: 24}),
    ({4: 1025, 3: 1, 2: 25}, {4: 2048, 3: 1024, 2: 32}),
], ids=["q3-sf1", "exact-power", "floor", "above-floor"])
def test_fitted_capacities(needs, fitted):
    root = _chain()
    base = S.capacities(root, 1 << 16)
    ran = S.scaled_capacities(root, base, 64)
    assert S.fitted_capacities(base, ran, needs) == {**fitted, 1: ran[1]}


@pytest.mark.parametrize("ran,fitted,worth", [
    ({7: 2_097_152, 6: 524_288, 4: 1_048_576},
     {7: 2_097_152, 6: 524_288, 4: 131_072}, True),   # Q3 at SF10
    ({7: 262_144, 6: 262_144, 4: 262_144},
     {7: 262_144, 6: 32_768, 4: 16_384}, True),       # Q3 at SF1
    ({4: 1_048_576}, {4: 1_048_576}, False),          # Q14 at SF10
    ({3: 16_384, 2: 32}, {3: 16_384, 2: 24}, False),  # a program for 8 rows
], ids=["q3-sf10", "q3-sf1", "q14-sf10", "crumbs"])
def test_a_refit_has_to_free_an_eighth_of_the_capacity_rows(ran, fitted,
                                                            worth):
    assert runner._worth_refit(ran, fitted) is worth
