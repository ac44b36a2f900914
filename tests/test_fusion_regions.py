"""Pipeline-region fusion compiler (exec/regions.py + the runner's
region executor): partition law, bit-exact fused-vs-materialized oracle
match over the TPC-H corpus, footprint refusal, demotion,
plan-cache behavior, and IR-audit cleanliness of the fused corpus.
"""

import numpy as np
import pytest

from presto_tpu import failpoints
from presto_tpu.exec.plan_cache import (cache_stats, cached_compile,
                                        clear_plan_cache, plan_fingerprint)
from presto_tpu.exec.regions import (FusionMemory, estimate_node_bytes,
                                     fusion_enabled, fusion_memory,
                                     partition_regions)
from presto_tpu.exec.runner import prepare_plan, run_query
from presto_tpu.plan import nodes as N
from presto_tpu.queries.tpch_sql import TPCH_QUERIES, tpch_query
from presto_tpu.sql import plan_sql
from presto_tpu.sql import sql as run_sql

SF = 0.01

Q1 = """SELECT returnflag, linestatus, sum(quantity) q, count(*) c
FROM lineitem WHERE shipdate <= date '1998-09-02'
GROUP BY returnflag, linestatus ORDER BY returnflag, linestatus"""


@pytest.fixture(autouse=True)
def _clean_fusion_memory():
    fusion_memory().clear()
    yield
    fusion_memory().clear()
    failpoints.disarm_all()


def _prepared(text=Q1, **kw):
    return prepare_plan(plan_sql(text, **kw), sf=SF)


def _canon(res):
    return res.canonical_rows()


# -- partition law ------------------------------------------------------


def test_fused_default_is_one_region_keeping_the_plan_fingerprint():
    """Fusion on + nothing refused = ONE region whose root IS the plan
    (same object, same fingerprint) -- the plan-cache/kernaudit
    keying contract of the refactor."""
    root = _prepared()
    rp = partition_regions(root, sf=SF)
    assert rp.fused and len(rp.regions) == 1
    assert rp.regions[0].root is root
    assert plan_fingerprint(rp.regions[0].root) == plan_fingerprint(root)


def test_partition_covers_every_operator_exactly_once():
    """Partition law: every non-leaf operator lands in exactly one
    region, leaves (scans) in none, in BOTH modes."""
    root = _prepared()
    for session in (None, {"fusion": False}):
        rp = partition_regions(root, sf=SF, session=session)
        ops = []

        def walk(n):
            if not isinstance(n, (N.TableScanNode, N.ValuesNode,
                                  N.RemoteSourceNode)):
                ops.append(n)
            for s in n.sources:
                walk(s)

        walk(root)
        assert set(rp.node_region) == {id(n) for n in ops}
        assert sum(r.ops for r in rp.regions) == len(ops)
        # producers precede consumers, and the last region owns the root
        for reg in rp.regions:
            for inp in reg.inputs:
                if inp.kind == "region":
                    assert inp.region < reg.index
        assert rp.node_region[id(root)] == rp.regions[-1].index


def test_per_op_mode_materializes_each_operator():
    root = _prepared()
    rp = partition_regions(root, sf=SF, session={"fusion": False})
    assert not rp.fused and len(rp.regions) > 1
    # Output and single-chip exchanges are transparent; everything else
    # runs alone
    for reg in rp.regions:
        standalone = [n for n in [reg.root]
                      if not isinstance(n, (N.OutputNode, N.ExchangeNode))]
        assert reg.ops <= 2 or not standalone


def test_mesh_plans_are_always_one_region():
    """Seam invariant: an SPMD plan's collectives are gang-scheduled
    inside ONE program -- no session/env setting may split it."""
    import jax
    from jax.sharding import Mesh

    from presto_tpu.parallel.mesh import WORKERS_AXIS
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:1]), (WORKERS_AXIS,))
    root = prepare_plan(plan_sql(Q1), sf=SF, mesh=mesh)
    for session in (None, {"fusion": False}):
        rp = partition_regions(root, sf=SF, session=session, mesh=mesh)
        assert len(rp.regions) == 1
        assert rp.regions[0].reason == "mesh"


def test_streaming_and_spill_seams_stay_outside_regions():
    """The streaming/spill executors take over before partitioning:
    run_query with split_rows on a streamable shape never reaches the
    region executor, and its result still matches the fused one (the
    seam contract)."""
    streamable = """SELECT returnflag, sum(quantity) q, count(*) c
    FROM lineitem WHERE shipdate <= date '1998-09-02'
    GROUP BY returnflag"""
    root = _prepared(streamable, max_groups=16)
    full = run_query(root, sf=SF, prepared=True)
    streamed = run_query(root, sf=SF, prepared=True, split_rows=8192,
                         session={"fusion": False})
    assert _canon(full) == _canon(streamed)
    assert "fusion_regions" not in streamed.stats


def test_fusion_env_gate(monkeypatch):
    monkeypatch.setenv("PRESTO_TPU_FUSION", "0")
    assert not fusion_enabled(None)
    assert fusion_enabled({"fusion": True})  # session overrides env
    monkeypatch.setenv("PRESTO_TPU_FUSION", "1")
    assert fusion_enabled(None)
    assert not fusion_enabled({"fusion": False})


# -- bit-exact oracle match over the corpus -----------------------------


# diverse-shape tier-1 slice (agg pipeline, join chains, global agg,
# case+join, exists/not-exists subqueries); the FULL q1-q22 sweep rides
# the slow marker -- tier-1's wall budget is shared with ~800 tests
_TIER1_ORACLE_SLICE = (1, 3, 6, 12, 19)


@pytest.mark.parametrize(
    "qnum",
    [q if q in _TIER1_ORACLE_SLICE else
     pytest.param(q, marks=pytest.mark.slow)
     for q in sorted(TPCH_QUERIES)])
def test_fused_vs_materialized_oracle_match(qnum):
    """TPC-H q1-q22: the materialized (per-operator) region executor
    returns EXACTLY the fused program's rows. Bit-exact because region
    boundaries hand off the same Batch values the fused program passes
    between operators internally."""
    q = tpch_query(qnum)
    kw = dict(max_groups=q.max_groups)
    if q.join_capacity:
        kw["join_capacity"] = q.join_capacity
    fused = run_sql(q.text, sf=SF, **kw)
    perop = run_sql(q.text, sf=SF, session={"fusion": False}, **kw)
    assert _canon(fused) == _canon(perop), f"q{qnum} fused != materialized"
    assert "fusion_regions" in perop.stats, f"q{qnum} ran fused?"


@pytest.mark.parametrize(
    "qnum", [1, pytest.param(6, marks=pytest.mark.slow),
             12, pytest.param(14, marks=pytest.mark.slow)])
def test_mesh_tier_oracle_match_under_fusion_modes(qnum):
    """Mesh tier: fusion on/off lowers the SAME single SPMD program;
    results match the local fused oracle."""
    import jax
    from jax.sharding import Mesh

    from presto_tpu.parallel.mesh import WORKERS_AXIS
    devs = jax.devices()
    mesh = Mesh(np.array(devs[:1]), (WORKERS_AXIS,))
    q = tpch_query(qnum)
    kw = dict(max_groups=q.max_groups)
    if q.join_capacity:
        kw["join_capacity"] = q.join_capacity
    local = run_sql(q.text, sf=SF, **kw)
    for session in (None, {"fusion": False}):
        dist = run_sql(q.text, sf=SF, mesh=mesh, session=session, **kw)
        assert _canon(dist) == _canon(local), f"q{qnum} mesh mismatch"


# -- footprint-based fusion refusal -------------------------------------


def test_tight_budget_refuses_fusion():
    root = _prepared()
    rp = partition_regions(root, sf=SF,
                           session={"kernel_audit_budget_bytes": 1})
    assert len(rp.regions) > 1
    assert any("budget" in r.reason for r in rp.regions)
    # and the query still runs correctly under the refusal
    res = run_query(root, sf=SF, prepared=True,
                    session={"kernel_audit_budget_bytes": 1})
    baseline = run_query(root, sf=SF, prepared=True)
    assert _canon(res) == _canon(baseline)


def test_budget_wide_enough_keeps_fusion():
    root = _prepared()
    rp = partition_regions(root, sf=SF,
                           session={"kernel_audit_budget_bytes": 1 << 34})
    assert len(rp.regions) == 1


def test_k005_feedback_overrides_static_estimate():
    """A measured K005 peak (fed back per region fingerprint) beyond
    the budget refuses the fusion even when the static estimate fits."""
    root = _prepared()
    fp = plan_fingerprint(root)
    static = sum(estimate_node_bytes(n, SF)
                 for n in [root] + list(_walk_ops(root)))
    budget = max(static * 4, 1 << 24)  # static estimate fits easily
    rp = partition_regions(root, sf=SF,
                           session={"kernel_audit_budget_bytes": budget})
    assert len(rp.regions) == 1
    fusion_memory().note_footprint(fp, budget + 1)  # the auditor's word
    rp = partition_regions(root, sf=SF,
                           session={"kernel_audit_budget_bytes": budget})
    assert len(rp.regions) > 1
    assert any("footprint" in r.reason for r in rp.regions)


def _walk_ops(root):
    out = []

    def walk(n):
        for s in n.sources:
            out.append(s)
            walk(s)

    walk(root)
    return out


def test_live_kernel_audit_feeds_fusion_footprint():
    """With kernel_audit armed, the staged program's K005 estimate
    lands in the fusion memory under the span fingerprint."""
    root = _prepared()
    fp = plan_fingerprint(root)
    assert fusion_memory().footprint(fp) == 0
    run_query(root, sf=SF, prepared=True, session={"kernel_audit": True})
    assert fusion_memory().footprint(fp) > 0


# -- sample-driven demotion ---------------------------------------------


def test_demotion_comparator_uses_perfgate_bands():
    mem = FusionMemory()
    fp = "f" * 12
    for v in (1000, 1020, 980):
        mem.note_unfused(fp, v)
    for v in (1040, 1060, 1010):
        mem.note_fused(fp, v)   # inside the band: no demotion
    assert mem.maybe_demote(fp) is None and mem.demoted(fp) is None
    for v in (5000, 5200, 4100):
        mem.note_fused(fp, v)   # way past the band: demote
    verdict = mem.maybe_demote(fp)
    assert verdict is not None and verdict["metric"] == "region_device_us"
    assert mem.demoted(fp)
    assert mem.maybe_demote(fp) is None  # demotion is edge-triggered


def test_demoted_span_partitions_materialized_and_still_matches():
    root = _prepared()
    baseline = run_query(root, sf=SF, prepared=True)
    fusion_memory().demote(plan_fingerprint(root), "test")
    rp = partition_regions(root, sf=SF)
    assert len(rp.regions) > 1
    assert any("demoted" in r.reason for r in rp.regions)
    res = run_query(root, sf=SF, prepared=True)
    assert "fusion_regions" in res.stats
    assert _canon(res) == _canon(baseline)


def test_runner_feeds_fused_and_unfused_samples():
    """The live wiring of the comparator: fused runs feed note_fused,
    materialized runs feed note_unfused under the SAME span key."""
    root = _prepared()
    fp = plan_fingerprint(root)
    run_query(root, sf=SF, prepared=True)
    assert fp in fusion_memory()._fused
    run_query(root, sf=SF, prepared=True, session={"fusion": False})
    assert fp in fusion_memory()._unfused


def test_failpoint_forces_demotion_mid_query():
    """fusion.demote armed: the query demotes, re-partitions, executes
    materialized, matches -- and the demotion sticks for later
    submissions until cleared."""
    root = _prepared()
    baseline = run_query(root, sf=SF, prepared=True)
    failpoints.arm("fusion.demote", "error:once")
    try:
        res = run_query(root, sf=SF, prepared=True)
    finally:
        failpoints.disarm_all()
    assert _canon(res) == _canon(baseline)
    assert "fusion_forced_demotions" in res.stats
    assert "fusion_regions" in res.stats
    assert fusion_memory().demoted(plan_fingerprint(root))
    res2 = run_query(root, sf=SF, prepared=True)   # sticky
    assert "fusion_regions" in res2.stats
    fusion_memory().clear()
    res3 = run_query(root, sf=SF, prepared=True)   # cleared: fused again
    assert "fusion_regions" not in res3.stats


# -- plan cache ---------------------------------------------------------


def test_region_programs_hit_the_plan_cache_on_repeat():
    clear_plan_cache()
    root = _prepared()
    run_query(root, sf=SF, prepared=True, session={"fusion": False})
    s1 = cache_stats()
    assert s1["misses"] >= 2  # one compile per region
    run_query(root, sf=SF, prepared=True, session={"fusion": False})
    s2 = cache_stats()
    assert s2["misses"] == s1["misses"]      # no recompiles
    assert s2["hits"] >= s1["hits"] + s1["misses"] - 1


def test_join_free_fingerprints_are_capacity_insensitive():
    """The satellite fix: join-free plans compile ONCE across
    default_join_capacity values; join plans still key on it."""
    clear_plan_cache()
    root = _prepared()
    cached_compile(root, None, 1 << 16)
    cached_compile(root, None, 1 << 20)
    assert cache_stats() == {"entries": 1, "hits": 1, "misses": 1}
    jroot = prepare_plan(plan_sql(
        "SELECT c.name FROM customer c JOIN orders o "
        "ON c.custkey = o.custkey"), sf=SF)
    clear_plan_cache()
    cached_compile(jroot, None, 1 << 16)
    cached_compile(jroot, None, 1 << 20)
    assert cache_stats()["misses"] == 2


def test_join_free_region_reruns_do_not_fragment_cache():
    """Same plan, different runner join-capacity defaults -> one cached
    executable per region, both runs, both modes."""
    clear_plan_cache()
    root = _prepared()
    run_query(root, sf=SF, prepared=True, default_join_capacity=1 << 16,
              session={"fusion": False})
    misses = cache_stats()["misses"]
    run_query(root, sf=SF, prepared=True, default_join_capacity=1 << 18,
              session={"fusion": False})
    assert cache_stats()["misses"] == misses


# -- provenance surfaces ------------------------------------------------


def test_explain_renders_region_annotations():
    from presto_tpu.plan import explain, explain_analyze
    txt = explain(plan_sql(Q1), regions=True, sf=SF)
    assert "[region=R0]" in txt and "-- regions (1, fusion on) --" in txt
    txt2 = explain_analyze(plan_sql(Q1), sf=SF,
                           session={"fusion": False})
    assert "-- regions (" in txt2 and "fusion off" in txt2
    assert "region=R1" in txt2
    assert "reason=materialized" in txt2


# -- IR audit over the fused corpus (the lint_all gate's tier-1 slice) --


@pytest.mark.lint
@pytest.mark.parametrize("qnum", (1, 6, 3))
def test_kernaudit_clean_over_fused_queries(qnum):
    """K001-K005 over the region executor's programs: audit the fused
    corpus slice live (full q1-q22 x both tiers = scripts/kernaudit.py
    with PRESTO_TPU_FUSION=1 in lint_all.sh)."""
    q = tpch_query(qnum)
    kw = dict(max_groups=q.max_groups)
    if q.join_capacity:
        kw["join_capacity"] = q.join_capacity
    res = run_sql(q.text, sf=SF, session={"kernel_audit": True}, **kw)
    counters = res.query_stats.counters
    findings = {k: v for k, v in counters.items()
                if k.startswith("kernel_audit.K")}
    assert not findings, f"q{qnum} fused program has findings {findings}"
