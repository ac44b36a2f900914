"""EXPLAIN: textual plan rendering + EXPLAIN ANALYZE annotation.

Reference surface: the EXPLAIN/EXPLAIN (TYPE DISTRIBUTED) plan printer
(sql/planner/planPrinter/ in presto-main-base) that renders the plan
tree with per-node details and fragment boundaries, and PlanPrinter's
textDistributedPlan-with-stats mode (ExplainAnalyzeOperator) that
annotates each node with observed rows/bytes/wall.

EXPLAIN ANALYZE here executes the SHAPED plan (exec.runner.prepare_plan
-- the exact tree that lowers to XLA, exchanges included) and annotates
from the collected QueryStats: host-visible nodes (scans, the output
root) carry measured rows/bytes/wall micros; interior nodes are fused
into one XLA program by design, so they carry the optimizer's row
estimate and a `fused` marker instead. A stage table (staging / compile
/ execute / exchange / fetch wall+compile micros, FLOPs and bytes from
XLA cost_analysis) and the exchange-collective counters follow the
tree.
"""

from __future__ import annotations

from typing import List

from . import nodes as N
from .fragment import fragment_plan

__all__ = ["explain", "explain_analyze", "explain_distributed"]


def _node_line(n: N.PlanNode) -> str:
    if isinstance(n, N.TableScanNode):
        extra = ""
        if n.physical_dtypes:
            from .widths import widths_summary
            w = widths_summary(n)
            if w:
                extra = f" widths={{{w}}}"
        return (f"TableScan[{n.connector}.{n.table} "
                f"columns={n.columns}{extra}]")
    if isinstance(n, N.ValuesNode):
        return f"Values[{len(n.rows)} rows]"
    if isinstance(n, N.FilterNode):
        return f"Filter[{n.predicate}]"
    if isinstance(n, N.ProjectNode):
        exprs = ", ".join(str(e) for e in n.expressions)
        return f"Project[{exprs}]"
    if isinstance(n, N.AggregationNode):
        aggs = ", ".join(f"{a.name}({'*' if a.input_channel is None else f'ch{a.input_channel}'})"
                         for a in n.aggregates)
        return (f"Aggregate[{n.step} keys=ch{n.group_channels} {aggs} "
                f"maxGroups={n.max_groups}]")
    if isinstance(n, N.JoinNode):
        return (f"Join[{n.join_type.upper()} {n.distribution} "
                f"left{n.left_keys}=right{n.right_keys}]")
    if isinstance(n, N.SemiJoinNode):
        return f"SemiJoin[ch{n.source_key} IN filteringSource ch{n.filtering_key}]"
    if isinstance(n, N.SortNode):
        return f"Sort[{_keys(n.keys)}]"
    if isinstance(n, N.TopNNode):
        return f"TopN[{n.count} by {_keys(n.keys)}]"
    if isinstance(n, N.LimitNode):
        return f"Limit[{n.count}]"
    if isinstance(n, N.DistinctNode):
        return f"Distinct[keys={n.key_channels or 'all'}]"
    if isinstance(n, N.ExchangeNode):
        part = f" by ch{n.partition_channels}" if n.partition_channels else ""
        return f"{n.scope.title()}Exchange[{n.kind}{part}]"
    if isinstance(n, N.OutputNode):
        return f"Output[{n.names}]"
    return type(n).__name__


def _keys(keys) -> str:
    return ", ".join(f"ch{c} {'DESC' if d else 'ASC'}"
                     f"{' NULLS LAST' if nl else ' NULLS FIRST'}"
                     for c, d, nl in keys)


def explain(root: N.PlanNode, *, regions: bool = False, session=None,
            sf: float = 0.01, mesh=None) -> str:
    """Single-plan tree rendering (EXPLAIN (TYPE LOGICAL) analog).
    With ``regions=True`` the plan is first SHAPED exactly as execution
    shapes it (exec.runner.prepare_plan -- region fingerprints and
    demotion/footprint state key on the executed tree, so partitioning
    the raw logical tree would render decisions the engine never
    makes), then each operator line carries the pipeline region it
    fuses into plus the per-region summary tail -- the statement tier's
    plain EXPLAIN opts in so fusion decisions are inspectable without
    executing."""
    node_region: dict = {}
    rplan = None
    if regions and not _is_write_root(root):
        # write/DDL roots are never partitioned by execution (they run
        # host-side and only their inner SELECT re-enters run_query) --
        # annotating them would render regions the engine never forms
        from ..exec.regions import partition_regions
        from ..exec.runner import prepare_plan
        root = prepare_plan(root, sf=sf, mesh=mesh, session=session)
        rplan = partition_regions(root, session=session, sf=sf, mesh=mesh)
        node_region = rplan.node_region
    lines: List[str] = []

    from ..exec.accuracy import est_rows_of

    def walk(n: N.PlanNode, depth: int):
        tag = ""
        # per-node planner estimate (stamped at prepare_plan when the
        # tree was prepared, computed fresh otherwise -- same pure
        # function either way), so estimate provenance is visible
        # BEFORE a query runs and stale connector stats are
        # diagnosable offline
        est = est_rows_of(n, sf)
        if est is not None:
            tag += f"  estRows={est:.0f}"
        if id(n) in node_region:
            tag += f"  [region=R{node_region[id(n)]}]"
        lines.append("    " * depth + "- " + _node_line(n) + tag)
        for s in n.sources:
            walk(s, depth + 1)

    walk(root, 0)
    if rplan is not None:
        lines.extend(_region_lines(rplan, None, sf))
    return "\n".join(lines)


def _is_write_root(root: N.PlanNode) -> bool:
    """Mirrors exec.runner._run_query_inner's write/DDL routing: these
    roots execute host-side and never partition into regions."""
    inner = root.source if isinstance(root, N.OutputNode) else root
    return isinstance(inner, (N.DdlNode, N.TableFinishNode,
                              N.TableWriterNode, N.TableRewriteNode))


def _region_lines(rplan, runtime_counters, sf: float) -> List[str]:
    """The '-- regions --' tail: one line per pipeline region with its
    fused-op count, boundary reason, fingerprint, footprint estimates
    (static + measured K005 when the auditor has seen it) and -- when
    the query executed materialized -- the region's device wall."""
    from ..exec.plan_cache import plan_fingerprint
    from ..exec.regions import estimate_region_bytes, fusion_memory
    lines = ["", f"-- regions ({len(rplan.regions)}, "
                 f"fusion {'on' if rplan.fused else 'off'}) --"]
    mem = fusion_memory()
    for reg in rplan.regions:
        fp = plan_fingerprint(reg.root)
        extra = ""
        measured = mem.footprint(fp)
        if measured:
            extra += f" k005Peak={_fmt_bytes(measured)}"
        demoted = mem.demoted(fp)
        if demoted:
            extra += " demoted"
        if runtime_counters:
            dev = runtime_counters.get(
                f"fusion_region_{reg.tag}_device_us")
            if dev:
                extra += f" device={int(dev['total'])}us"
        lines.append(f"{reg.tag}: ops={reg.ops} reason={reg.reason} "
                     f"fingerprint={fp[:12]} "
                     f"estPeak={_fmt_bytes(estimate_region_bytes(reg, sf))}"
                     f"{extra}")
        lines.append(f"    {reg.span}")
    return lines


def _fmt_bytes(n: int) -> str:
    if n >= 1 << 30:
        return f"{n / (1 << 30):.2f}GB"
    if n >= 1 << 20:
        return f"{n / (1 << 20):.2f}MB"
    if n >= 1 << 10:
        return f"{n / (1 << 10):.1f}KB"
    return f"{n}B"


def _collect_scan_leaves(root: N.PlanNode) -> List[N.PlanNode]:
    """Scan leaves in the planner's staging order (exec.planner
    _collect_scans: DFS, identity-deduped) so annotation keys scan[i]
    line up with the runner's OperatorStats keys."""
    from ..exec.planner import _collect_scans
    out: List[N.PlanNode] = []
    _collect_scans(root, out)
    return out


def _annotated_tree(root: N.PlanNode, qs, sf: float,
                    node_region=None) -> str:
    from .stats import estimate_rows

    scan_index = {id(n): i for i, n in enumerate(_collect_scan_leaves(root))}
    ops = qs.operators if qs is not None else {}
    node_region = node_region or {}
    lines: List[str] = []
    seen = set()

    def annotate(n: N.PlanNode, is_root: bool) -> str:
        from ..exec.runner import _scan_key
        op = None
        if id(n) in scan_index:
            op = ops.get(_scan_key(scan_index[id(n)], n))
        elif is_root:
            op = ops.get("output")
        if op is not None:
            return (f"  [rows={op.output_rows} "
                    f"bytes={_fmt_bytes(op.output_bytes)} "
                    f"wall={op.wall_us}us"
                    + (f" tasks={op.task_count}" if op.task_count > 1
                       else "") + "]")
        if isinstance(n, N.ExchangeNode) and n.scope == "REMOTE":
            return "  [collective: fused into execute stage]"
        est = None
        try:
            est = estimate_rows(n, sf)
        except Exception:  # noqa: BLE001 - estimates are best-effort
            est = None
        if est is not None:
            return f"  [est. {int(est)} rows, fused]"
        return "  [fused]"

    def walk(n: N.PlanNode, depth: int, is_root: bool):
        line = "    " * depth + "- " + _node_line(n)
        if id(n) in seen:
            lines.append(line + "  [shared subtree]")
            return
        seen.add(id(n))
        tag = f"  [region=R{node_region[id(n)]}]" \
            if id(n) in node_region else ""
        lines.append(line + annotate(n, is_root) + tag)
        for s in n.sources:
            walk(s, depth + 1, False)

    walk(root, 0, True)
    return "\n".join(lines)


def explain_analyze(root: N.PlanNode, sf: float = 0.01, **kwargs) -> str:
    """EXPLAIN ANALYZE: shape the plan exactly as execution will
    (prepare_plan), run it, and annotate the executed tree with the
    collected QueryStats (ExplainAnalyzeOperator analog -- per-node
    rows/bytes/wall where host-visible, per-stage wall/compile micros
    with XLA cost_analysis FLOPs, exchange-collective counts). Stats
    inside one fused XLA program are not separable by design; fused
    nodes carry optimizer row estimates instead."""
    from ..exec.runner import prepare_plan, run_query

    session = dict(kwargs.pop("session", None) or {})
    # EXPLAIN ANALYZE always pays the one extra trace for FLOPs/bytes
    session.setdefault("query_cost_analysis", True)
    mesh = kwargs.get("mesh")
    executed = prepare_plan(root, sf=sf, mesh=mesh, session=session)
    res = run_query(executed, sf=sf, session=session, prepared=True,
                    **kwargs)
    qs = res.query_stats
    # region grouping (exec/regions.py): re-partition the executed tree
    # under the same session/kernel mode -- deterministic, so the
    # annotation matches what ran (modulo a demotion this very run
    # recorded, which the NEXT run and this tail both reflect). Write
    # roots never partition (they execute host-side).
    rplan = None
    if not _is_write_root(executed):
        from ..exec.regions import partition_regions
        rplan = partition_regions(executed, session=session, sf=sf,
                                  mesh=mesh)
    lines = [_annotated_tree(executed, qs, sf,
                             node_region=rplan.node_region
                             if rplan else None)]
    if rplan is not None:
        lines.extend(_region_lines(rplan, res.stats, sf))
    if qs is not None:
        lines += ["", "-- stages --"]
        for name in ("staging", "compile", "execute", "exchange", "fetch"):
            st = qs.stages.get(name)
            if st is None:
                continue
            extra = ""
            if st.compile_us:
                extra += f" compile={st.compile_us}us"
            if st.flops:
                extra += f" flops={st.flops:.3g}"
            if st.bytes_accessed:
                extra += f" bytesAccessed={st.bytes_accessed:.3g}"
            if st.rows:
                extra += f" rows={st.rows}"
            if st.bytes:
                extra += f" bytes={_fmt_bytes(st.bytes)}"
            lines.append(f"{name}: wall={st.wall_us}us{extra}")
        if qs.counters:
            lines += ["", "-- collectives --"]
            for k in sorted(qs.counters):
                lines.append(f"{k}: {qs.counters[k]}")
        lines.append("")
        lines.append(f"output rows: {res.row_count}, "
                     f"peak memory: {_fmt_bytes(qs.peak_memory_bytes)}, "
                     f"wall: {qs.wall_us}us")
    else:
        lines += ["", f"output rows: {res.row_count}"]
    lines.extend(_datapath_lines(qs))
    lines.extend(_accuracy_lines(qs))
    # the flat named counters keep their historical tail section
    if res.stats:
        lines += ["", "-- runtime counters --"]
        for name, s in sorted(res.stats.items()):
            lines.append(f"{name}: total={s['total']} count={s['count']} "
                         f"max={s['max']}")
    return "\n".join(lines)


def _datapath_lines(qs) -> List[str]:
    """EXPLAIN ANALYZE's data-path waterfall tail (exec/datapath.py):
    one line per hop THIS query exercised -- bytes, wall, achieved
    rate, utilization of the hop's measured ceiling -- closed by the
    named bottleneck verdict (the hop with max wall share below band).
    The first call in a process pays the one-shot ceilings probe."""
    try:
        from ..exec.datapath import (HOP_CEILING, HOPS, achieved_b_per_s,
                                     bottleneck_verdict, probe_ceilings)
        if qs is None or not qs.datapath:
            return []
        ceilings = probe_ceilings()
        lines = ["", "-- datapath --"]
        total_wall = sum(h.wall_us for h in qs.datapath.values())
        for hop in HOPS:
            h = qs.datapath.get(hop)
            if h is None:
                continue
            achieved = achieved_b_per_s(h.bytes, h.wall_us)
            ceiling = ceilings.get(HOP_CEILING.get(hop, ""), 0.0)
            util = achieved / ceiling if ceiling > 0 else 0.0
            share = h.wall_us / total_wall if total_wall else 0.0
            lines.append(
                f"{hop}: bytes={_fmt_bytes(h.bytes)} "
                f"wall={h.wall_us}us ({share:.0%}) "
                f"rate={achieved / 1e9:.3f}GB/s "
                f"util={util:.0%} of {HOP_CEILING.get(hop, '?')}")
        verdict = bottleneck_verdict(qs.datapath, ceilings)
        if verdict is not None:
            qual = "below band" if verdict["belowBand"] else \
                "at ceiling; largest wall share"
            lines.append(
                f"bottleneck: {verdict['hop']} "
                f"(wall share {verdict['wallShare']:.0%}, "
                f"util {verdict['utilization']:.0%}, {qual})")
        return lines
    except Exception:  # noqa: BLE001 - the waterfall is garnish here;
        # EXPLAIN ANALYZE output must never fail on it
        return []


def _accuracy_lines(qs) -> List[str]:
    """EXPLAIN ANALYZE's estimate-accuracy tail (exec/accuracy.py):
    one line per recorded plan node -- the planner's estimate beside
    what the runtime measured, folded into a q-error with direction --
    closed by the named misestimate verdict."""
    try:
        from ..exec.accuracy import (direction_of, misestimate_verdict,
                                     q_error)
        if qs is None or not qs.accuracy:
            return []
        lines = ["", "-- accuracy --"]
        for node in sorted(qs.accuracy):
            r = qs.accuracy[node]
            q = q_error(r.est, r.actual)
            est_s = f"{r.est:.0f}" if r.est is not None else "?"
            act_s = f"{r.actual:.0f}" if r.actual is not None else "?"
            q_s = (f"{q:.2f}x {direction_of(r.est, r.actual)}"
                   if q is not None else "-")
            lines.append(f"{node}: est={est_s} actual={act_s} "
                         f"q={q_s} [{r.unit}]")
        verdict = misestimate_verdict(qs.accuracy)
        if verdict is not None:
            qual = "within band" if verdict["withinBand"] \
                else "MISESTIMATE"
            lines.append(f"verdict: {verdict['message']} ({qual})")
        return lines
    except Exception:  # noqa: BLE001 - the ledger is garnish here;
        # EXPLAIN ANALYZE output must never fail on it
        return []


def explain_distributed(root: N.PlanNode) -> str:
    """Fragment-by-fragment rendering (EXPLAIN (TYPE DISTRIBUTED) analog)."""
    out: List[str] = []
    for frag in fragment_plan(root):
        out.append(f"Fragment {frag.id} [{frag.partitioning}]"
                   + (f" <- fragments {frag.remote_sources}"
                      if frag.remote_sources else ""))
        out.append(explain(frag.root))
        out.append("")
    return "\n".join(out).rstrip()
