"""Dynamic filtering: build-side join keys prune probe-side scans.

Reference surface: operator/DynamicFilterSourceOperator.java:50 (build
side collects its key values at runtime), sql/planner/
LocalDynamicFilter.java:44 (the collected domain pushed into the probe
side's scan), presto-expressions' DynamicFilters.

TPU-first placement: the payoff on this engine is at STAGING -- fewer
fact rows materialized into HBM (smaller static shapes = smaller
programs), not a per-row filter inside the fused plan (XLA would fuse
such a filter for free anyway, but by then the rows were already
staged). So the runner pre-executes small DIMENSION build sides
host-side, derives each probe key's domain (min/max plus an exact
value set when the build is small), and applies it to the fact scan's
host arrays BEFORE they are staged. Results are unchanged by
construction: only rows that cannot join are dropped, and only under
join types that do not preserve unmatched probe rows (INNER/RIGHT).
Counters (dynamic_filter_rows_pruned / dynamic_filters) surface
through EXPLAIN ANALYZE.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..plan import nodes as N

__all__ = ["collect_dynamic_filters", "apply_dynamic_filters"]

# builds estimated beyond this don't qualify (collection would rival
# the scan it prunes; join-max-broadcast-table-size spirit)
_MAX_BUILD_ROWS = 1 << 20
# exact-set filtering (isin) below this many distinct keys; above it
# min/max range pruning still applies
_SET_LIMIT = 1 << 16


def _strip_exchanges(node: N.PlanNode) -> N.PlanNode:
    while isinstance(node, N.ExchangeNode):
        node = node.source
    return node


def _is_dimension_subtree(node: N.PlanNode) -> bool:
    node = _strip_exchanges(node)
    if isinstance(node, N.TableScanNode):
        return True
    if isinstance(node, (N.FilterNode, N.ProjectNode)):
        return _is_dimension_subtree(node.source)
    return False


def _trace_to_scan(node: N.PlanNode, channel: int
                   ) -> Optional[Tuple[N.TableScanNode, int]]:
    """Like plan.stats.column_source, but returns the scan NODE (by
    identity) so the runner can target its staging."""
    from ..expr import ir as E
    if isinstance(node, N.TableScanNode):
        if 0 <= channel < len(node.columns):
            return node, channel
        return None
    if isinstance(node, N.ProjectNode):
        e = node.expressions[channel] \
            if 0 <= channel < len(node.expressions) else None
        if isinstance(e, E.InputReference):
            return _trace_to_scan(node.source, e.channel)
        return None
    if isinstance(node, (N.FilterNode, N.ExchangeNode)):
        # NOT SampleNode: Bernoulli sampling hashes the staged row
        # index, so pre-staging compaction would change which rows
        # survive the sample
        return _trace_to_scan(node.sources[0], channel)
    if isinstance(node, N.JoinNode):
        nleft = len(node.left.output_types())
        if channel < nleft:
            return _trace_to_scan(node.left, channel)
        return None  # build-side columns: a filter there has no fact win
    if isinstance(node, N.SemiJoinNode):
        n_src = len(node.source.output_types())
        if channel < n_src:
            return _trace_to_scan(node.source, channel)
        return None
    return None


def collect_dynamic_filters(root: N.PlanNode, sf: float,
                            ) -> Dict[str, List[Tuple[int, object]]]:
    """Find qualifying joins, EXECUTE their dimension build sides, and
    return {scan_node_id: [(scan_column_index, domain)]} where domain =
    (lo, hi, values-or-None). Joins qualify when the build is a small
    scan/filter/project subtree and the join type drops unmatched probe
    rows (INNER/RIGHT)."""
    from ..plan.stats import estimate_rows

    joins: List[N.JoinNode] = []
    seen: Dict[int, N.PlanNode] = {}
    parent_ids: Dict[int, set] = {}

    def walk(n: N.PlanNode):
        if id(n) in seen:
            return
        seen[id(n)] = n
        if isinstance(n, N.JoinNode):
            joins.append(n)
        for s in n.sources:
            parent_ids.setdefault(id(s), set()).add(id(n))
            walk(s)

    walk(root)

    def _single_consumer(scan: N.PlanNode, join: N.JoinNode) -> bool:
        """The pruned batch is keyed by scan id and shared by every
        reader (plan DAGs: CTE planned once); pruning is only safe when
        each node from the scan up to the join has exactly ONE parent,
        so no other branch reads the filtered rows."""
        cur = scan
        while cur is not join:
            parents = parent_ids.get(id(cur), set())
            if len(parents) != 1:
                return False
            cur = seen[next(iter(parents))]
        return True
    out: Dict[str, List[Tuple[int, object]]] = {}
    for j in joins:
        if j.join_type not in ("inner", "right"):
            continue
        build = _strip_exchanges(j.right)
        if not _is_dimension_subtree(build):
            continue
        est = estimate_rows(build, sf)
        if est is None or est > _MAX_BUILD_ROWS:
            continue
        targets = []
        for probe_ch, build_ch in zip(j.left_keys, j.right_keys):
            hit = _trace_to_scan(j.left, probe_ch)
            ty = build.output_types()[build_ch]
            if hit is None or not (ty.is_integral or ty.is_decimal
                                   or ty.base == "date"):
                continue
            if not _single_consumer(hit[0], j):
                continue
            targets.append((hit, build_ch))
        if not targets:
            continue
        domains = _build_domains(build, sf, [bc for _, bc in targets])
        if domains is None:
            continue
        for (scan, scan_col), dom in zip((t[0] for t in targets), domains):
            if dom is not None:
                out.setdefault(scan.id, []).append((scan_col, dom))
    return out


def _build_domains(build: N.PlanNode, sf: float, channels: List[int]):
    """Run the dimension subtree and pull the key domains to host."""
    import jax

    from ..block import to_numpy
    from .planner import compile_plan

    try:
        plan = compile_plan(build)
        from .runner import _scan_batch
        # staged afresh, not through the resident tier: this program is
        # traced, lowered and read from the compile cache anew in every
        # statement, and on the chip that ran several times slower, in
        # stretches of seconds, on resident inputs than on fresh ones
        batches = [_scan_batch(s, sf, None, 8, resident=False)
                   for s in plan.scan_nodes]
        out, _flags = jax.jit(plan.fn)(batches)
    except Exception:  # noqa: BLE001 - collection is best-effort
        return None
    act = np.asarray(out.active)
    domains = []
    for ch in channels:
        vals, nulls = to_numpy(out.column(ch))
        live = act & ~nulls
        v = vals[live]
        if v.dtype == object:  # long decimals: python ints
            v = np.array([int(x) for x in v], dtype=np.float64)
        if len(v) == 0:
            domains.append((0, -1, np.array([], dtype=np.int64)))
            continue
        uniq = np.unique(v)
        domains.append((v.min(), v.max(),
                        uniq if len(uniq) <= _SET_LIMIT else None))
    return domains


def apply_dynamic_filters(arrays: Dict[str, np.ndarray],
                          columns: List[str],
                          filters: List[Tuple[int, object]],
                          ) -> Tuple[np.ndarray, int]:
    """Row mask for one scan's host arrays under its collected domains.
    Returns (keep_mask, pruned_count)."""
    n = len(arrays[columns[0]])
    keep = np.ones(n, dtype=bool)
    for col_idx, (lo, hi, values) in filters:
        v = arrays[columns[col_idx]]
        if v.dtype == object:
            v = np.array([int(x) for x in v], dtype=np.float64)
        keep &= (v >= lo) & (v <= hi)
        if values is not None:
            keep &= np.isin(v, values)
    return keep, int(n - keep.sum())
