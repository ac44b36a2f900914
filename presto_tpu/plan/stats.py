"""Plan statistics: column provenance, NDV and row estimates.

Also home of the adaptive-rerun rewrite (`capacities`,
`with_capacities`, `grown_capacities`, `fitted_capacities`): when a
static bucket overflows at runtime, the runner re-plans with the
capacity of each node that the program counted sized from its count and
the others geometrically enlarged (the memory-feedback analog of the
reference's reserve/revoke loop) instead of failing the query, and a
plan that fitted is kept at what its nodes needed -- the piece that
lets NDV-driven sizing stand WITHOUT per-query hand hints.

Reference surface: the cost/stats stack --
presto-main-base/.../cost/StatsCalculator.java (per-PlanNode stats
propagation), cost/CostCalculatorUsingExchanges.java, and the connector
statistics providers (TpchMetadata.getTableStatistics). This is the
deliberately small TPU-engine version: statistics answer exactly the
questions the physical planner asks --

  * how many distinct groups can this GROUP BY produce?  (sizes the
    static group table; small tables unlock the scatter-free MXU
    kernels in ops/aggregation.py)
  * roughly how many rows feed this join side?  (broadcast vs
    partitioned distribution)

NDV answers are UPPER BOUNDS (connector contract), so capacities sized
from them cannot overflow. Row estimates are heuristic (filters taken
at face value x selectivity guess) and are only used for relative
cost choices, never for capacities.
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..expr import ir as E
from . import nodes as N

__all__ = ["column_source", "estimate_distinct", "estimate_group_bound",
           "estimate_rows", "refine_capacities", "preorder", "preorder_index",
           "is_counted",
           "capacities", "scaled_capacities", "with_capacities",
           "fitted_capacities", "grown_capacities"]

# guessed fraction of rows surviving one filter conjunct (Presto's
# UNKNOWN_FILTER_COEFFICIENT analog, FilterStatsCalculator.java)
_FILTER_SELECTIVITY = 0.33


def column_source(node: N.PlanNode, channel: int
                  ) -> Optional[Tuple[str, str, str]]:
    """Trace an output channel to its originating base-table column:
    (connector, table, column), or None when the channel is computed
    (expressions, aggregates) or crosses an un-traceable operator."""
    if isinstance(node, N.TableScanNode):
        if 0 <= channel < len(node.columns):
            return (node.connector, node.table, node.columns[channel])
        return None
    if isinstance(node, N.ProjectNode):
        e = node.expressions[channel] \
            if 0 <= channel < len(node.expressions) else None
        if isinstance(e, E.InputReference):
            return column_source(node.source, e.channel)
        return None
    if isinstance(node, (N.FilterNode, N.SortNode, N.TopNNode, N.LimitNode,
                         N.DistinctNode, N.SampleNode, N.ExchangeNode,
                         N.OutputNode)):
        return column_source(node.sources[0], channel)
    if isinstance(node, N.JoinNode):
        nleft = len(node.left.output_types())
        if channel < nleft:
            return column_source(node.left, channel)
        rch = channel - nleft
        out = node.right_output_channels
        if out is not None:
            if 0 <= rch < len(out):
                rch = out[rch]
            else:
                return None
        return column_source(node.right, rch)
    if isinstance(node, N.SemiJoinNode):
        n_src = len(node.source.output_types())
        if channel < n_src:
            return column_source(node.source, channel)
        return None  # the appended membership mask
    if isinstance(node, N.AggregationNode):
        # group-key channels pass the source column through (so a FINAL
        # step traces through its PARTIAL's keys); state channels do not
        if 0 <= channel < len(node.group_channels):
            return column_source(node.source, node.group_channels[channel])
        return None
    if isinstance(node, (N.WindowNode, N.RowNumberNode, N.MarkDistinctNode,
                         N.AssignUniqueIdNode)):
        n_src = len(node.sources[0].output_types())
        if channel < n_src:
            return column_source(node.sources[0], channel)
        return None  # appended function outputs
    if isinstance(node, N.GroupIdNode):
        # key channels keep their source NDV bound (NULL injection adds
        # at most the nullable_slack group); the gid channel is handled
        # in estimate_distinct
        n_src = len(node.source.output_types())
        if channel < n_src:
            return column_source(node.source, channel)
        return None
    return None


def estimate_distinct(node: N.PlanNode, channel: int,
                      sf: float) -> Optional[int]:
    """Distinct-count upper bound for one output channel, from the
    originating connector's statistics."""
    if isinstance(node, N.GroupIdNode) and \
            channel == len(node.source.output_types()):
        return len(node.grouping_sets)  # the appended gid column
    src = column_source(node, channel)
    if src is None:
        return None
    connector, table, column = src
    from ..connectors import catalog
    mod = catalog(connector)
    fn = getattr(mod, "column_distinct_count", None)
    if fn is None:
        return None
    try:
        return fn(table, column, sf)
    except KeyError:
        return None


def estimate_group_bound(node: N.PlanNode, channels, sf: float,
                         nullable_slack: int = 1) -> Optional[int]:
    """Upper bound on distinct key TUPLES over `channels` (product of
    per-channel bounds, +nullable_slack per channel for a possible NULL
    group). None when any channel is unbounded."""
    bound = 1
    for ch in channels:
        ndv = estimate_distinct(node, ch, sf)
        if ndv is None:
            return None
        bound *= ndv + nullable_slack
        if bound > 1 << 30:  # stop multiplying into the void
            return None
    return bound


def refine_capacities(node: N.PlanNode, sf: float, _memo=None) -> N.PlanNode:
    """Physical-capacity pass (run at execution time, when sf is known):
    SHRINK group-table capacities to the NDV bound the connector proves.
    Small tables route group-by to the scatter-free MXU kernels
    (ops/aggregation.py _SMALL_G), which measured ~500x faster than the
    scatter path on TPU. Bounds are upper bounds, so shrinking can never
    cause overflow; capacities are never grown (a user's explicit small
    max_groups stays authoritative, and an explicit large one only
    shrinks when the connector PROVES fewer groups are possible).
    Identity-memoized so shared CTE subtrees (plan DAGs) stay shared."""
    import dataclasses as _dc

    if _memo is None:
        _memo = {}
    if id(node) in _memo:
        return _memo[id(node)]
    orig_key = id(node)

    replaced = {}
    for f in _dc.fields(node):
        v = getattr(node, f.name)
        if isinstance(v, N.PlanNode):
            nv = refine_capacities(v, sf, _memo)
            if nv is not v:
                replaced[f.name] = nv
        elif isinstance(v, list) and v and isinstance(v[0], N.PlanNode):
            nl = [refine_capacities(s, sf, _memo) for s in v]
            if any(a is not b for a, b in zip(nl, v)):
                replaced[f.name] = nl
    if replaced:
        node = _dc.replace(node, **replaced)

    if isinstance(node, N.AggregationNode) and node.group_channels:
        bound = estimate_group_bound(node.source, node.group_channels, sf)
        if bound is not None:
            cap = max(-(-bound // 8) * 8, 8)
            if cap < node.max_groups:
                node = _dc.replace(node, max_groups=cap)
    elif isinstance(node, N.DistinctNode) and node.key_channels is not None:
        bound = estimate_group_bound(node.source, node.key_channels, sf)
        if bound is not None:
            cap = max(-(-bound // 8) * 8, 8)
            if cap < node.max_groups:
                node = _dc.replace(node, max_groups=cap)
    _memo[orig_key] = node
    return node


def estimate_rows(node: N.PlanNode, sf: float) -> Optional[float]:
    """Heuristic output-row estimate, for relative cost choices only."""
    if isinstance(node, N.TableScanNode):
        from ..connectors import catalog
        try:
            return float(catalog(node.connector)
                         .table_row_count(node.table, sf))
        except Exception:  # noqa: BLE001 - unknown table
            return None
    if isinstance(node, N.ValuesNode):
        return float(len(node.rows))
    if isinstance(node, N.FilterNode):
        r = estimate_rows(node.source, sf)
        return None if r is None else r * _FILTER_SELECTIVITY
    if isinstance(node, N.SemiJoinNode):
        r = estimate_rows(node.source, sf)
        return r  # mask append; filtering happens in a FilterNode above
    if isinstance(node, N.JoinNode):
        left = estimate_rows(node.left, sf)
        right = estimate_rows(node.right, sf)
        if left is None or right is None:
            return None
        # equi-join fan-out guess: the larger side survives (the
        # PK-FK common case); outer joins keep at least the outer side
        return max(left, right)
    if isinstance(node, N.AggregationNode):
        r = estimate_rows(node.source, sf)
        bound = estimate_group_bound(node.source, node.group_channels, sf)
        if not node.group_channels:
            return 1.0
        if bound is not None and r is not None:
            return float(min(r, bound))
        return r
    if isinstance(node, N.DistinctNode):
        return estimate_rows(node.source, sf)
    if isinstance(node, (N.TopNNode, N.LimitNode)):
        r = estimate_rows(node.sources[0], sf)
        cnt = float(node.count)
        return cnt if r is None else min(r, cnt)
    if isinstance(node, N.UnionNode):
        parts = [estimate_rows(s, sf) for s in node.inputs]
        if any(p is None for p in parts):
            return None
        return sum(parts)
    if isinstance(node, N.UnnestNode):
        r = estimate_rows(node.source, sf)
        return None if r is None else r * 4.0
    if isinstance(node, N.SampleNode):
        r = estimate_rows(node.source, sf)
        return None if r is None else r * node.ratio
    if isinstance(node, N.GroupIdNode):
        r = estimate_rows(node.source, sf)
        return None if r is None else r * len(node.grouping_sets)
    if node.sources:
        return estimate_rows(node.sources[0], sf)
    return None


_MAX_GROUPS_CEILING = 1 << 23
_CAPACITY_CEILING = 1 << 24
# A fitted capacity never goes under this many rows, nor under the
# plan's own value where that is smaller still (a hand-set hint or an
# NDV-refined table of a few groups chose its kernel: the ladder never
# went under it either). Under a thousand rows an operator's cost is
# not its capacity, while every distinct capacity is a program.
_FIT_FLOOR = 1 << 10


def preorder(root: N.PlanNode) -> list:
    """The plan's nodes in structural pre-order (a shared subtree
    counts once, where it is first met). A node's place in the list is
    its pre-order index: stable across plannings of one SQL text, which
    `node.id`, a process-wide counter, is not; the device scopes
    (exec/planner.py) and the capacity feedback name a node by it."""
    seen: set = set()
    out: list = []

    def walk(n):
        if id(n) not in seen:
            seen.add(id(n))
            out.append(n)
            for s in n.sources:
                walk(s)
    walk(root)
    return out


def preorder_index(root: N.PlanNode) -> dict:
    """id(node) -> its pre-order index (`preorder`)."""
    return {id(n): k for k, n in enumerate(preorder(root))}


def _pow2_at_or_above(rows: int) -> int:
    return 1 << max(rows - 1, 0).bit_length()


def is_counted(n: N.PlanNode) -> bool:
    """Whether the program reports how many rows the node needed: a
    join's output rows, a keyed aggregation's groups (exact, overflow
    or not, from the join and the sorted group-by; the small and hash
    group kernels stop counting at their table and say four times the
    capacity when they overflow: `exec/planner.py`). Distinct,
    mark-distinct and unnest report an overflow flag alone."""
    return isinstance(n, N.JoinNode) or (
        isinstance(n, N.AggregationNode) and bool(n.group_channels))


def _capacity_field(n: N.PlanNode) -> Optional[str]:
    if is_counted(n):
        return "out_capacity" if isinstance(n, N.JoinNode) else "max_groups"
    if isinstance(n, (N.DistinctNode, N.MarkDistinctNode)):
        return "max_groups"
    if isinstance(n, N.UnnestNode) and n.out_capacity is not None:
        return "out_capacity"  # None: four times its input, whatever that is
    return None


def _ceiling(n: N.PlanNode) -> int:
    return _CAPACITY_CEILING if isinstance(n, (N.JoinNode, N.UnnestNode)) \
        else _MAX_GROUPS_CEILING


def capacities(root: N.PlanNode, default_join_capacity: int) -> dict:
    """{pre-order index: capacity} of every node that has a static
    capacity the ladder can change: joins, keyed aggregations (the
    counted nodes), distinct, mark-distinct, unnest."""
    found = {}
    for k, n in enumerate(preorder(root)):
        field = _capacity_field(n)
        if field is not None:
            found[k] = getattr(n, field) or default_join_capacity
    return found


def scaled_capacities(root: N.PlanNode, caps: dict, factor: int) -> dict:
    """`caps`, every one `factor` times as large, up to the ceilings."""
    nodes = preorder(root)
    return {k: max(min(c * factor, _ceiling(nodes[k])), c)
            for k, c in caps.items()}


def with_capacities(root: N.PlanNode, caps: dict) -> N.PlanNode:
    """Rebuild the plan with the capacity of node `k` set to `caps[k]`
    (explicit `out_capacity`, `max_groups`), preserving shared subtrees
    (CTE DAGs); `root` itself where nothing changes. Exchange slot
    capacities are excluded: slot overflow has its own (cheaper) rerun
    loop in the executor."""
    import dataclasses

    index = preorder_index(root)
    memo: dict = {}

    def walk(n: N.PlanNode) -> N.PlanNode:
        if id(n) in memo:
            return memo[id(n)]
        changes = {}
        for f in dataclasses.fields(n):
            v = getattr(n, f.name)
            if isinstance(v, N.PlanNode):
                w = walk(v)
                if w is not v:
                    changes[f.name] = w
            elif isinstance(v, list) and v and isinstance(v[0], N.PlanNode):
                w = [walk(x) for x in v]
                if any(a is not b for a, b in zip(w, v)):
                    changes[f.name] = w
        cap = caps.get(index[id(n)])
        field = _capacity_field(n)
        if cap is not None and field and getattr(n, field) != cap:
            changes[field] = cap
        out = dataclasses.replace(n, **changes) if changes else n
        memo[id(n)] = out
        return out

    return walk(root)


def fitted_capacities(base: dict, ran: dict, needs: dict) -> dict:
    """After a dispatch that fitted, every count is exact: a counted
    node's capacity is the power of two at or above its need (powers of
    two keep `ops/join._slot_rows`' block rule and the compile cache's
    key space small), not under `_FIT_FLOOR` rows or the plan's own
    capacity `base`, whichever is smaller. A node without a count keeps
    what it ran at."""
    fitted = dict(ran)
    for k, need in needs.items():
        fitted[k] = max(_pow2_at_or_above(need), min(base[k], _FIT_FLOOR))
    return fitted


def grown_capacities(root: N.PlanNode, ran: dict, needs: dict) -> dict:
    """After a dispatch that overflowed: a counted node whose need
    passed its capacity is sized from the need (the power of two at or
    above it); the nodes above such a node saw truncated input, and the
    nodes that report a flag alone may be the ones that overflowed, so
    both grow four times; a counted node below every overflow has an
    exact count that fitted and stays. Where no count passed its
    capacity (a hash table's probe budget, a distinct) every capacity
    grows four times. All held to the ceilings: a result equal to `ran`
    means nothing can grow."""
    index = preorder_index(root)
    over = {k for k, need in needs.items() if need > ran[k]}
    grown = {}
    memo: dict = {}

    def walk(n: N.PlanNode) -> bool:
        """Whether an overflow lies at or below `n`."""
        if id(n) in memo:
            return memo[id(n)]
        k = index[id(n)]
        below = False
        for s in n.sources:  # every source: no short circuit
            below = walk(s) or below
        if k in ran:
            if k in over:
                grown[k] = _pow2_at_or_above(needs[k])
            elif below or not over or k not in needs:
                grown[k] = ran[k] * 4
            else:
                grown[k] = ran[k]
            grown[k] = max(min(grown[k], _ceiling(n)), ran[k])
        memo[id(n)] = below or k in over or (k in ran and k not in needs)
        return memo[id(n)]

    walk(root)
    return grown
