"""Pipeline-region fusion compiler: plan -> regions, each ONE program.

Reference surface: Flare's whole-stage native compilation (one
generated pipeline per stage instead of one operator at a time) and
SystemML's cost-based operator-fusion-plan selection -- choose WHAT to
fuse and WHERE to materialize from measured costs, not heuristics.

A *pipeline region* is a maximal chain of plan operators staged as one
XLA program: scan -> filter -> project -> partial-agg bodies, and the
exchange-adjacent final-agg -> project -> limit/sort tails, fuse into
single jitted executables; region boundaries are materialized Batch
handoffs in HBM (no host round trip). With fusion ON (the default) a
whole local fragment is normally ONE region -- exactly the fused
whole-fragment program the engine has always staged, now as the
1-region special case of the general executor. The partitioner splits
a would-be region only for CAUSE:

  * **footprint refusal** -- a fusion whose estimated peak intermediate
    exceeds ``kernel_audit_budget_bytes`` is rejected: the static
    estimate (row estimates x output widths, the planner-side
    approximation of kernaudit K005's liveness walk) gates at
    partition time, and the REAL K005 estimate -- fed back per region
    fingerprint whenever the staging-time auditor runs -- overrides
    the estimate on the next submission of the same region.
  * **demotion** -- a region whose fused per-dispatch device
    time regresses beyond the perfgate noise band vs the recorded
    materialized (per-operator) execution of the same span is demoted
    back to materialized boundaries. Both sides of the comparison are
    the runner's host-timed dispatch walls folded into
    :class:`FusionMemory`; the band math is exec/perfgate.py's --
    the ONE regression comparator this repo allows.
  * **fusion off** -- ``fusion`` session property / ``PRESTO_TPU_FUSION=0``
    (registered in KERNEL_MODE_ENVS) runs one region per operator: the
    A/B + bisection mode, and the baseline the demotion contract
    compares against.

Seam invariants (the partition law tests pin): region boundaries sit
EXACTLY at the engine's materialization seams and never inside them --

  * scan/values/remote-source leaves are region INPUTS, never regions;
  * a meshed (SPMD) plan is always one region: its REMOTE exchanges
    lower to collectives gang-scheduled inside one shard_map program,
    and splitting would materialize exchange state host-side
    (parallel/stages.py keeps its contract);
  * the streaming/spill executors (exec/streaming.py, exec/spill.py)
    take over BEFORE region partitioning -- their split-by-split
    programs are their own pipeline form;
  * write/DDL roots re-enter run_query for their inner SELECT, which
    is where partitioning happens.

Region identity: each region's root is a standalone plan tree (cut
children replaced by RemoteSourceNode leaves), so its plan-cache
fingerprint derives from the ORIGINAL plan's structure restricted to
the region span -- a single-region plan keeps the existing whole-plan
fingerprint unchanged, which is what keeps the plan cache and the
kernaudit memo keyed exactly as before this refactor.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Dict, List, Optional

from ..plan import nodes as N
from ..utils.locks import OrderedLock
from .perfgate import MetricSpec, compare

__all__ = ["FUSION_ENV", "fusion_enabled", "RegionInput", "PipelineRegion",
           "RegionPlan", "partition_regions", "fusion_memory",
           "FusionMemory", "estimate_node_bytes"]

FUSION_ENV = "PRESTO_TPU_FUSION"

_LEAF_TYPES = (N.TableScanNode, N.ValuesNode, N.RemoteSourceNode)


def fusion_enabled(session) -> bool:
    """Session property ``fusion``; process default from
    PRESTO_TPU_FUSION (default ON). Spelled literally so tpulint R001
    proves the knob is registered in KERNEL_MODE_ENVS."""
    import os
    env_on = os.environ.get("PRESTO_TPU_FUSION", "1") \
        not in ("0", "", "false")
    from ..utils.config import session_flag
    return session_flag(session, "fusion", env_on)


@dataclasses.dataclass
class RegionInput:
    """One positional input of a region's compiled program, in the
    planner's scan-collection (DFS preorder, identity-deduped) order.
    ``kind="scan"``: `node` is the ORIGINAL plan leaf (stage its batch
    once, by identity). ``kind="region"``: the batch is the output of
    `region` (an upstream PipelineRegion index)."""
    kind: str
    node: Optional[N.PlanNode] = None
    region: int = -1


@dataclasses.dataclass
class PipelineRegion:
    """One fused chain, lowered to ONE program by exec/planner.py."""
    index: int
    root: N.PlanNode           # standalone subtree (cuts = RemoteSource)
    inputs: List[RegionInput]  # positional, planner scan order
    span: str                  # node-chain label (provenance surfaces)
    ops: int                   # fused operator count (non-leaf nodes)
    reason: str                # why this region ends where it does
    est_peak_bytes: int        # static intermediate-footprint estimate

    @property
    def tag(self) -> str:
        return f"R{self.index}"


@dataclasses.dataclass
class RegionPlan:
    root: N.PlanNode
    regions: List[PipelineRegion]   # topological: producers first
    node_region: Dict[int, int]     # id(original node) -> region index
    fused: bool                     # fusion was in force


# ---------------------------------------------------------------------------
# cost model inputs
# ---------------------------------------------------------------------------


def _row_width_bytes(types) -> int:
    """Bytes per row of a node's output at the declared (logical)
    widths + the active/null lanes -- the same shape arithmetic as
    runner._planned_scan_bytes."""
    per_row = 1  # active mask
    for ty in types:
        if ty.is_string:
            per_row += (ty.max_length if ty.max_length < 1 << 20 else 64) + 5
        elif ty.is_decimal and not ty.is_short_decimal:
            per_row += 17  # int128 lanes: hi + lo + null
        else:
            try:
                per_row += ty.to_dtype().itemsize + 1
            except Exception:  # noqa: BLE001 - exotic logical type
                per_row += 9
    return per_row


def estimate_node_bytes(node: N.PlanNode, sf: float) -> int:
    """Static estimate of one operator's materialized output: the
    optimizer row estimate x logical row width. This is the
    partition-time stand-in for kernaudit K005's liveness-walk peak --
    conservative (block capacities pad upward, narrowed lanes shrink
    real bytes) and cheap (no tracing)."""
    from ..plan.stats import estimate_rows
    rows = None
    try:
        rows = estimate_rows(node, sf)
    except Exception:  # noqa: BLE001 - estimates are best-effort
        rows = None
    if rows is None:
        for s in node.sources:
            try:
                child = estimate_rows(s, sf)
            except Exception:  # noqa: BLE001
                child = None
            if child is not None:
                rows = max(rows or 0.0, child)
    if rows is None:
        rows = 1024.0
    try:
        width = _row_width_bytes(node.output_types())
    except Exception:  # noqa: BLE001 - INTERMEDIATE agg state types etc.
        width = 64
    return int(rows) * width


# ---------------------------------------------------------------------------
# fusion memory: measured costs per region fingerprint
# ---------------------------------------------------------------------------


class FusionMemory:
    """Process-wide feedback store for fusion-plan choice.

    Keyed by region fingerprint (exec/plan_cache.plan_fingerprint of
    the region root -- the same identity the executable cache and
    the kernaudit memo use):

      * ``note_footprint``: kernaudit K005's measured peak-intermediate
        estimate (max over audits); the partitioner prefers it over the
        static estimate when refusing over-budget fusions.
      * ``note_fused`` / ``note_unfused``: per-dispatch device-time
        samples of the FUSED region vs the MATERIALIZED (per-operator)
        execution of the same span (the runner feeds both; the unfused
        side keys on the fingerprint the span WOULD fuse to, so the
        pair compares like for like).
      * ``maybe_demote``: perfgate-band comparison -- a warmed fused
        median regressing beyond the band vs the warmed unfused median
        demotes the fingerprint; demoted regions partition with
        materialized boundaries until the process restarts or
        ``clear()`` (tests, plan-cache clears).

    Bounded maps + bounded sample windows; lock-guarded (the runner's
    hot path appends one sample per dispatch)."""

    _WINDOW = 16
    _MAX_KEYS = 512
    # tpulint C001: the runner's hot path appends samples from every
    # dispatch thread; the partitioner reads across them
    _GUARDED_BY = {"_lock": ("_footprint", "_fused", "_unfused",
                             "_demoted")}
    # device time regresses upward; a fused region must beat its
    # materialized form by more than noise + 10% before demotion is
    # even considered, and micro-kernels under 200us never demote
    # (dispatch jitter dominates them)
    SPEC = MetricSpec("region_device_us", higher_is_worse=True,
                      rel_threshold=0.10, abs_floor=200.0, mad_k=5.0)
    MIN_SAMPLES = 3

    def __init__(self):
        self._lock = OrderedLock("regions.FusionMemory._lock")
        self._footprint: "collections.OrderedDict[str, int]" = \
            collections.OrderedDict()
        self._fused: "collections.OrderedDict[str, collections.deque]" = \
            collections.OrderedDict()
        self._unfused: "collections.OrderedDict[str, collections.deque]" = \
            collections.OrderedDict()
        self._demoted: Dict[str, str] = {}

    def _bump(self, table, key, value) -> None:
        q = table.get(key)
        if q is None:
            q = table[key] = collections.deque(maxlen=self._WINDOW)
            while len(table) > self._MAX_KEYS:
                table.popitem(last=False)
        else:
            table.move_to_end(key)
        q.append(float(value))

    def note_footprint(self, fingerprint: str, peak_bytes: int) -> None:
        with self._lock:
            have = self._footprint.get(fingerprint, 0)
            self._footprint[fingerprint] = max(have, int(peak_bytes))
            self._footprint.move_to_end(fingerprint)
            while len(self._footprint) > self._MAX_KEYS:
                self._footprint.popitem(last=False)

    def footprint(self, fingerprint: str) -> int:
        with self._lock:
            return self._footprint.get(fingerprint, 0)

    def note_fused(self, fingerprint: str, device_us: int) -> None:
        with self._lock:
            self._bump(self._fused, fingerprint, device_us)

    def note_unfused(self, fingerprint: str, device_us: int) -> None:
        with self._lock:
            self._bump(self._unfused, fingerprint, device_us)

    def demoted(self, fingerprint: str) -> Optional[str]:
        with self._lock:
            return self._demoted.get(fingerprint)

    def demote(self, fingerprint: str, reason: str) -> None:
        with self._lock:
            self._demoted[fingerprint] = reason
            while len(self._demoted) > self._MAX_KEYS:
                self._demoted.pop(next(iter(self._demoted)))

    def maybe_demote(self, fingerprint: str) -> Optional[dict]:
        """Compare the fused region's device-time samples against the
        materialized baseline; on a band breach, demote and return the
        verdict (None otherwise). Pure perfgate math -- no clocks."""
        with self._lock:
            if fingerprint in self._demoted:
                return None
            fused = list(self._fused.get(fingerprint) or ())
            base = list(self._unfused.get(fingerprint) or ())
        if len(fused) < self.MIN_SAMPLES or len(base) < self.MIN_SAMPLES:
            return None
        from .perfgate import median
        verdict = compare(median(fused), base, self.SPEC)
        if verdict is None:
            return None
        self.demote(fingerprint, f"device_us {verdict['value']:.0f} vs "
                                 f"materialized median {verdict['median']:.0f}"
                                 f" (band {verdict['band']:.0f})")
        return verdict

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "demoted": dict(self._demoted),
                "footprints": dict(self._footprint),
                "fused_keys": len(self._fused),
                "unfused_keys": len(self._unfused),
            }

    def clear(self) -> None:
        with self._lock:
            self._footprint.clear()
            self._fused.clear()
            self._unfused.clear()
            self._demoted.clear()


def estimate_region_bytes(region: "PipelineRegion",
                          sf: float = 0.01) -> int:
    """Static peak estimate of a carved region, computed on demand
    (partitioning only pays the estimate walk when a budget is set;
    EXPLAIN's region tail asks lazily)."""
    if region.est_peak_bytes:
        return region.est_peak_bytes
    total = 0

    def walk(n):
        nonlocal total
        if not isinstance(n, _LEAF_TYPES):
            total += estimate_node_bytes(n, sf)
        for s in n.sources:
            walk(s)

    walk(region.root)
    return total


_MEMORY = FusionMemory()


def fusion_memory() -> FusionMemory:
    return _MEMORY


# ---------------------------------------------------------------------------
# partitioning
# ---------------------------------------------------------------------------


def _span_label(root) -> str:
    """A region's `span` text: the node-type chain in DFS preorder with
    scan tables inlined, capped."""
    parts: List[str] = []

    def walk(n):
        if len(parts) > 24:
            return
        name = type(n).__name__.replace("Node", "")
        table = getattr(n, "table", None)
        conn = getattr(n, "connector", None)
        if table and conn:
            name += f"[{conn}.{table}]"
        step = getattr(n, "step", None)
        if step and name.startswith("Aggregation"):
            name += f"({step})"
        parts.append(name)
        for s in getattr(n, "sources", ()):
            walk(s)

    walk(root)
    return " > ".join(parts)[:120]


def _audit_budget(session) -> int:
    from ..audit.staged import _budget
    return _budget(session)


def partition_regions(root: N.PlanNode, *, session=None, sf: float = 0.01,
                      mesh=None, force_per_op: bool = False) -> RegionPlan:
    """Partition a PREPARED plan into pipeline regions (see module
    docstring for the grammar). Deterministic for a given (plan,
    session, kernel mode, FusionMemory state)."""
    fused = fusion_enabled(session) and not force_per_op
    single = mesh is not None       # SPMD programs stay whole
    per_op = not fused and not single
    budget = _audit_budget(session) if not single else 0

    regions: List[PipelineRegion] = []
    node_region: Dict[int, int] = {}
    carved: Dict[int, int] = {}     # id(original subtree root) -> region
    est_memo: Dict[int, int] = {}

    def est(n: N.PlanNode) -> int:
        if id(n) not in est_memo:
            est_memo[id(n)] = estimate_node_bytes(n, sf)
        return est_memo[id(n)]

    def fp_of(region_root: N.PlanNode) -> str:
        from .plan_cache import plan_fingerprint
        return plan_fingerprint(region_root)

    def carve(n: N.PlanNode, materialize_root: bool = False,
              cause: str = "") -> int:
        """Carve the region producing `n`'s output; returns its index.
        `materialize_root=True` re-carves a demoted/refused span: `n`
        runs alone (`cause` says why) and its children re-enter fusion
        independently."""
        if id(n) in carved and not materialize_root:
            return carved[id(n)]

        nodes: List[N.PlanNode] = []
        inputs: List[RegionInput] = []
        seen_leaves: Dict[int, None] = {}
        est_sum = [0]
        reasons: List[str] = []

        def absorb(parent: N.PlanNode, m: N.PlanNode) -> bool:
            """Whether child chain `m` fuses into `parent`'s region."""
            if single:
                return True
            if isinstance(parent, N.OutputNode):
                # Output is a pure rename -- never a region of its own
                return True
            if isinstance(m, N.ExchangeNode):
                # a single-chip ExchangeNode lowers to a no-op: it is
                # transparent (rides with its consumer) and ITS child
                # decides the real cut on the next absorb call
                return True
            if materialize_root or per_op:
                return False
            if budget > 0 and est_sum[0] + est(m) > budget:
                reasons.append("budget")
                return False
            return True

        def rebuild(m: N.PlanNode) -> N.PlanNode:
            nodes.append(m)
            node_region[id(m)] = len(regions)  # provisional; fixed below
            if budget > 0:  # estimates are only consulted by the
                est_sum[0] += est(m)  # budget rule; skip the walk otherwise
            new_sources: List[N.PlanNode] = []
            changed = False
            for c in m.sources:
                if isinstance(c, _LEAF_TYPES):
                    if id(c) not in seen_leaves:
                        seen_leaves[id(c)] = None
                        inputs.append(RegionInput("scan", node=c))
                    new_sources.append(c)
                    continue
                if id(c) in rebuilt:
                    new_sources.append(rebuilt[id(c)])
                    changed = changed or rebuilt[id(c)] is not c
                    continue
                if absorb(m, c):
                    rc = rebuild(c)
                    rebuilt[id(c)] = rc
                    new_sources.append(rc)
                    changed = changed or rc is not c
                    continue
                # cut: the child chain becomes its own (upstream) region
                # and this region reads its materialized batch
                src_region = carve(c)
                leaf = N.RemoteSourceNode(types=c.output_types())
                rebuilt[id(c)] = leaf
                inputs.append(RegionInput("region", region=src_region))
                new_sources.append(leaf)
                changed = True
            if not changed:
                return m
            from ..plan.rules import _replace_sources
            return _replace_sources(m, new_sources)

        rebuilt: Dict[int, N.PlanNode] = {}
        region_root = rebuild(n)

        # demotion check: a fused multi-op region whose fingerprint
        # FusionMemory has proven regressive re-carves materialized
        if fused and not single and not materialize_root and len(nodes) > 1:
            region_fp = fp_of(region_root)
            why = _MEMORY.demoted(region_fp)
            if why is None and budget > 0:
                # kernaudit K005 feedback: the measured peak of this
                # exact program overrides the static estimate
                if _MEMORY.footprint(region_fp) > budget:
                    why = "footprint"
            if why is not None:
                return carve(n, materialize_root=True,
                             cause=("footprint" if why == "footprint"
                                    else "demoted"))

        idx = len(regions)
        for m in nodes:
            node_region[id(m)] = idx
        reason = ("mesh" if single else
                  (cause or "materialized")
                  if (per_op or materialize_root) else
                  "+".join(sorted(set(reasons))) or "fused")
        regions.append(PipelineRegion(
            index=idx, root=region_root, inputs=inputs,
            span=_span_label(region_root), ops=len(nodes),
            reason=reason, est_peak_bytes=est_sum[0]))
        carved[id(n)] = idx
        return idx

    carve(root)
    return RegionPlan(root=root, regions=regions,
                      node_region=node_region, fused=fused)
