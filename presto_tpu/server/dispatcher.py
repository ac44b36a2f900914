"""Dispatcher: query admission, resource-group queueing, execution.

Reference surface: dispatcher/DispatchManager.java:68 (createQuery:234
parses, picks a resource group, queues), resourceGroups'
InternalResourceGroupManager (hierarchical admission: hard concurrency
+ queue caps per group), and QueuedStatementResource's queue-then-
redirect flow.

Slice here: named resource groups with hard_concurrency_limit /
max_queued / memory gate, selected by user or source (the file-based
selector pattern); a query BLOCKS in its group's queue until a slot
frees (the reference long-polls the same wait), then runs through the
coordinator or local runner. Events fire at create/complete
(QueryCreated/QueryCompleted)."""

from __future__ import annotations

import dataclasses
import threading
import time
import uuid
from typing import Callable, Dict, List, Optional

from .. import failpoints
from ..utils.locks import OrderedLock
from .events import event_listeners

__all__ = ["ResourceGroup", "Dispatcher", "QueryRejected",
           "LATENCY_CLASSES", "latency_class_groups",
           "latency_class_selector"]


class QueryRejected(RuntimeError):
    """Admission failure: queue full or no matching group."""


@dataclasses.dataclass
class ResourceGroup:
    """InternalResourceGroup analog, now HIERARCHICAL: a query admitted
    into a leaf holds one concurrency slot (and its memory budget) in
    the leaf AND every ancestor, so parent limits cap whole subtrees
    (InternalResourceGroup.java's canRunMore chain). Admission among
    competing queued leaves under a constrained ancestor is
    weighted-fair: the eligible leaf with the LOWEST running/weight
    ratio goes first (ties FIFO), the reference's WEIGHTED_FAIR
    scheduling policy."""
    name: str
    hard_concurrency_limit: int = 4
    max_queued: int = 16
    soft_memory_limit_bytes: Optional[int] = None
    scheduling_weight: int = 1
    # admission preemption (latency classes): among capacity-eligible
    # waiters a HIGHER-priority leaf always admits first -- interactive
    # traffic preempts queued scans at the slot boundary (the
    # cooperative analog of the reference's query preemption)
    priority: int = 0

    # tpulint C001: admission state is written through WHATEVER
    # receiver walks the tree (g/root/leaf) while holding the ONE
    # per-tree condition -- _cv is a shared lock, any receiver counts
    _GUARDED_BY = {"_cv": ("_running", "_queued", "_mem_used",
                           "_ticket", "_waiters")}
    _GUARDED_BY_SHARED = ("_cv",)

    def __post_init__(self):
        self._running = 0
        self._queued = 0
        self._mem_used = 0
        self.parent: Optional["ResourceGroup"] = None
        self.children: Dict[str, "ResourceGroup"] = {}
        # one condition per TREE (the root's); shared by add_child
        # the tree's condition wraps an OrderedLock so admission waits
        # ride the runtime lock-order witness like every other lock
        # (Condition probes ownership via OrderedLock._is_owned)
        self._cv = threading.Condition(
            OrderedLock("dispatcher.ResourceGroup._cv"))
        self._waiters: List[tuple] = []  # (ticket, leaf) FIFO registry
        self._ticket = 0

    # -- tree construction -------------------------------------------------

    def add_child(self, child: "ResourceGroup") -> "ResourceGroup":
        child.parent = self
        root = self._root()
        child._cv = root._cv
        for g in child._subtree():
            g._cv = root._cv
        self.children[child.name] = child
        return child

    def _root(self) -> "ResourceGroup":
        g = self
        while g.parent is not None:
            g = g.parent
        return g

    def _subtree(self):
        yield self
        for c in self.children.values():
            yield from c._subtree()

    def _chain(self):
        g = self
        while g is not None:
            yield g
            g = g.parent

    def find(self, dotted: str) -> Optional["ResourceGroup"]:
        """Resolve "etl.nightly" relative to this group."""
        g = self
        for part in dotted.split("."):
            if part == g.name and g is self:
                continue
            nxt = g.children.get(part)
            if nxt is None:
                return None
            g = nxt
        return g

    def stats(self) -> Dict[str, int]:
        with self._cv:
            out = {"running": self._running, "queued": self._queued,
                   "hardConcurrencyLimit": self.hard_concurrency_limit,
                   "maxQueued": self.max_queued,
                   "schedulingWeight": self.scheduling_weight,
                   "priority": self.priority,
                   "memoryUsedBytes": self._mem_used}
            if self.soft_memory_limit_bytes is not None:
                out["softMemoryLimitBytes"] = self.soft_memory_limit_bytes
            return out

    # -- admission ---------------------------------------------------------

    def _capacity_now(self, mem: int) -> bool:
        for g in self._chain():
            if g._running >= g.hard_concurrency_limit:
                return False
            if g.soft_memory_limit_bytes is not None and \
                    g._mem_used + mem > g.soft_memory_limit_bytes:
                return False
        return True

    def acquire(self, timeout: Optional[float] = None, mem: int = 0):
        root = self._root()
        with self._cv:
            for g in self._chain():
                if g.soft_memory_limit_bytes is not None and \
                        mem > g.soft_memory_limit_bytes:
                    raise QueryRejected(
                        f"query memory {mem} exceeds group "
                        f"{g.name!r} limit {g.soft_memory_limit_bytes}")
                if g._queued >= g.max_queued:
                    raise QueryRejected(
                        f"resource group {g.name!r} queue is full "
                        f"({g.max_queued})")
            for g in self._chain():
                g._queued += 1
            root._ticket += 1
            me = (root._ticket, self, mem)
            root._waiters.append(me)
            deadline = None if timeout is None else time.time() + timeout

            def my_turn() -> bool:
                if not self._capacity_now(mem):
                    return False
                # priority-then-weighted-fair: among capacity-eligible
                # waiters the highest-priority leaf admits first
                # (latency-class preemption), ties by lowest
                # running/weight, then FIFO ticket
                best = None
                for tkt, leaf, wmem in root._waiters:
                    if not leaf._capacity_now(wmem):
                        continue
                    key = (-leaf.priority,
                           leaf._running / max(leaf.scheduling_weight, 1),
                           tkt)
                    if best is None or key < best[0]:
                        best = (key, tkt, leaf)
                return best is not None and best[1] == me[0]

            try:
                while not my_turn():
                    remaining = None if deadline is None \
                        else deadline - time.time()
                    if remaining is not None and remaining <= 0:
                        raise QueryRejected(
                            f"query queued in {self.name!r} longer than "
                            f"{timeout}s")
                    self._cv.wait(remaining)
            finally:
                root._waiters.remove(me)
                for g in self._chain():
                    g._queued -= 1
                # our departure (admitted OR timed out) can unblock a
                # differently-shaped waiter
                self._cv.notify_all()
            for g in self._chain():
                g._running += 1
                g._mem_used += mem

    def release(self, mem: int = 0):
        with self._cv:
            for g in self._chain():
                g._running -= 1
                g._mem_used -= mem
            # notify_all, not notify: a waiter that times out may have
            # just consumed the single notify without taking the slot,
            # which would leave another queued waiter blocked forever.
            self._cv.notify_all()


# the latency classes (admission-to-SLO): interactive point
# lookups preempt dashboard refreshes preempt batch scans. Limits are
# per-class concurrency + queue depth; the shared root caps the tree.
LATENCY_CLASSES = ("interactive", "dashboard", "batch")


def latency_class_groups(root_concurrency: int = 64,
                         root_queued: int = 1024) -> ResourceGroup:
    """The default latency-class resource-group tree: a ``global``
    root bounding total admission, with interactive/dashboard/batch
    leaves whose priority + weight implement admission preemption
    (interactive first) and whose per-class limits keep one class from
    starving the others' queues."""
    root = ResourceGroup("global",
                         hard_concurrency_limit=root_concurrency,
                         max_queued=root_queued)
    root.add_child(ResourceGroup(
        "interactive", hard_concurrency_limit=root_concurrency,
        max_queued=root_queued, scheduling_weight=8, priority=2))
    root.add_child(ResourceGroup(
        "dashboard", hard_concurrency_limit=max(root_concurrency // 2, 1),
        max_queued=max(root_queued // 2, 1), scheduling_weight=4,
        priority=1))
    root.add_child(ResourceGroup(
        "batch", hard_concurrency_limit=max(root_concurrency // 16, 1),
        max_queued=max(root_queued // 16, 1), scheduling_weight=1,
        priority=0))
    return root


def latency_class_selector(session: Dict) -> str:
    """Route on the ``latency_class`` session property: a class name
    maps under the global tree, an explicit dotted path passes
    through, absent/empty lands on the root group."""
    lc = str((session or {}).get("latency_class", "") or "")
    if lc in LATENCY_CLASSES:
        return f"global.{lc}"
    return lc or "global"


class Dispatcher:
    """DispatchManager analog: select a group, admit, execute, account.

    `executor(query_id, query)` does the actual work (the coordinator's
    execute or a local run_query closure); the dispatcher owns only
    admission and lifecycle events."""

    def __init__(self, groups: Optional[List[ResourceGroup]] = None,
                 selector: Optional[Callable[[Dict], str]] = None,
                 resource_manager_url: Optional[str] = None,
                 coordinator_id: Optional[str] = None,
                 cluster_limits: Optional[Dict[str, int]] = None):
        """`resource_manager_url` + `cluster_limits` ({group path:
        cluster-wide hard concurrency}) enforce limits ACROSS
        coordinators: admission consults the resource manager's
        aggregated view and waits while other coordinators hold the
        cluster's slots (resourcemanager/ multi-coordinator
        arbitration)."""
        # register every group in each tree under its dotted path, so
        # selectors can target leaves ("etl.nightly") or roots ("etl")
        self.groups: Dict[str, ResourceGroup] = {}
        for root in (groups or [ResourceGroup("global")]):
            self._register(root, root.name)
        self._selector = selector or (lambda session: "global")
        self.resource_manager_url = resource_manager_url
        self.coordinator_id = coordinator_id or f"coord-{id(self):x}"
        self.cluster_limits = dict(cluster_limits or {})

    @classmethod
    def with_latency_classes(cls, root_concurrency: int = 64,
                             root_queued: int = 1024,
                             **kwargs) -> "Dispatcher":
        """A dispatcher admitting through the latency-class tree
        (interactive/dashboard/batch under one global root), routed by
        the ``latency_class`` session property -- the admission-to-SLO
        configuration scripts/loadgen.py drives."""
        return cls(groups=[latency_class_groups(root_concurrency,
                                                root_queued)],
                   selector=latency_class_selector, **kwargs)

    def _register(self, g: ResourceGroup, path: str):
        self.groups[path] = g
        self.groups.setdefault(g.name, g)
        for c in g.children.values():
            self._register(c, f"{path}.{c.name}")

    def select_group(self, session: Optional[Dict] = None) -> str:
        """The group path the selector routes this session to (public:
        the statement tier records it per query for system.queries)."""
        return self._selector(session or {})

    def _await_cluster_slot(self, group_name: str, group: ResourceGroup,
                            deadline: Optional[float]) -> None:
        """Cluster-wide admission gate: while OTHER coordinators'
        running queries leave no room under a cluster limit configured
        on the selected group OR ANY ANCESTOR path (local admission
        enforces the whole chain; so does this gate), wait (bounded
        poll; the reference long-polls the RM the same way). RM
        unreachable = fail open to local-only admission (availability
        over global strictness, the reference's degraded mode)."""
        if self.resource_manager_url is None:
            return
        parts = group_name.split(".")
        gates = []
        for i in range(len(parts)):
            prefix = ".".join(parts[:i + 1])
            limit = self.cluster_limits.get(prefix)
            if limit is not None and prefix in self.groups:
                gates.append((prefix, limit, self.groups[prefix]))
        if not gates:
            return
        from .resource_manager import remote_group_load
        while True:
            try:
                blocked = None
                for prefix, limit, g in gates:
                    remote = remote_group_load(self.resource_manager_url,
                                               prefix,
                                               self.coordinator_id)
                    if remote + g.stats()["running"] >= limit:
                        blocked = (prefix, limit)
                        break
            except Exception as e:  # noqa: BLE001 - RM down: degrade
                # to local-only admission, but count it -- a flapping
                # RM silently disabling cluster limits is an outage
                from .metrics import record_suppressed
                record_suppressed("dispatcher", "rm_gate", e)
                return
            if blocked is None:
                return
            if deadline is not None and time.time() >= deadline:
                raise QueryRejected(
                    f"cluster limit {blocked[1]} for group "
                    f"{blocked[0]!r} held by other coordinators")
            time.sleep(0.05)

    def group_stats(self) -> Dict[str, Dict[str, int]]:
        return {name: g.stats() for name, g in self.groups.items()
                if "." in name or not g.parent}

    def submit(self, executor: Callable[[str], object],
               session: Optional[Dict] = None,
               query_text: str = "",
               queue_timeout: Optional[float] = None,
               query_id: Optional[str] = None):
        """Admit + run one query synchronously (the reference's async
        dispatch is its HTTP shell; the admission semantics live here).
        Raises QueryRejected when the group's queue is full. The caller
        may supply the query id (the statement resource mints ids at
        POST time, before admission, like QueuedStatementResource)."""
        session = session or {}
        group_name = self._selector(session)
        group = self.groups.get(group_name)
        if group is None:
            raise QueryRejected(f"no resource group {group_name!r}")
        query_id = query_id or f"q-{uuid.uuid4().hex[:12]}"
        events = event_listeners()
        events.query_created(query_id, query_text,
                             session.get("user", ""))
        if failpoints.ARMED:
            # delay = a stalled dispatch ahead of the resource-group
            # queue, error = failed admission (the query fails cleanly
            # before holding any slot)
            failpoints.hit("dispatcher.admit")
        mem = 0
        if "query_max_memory" in session:
            from ..utils.config import parse_size
            mem = parse_size(session["query_max_memory"])
        # ONE admission deadline covers the cluster gate AND the local
        # queue wait (the caller's bound, not 2x it)
        deadline = None if queue_timeout is None \
            else time.time() + queue_timeout
        t_queue0 = time.time()
        try:
            self._await_cluster_slot(group_name, group, deadline)
            remaining = None if deadline is None \
                else max(deadline - time.time(), 0.001)
            group.acquire(remaining, mem=mem)
        finally:
            # queue-wait distribution (previously timed by NOBODY): the
            # cluster gate + local slot wait, rejected waits included --
            # a full queue's p99 is exactly the signal this exists for.
            # Labeled by resource group so loadgen p99s are
            # attributable per latency class.
            from .metrics import observe_histogram
            observe_histogram("presto_tpu_dispatch_queue_wait_seconds",
                              time.time() - t_queue0,
                              labels={"group": group_name})
        t0 = time.time()
        try:
            result = executor(query_id)
        except Exception as e:
            events.query_completed(query_id, "FAILED",
                                   wall_s=time.time() - t0, error=str(e))
            raise
        finally:
            group.release(mem=mem)
        rows = getattr(result, "row_count", 0)
        events.query_completed(query_id, "FINISHED", rows=rows,
                               wall_s=time.time() - t0)
        return result
