"""Worker control/data plane: the TaskResource / TaskManager analog.

Reference surface: the worker REST API contract
(presto-docs/.../develop/worker-protocol.rst; Java TaskResource.java:79
createOrUpdate:118 status-long-poll:182 results:283 acknowledge:244;
C++ presto_cpp/main/TaskResource.cpp + TaskManager.cpp:506) and the
discovery announcer (presto_cpp/main/Announcer.cpp).

Endpoints (coordinator-facing contract):
  GET    /v1/info                     server info (node id, state, uptime)
  GET    /v1/status                   node status (memory, tasks)
  POST   /v1/task/{taskId}            create/update: body carries the plan
                                      JSON + scan config (TaskUpdateRequest
                                      analog); idempotent
  GET    /v1/task/{taskId}            TaskInfo JSON (state, stats)
  GET    /v1/task/{taskId}/results/{bufferId}/{token}
                                      SerializedPage bytes; token/ack pull
                                      protocol with X-Presto-Page-* headers
  GET    /v1/task/{taskId}/results/{bufferId}/{token}/acknowledge
  DELETE /v1/task/{taskId}            abort

Execution runs on a background thread per task (the TPU device stream
serializes actual kernels); results buffer as SerializedPages with
monotonically increasing tokens, deleted on ack -- the same
at-least-once pull contract the reference's ExchangeClient speaks.

This is the Python control-plane shell; the reference keeps its shell in
C++ for RPC-throughput reasons and a C++ port of this module is planned
once the protocol stabilizes (SURVEY.md §2.3).
"""

from __future__ import annotations

import json
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional

import numpy as np

from .. import failpoints
from ..plan import nodes as N
from ..serde import PageCodec, serialize_page
from ..utils.config import Session
from ..utils.locks import OrderedLock
from .buffers import SpoolingOutputBuffer

__all__ = ["TpuWorkerServer", "TaskManager"]


def _hash_partition_rows(res, channels: List[int], nparts: int):
    """Destination partition per result row, using the engine's row hash
    (expr.functions splitmix64) so routing matches on-device exchanges.
    Returns a list of index arrays, one per partition."""
    import numpy as np

    from .. import types as _T
    from ..block import batch_from_numpy
    from ..expr.functions import combine_hash, hash64_block

    n = res.row_count
    if n == 0:
        return [np.array([], dtype=np.int64)] * nparts
    tys = [res.types[c] if res.types else _T.BIGINT for c in channels]
    key_batch = batch_from_numpy(tys, [res.columns[c] for c in channels],
                                 [res.nulls[c] for c in channels])
    h = None
    for i in range(len(channels)):
        hc = hash64_block(key_batch.column(i))
        h = hc if h is None else combine_hash(h, hc)
    dest = np.asarray(h % np.uint64(nparts)).astype(np.int64)
    return [np.nonzero(dest == p)[0] for p in range(nparts)]


class _GoneError(Exception):
    """Requested pages were acked away by a prior consumer (HTTP 410)."""


class _MovedError(Exception):
    """The task's buffered pages migrated to a peer during graceful
    drain; str(self) is the adopting worker's base url. The HTTP layer
    answers with an ``X-Presto-Task-Moved`` header and the consumer
    (WorkerClient.fetch_results) resumes its token stream against the
    peer -- tokens are absolute and the acked prefix migrated with the
    pages, so the replay is exactly-once by construction."""


class FragmentResultCache:
    """Leaf-fragment output cache (FileFragmentResultCacheManager
    analog): serialized result pages keyed by (canonical plan
    fingerprint, sf, scan ranges, output partitioning, connector data
    versions). Deterministic generator scans key on sf alone; memory
    tables key on their mutation counters; parquet on file mtimes;
    volatile catalogs (system) are uncacheable. Bounded LRU by bytes."""

    # write-barrier contract, enforced statically (tpulint C001)
    _GUARDED_BY = {"_lock": ("_entries", "_bytes", "hits", "misses")}

    def __init__(self, max_bytes: int = 256 << 20):
        import collections
        self.max_bytes = max_bytes
        self._entries = collections.OrderedDict()
        self._bytes = 0
        self._lock = OrderedLock("worker.FragmentResultCache._lock")
        self.hits = 0
        self.misses = 0

    @staticmethod
    def key_of(plan: N.PlanNode, sf: float, scan_ranges: dict,
               out_part, compression) -> Optional[tuple]:
        """None = not cacheable."""
        scans: List[N.TableScanNode] = []

        def walk(n):
            if isinstance(n, (N.RemoteSourceNode, N.TableWriterNode,
                              N.TableFinishNode, N.TableRewriteNode,
                              N.DdlNode)):
                # remote inputs aren't pure; writes/DDL are SIDE EFFECTS
                # a replayed page must never skip
                scans.append(None)
            if isinstance(n, N.TableScanNode):
                scans.append(n)
            for s in n.sources:
                walk(s)
        walk(plan)
        versions = []
        for s in scans:
            if s is None:
                return None
            # connector-level seam: a catalog is cacheable iff it
            # exposes data_version(table) (system & unknown catalogs
            # don't -- volatile by default)
            from ..connectors import catalog as _catalog
            try:
                fn = getattr(_catalog(s.connector), "data_version", None)
                if fn is None:
                    return None
                versions.append((s.connector, s.table, fn(s.table)))
            except KeyError:
                return None  # table/catalog vanished: don't cache
        from ..exec.plan_cache import plan_fingerprint
        return (plan_fingerprint(plan), sf,
                tuple(sorted((k, tuple(v)) for k, v in scan_ranges.items())),
                repr(out_part), compression, tuple(versions))

    def get(self, key) -> Optional[dict]:
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return e

    def put(self, key, buffers: Dict[int, List[bytes]], rows: int,
            stats: Dict[str, float]) -> None:
        size = sum(len(p) for pages in buffers.values() for p in pages)
        if size > self.max_bytes:
            return
        with self._lock:
            if key in self._entries:
                return
            self._entries[key] = {"buffers": {k: list(v) for k, v
                                              in buffers.items()},
                                  "rows": rows, "stats": dict(stats),
                                  "bytes": size}
            self._bytes += size
            while self._bytes > self.max_bytes and self._entries:
                _k, old = self._entries.popitem(last=False)
                self._bytes -= old["bytes"]


class _Task:
    # every field the HTTP threads and the execution thread share is
    # written under the task lock (tpulint C001 enforces this, module-
    # wide: TaskManager's writes through `task.` are checked too)
    _GUARDED_BY = {"lock": ("state", "error", "buffers", "first_token",
                            "no_more_pages", "stats", "finished_at",
                            "spans", "moved_to")}

    def __init__(self, task_id: str, spool_threshold: int = 64 << 20,
                 spool_dir: Optional[str] = None,
                 session_stuck_ms=None):
        self.task_id = task_id
        self.state = "PLANNED"  # PLANNED -> RUNNING -> FINISHED/FAILED/ABORTED
        self.error: Optional[str] = None
        self._spool_threshold = spool_threshold
        self._spool_dir = spool_dir
        # the task body session's stuck_query_threshold_ms (None =
        # resolve the PRESTO_TPU_STUCK_MS env at watchdog scan time)
        self.session_stuck_ms = session_stuck_ms
        # partition-addressed output buffers (OutputBufferId -> pages);
        # unpartitioned results live in buffer 0. Pages past the memory
        # budget spool to disk (SpoolingOutputBuffer.java analog)
        self.buffers: Dict[int, SpoolingOutputBuffer] = {
            0: self._new_buffer()}
        self.first_token: Dict[int, int] = {}  # per-buffer acked prefix
        self.no_more_pages = False
        # base url of the peer this task's pages migrated to during a
        # graceful drain (None = pages are local); once set, result
        # pulls redirect and local acks are no-ops
        self.moved_to: Optional[str] = None
        self.created_at = time.time()
        self.finished_at: Optional[float] = None
        self.stats: Dict[str, float] = {}
        # the task's local span docs, set once at terminal state: they
        # ship to the coordinator piggybacked on the final task status
        # (the distributed-trace stitch transport)
        self.spans: List[dict] = []
        self.lock = OrderedLock("worker._Task.lock")

    def _new_buffer(self) -> SpoolingOutputBuffer:
        return SpoolingOutputBuffer(self._spool_threshold, self._spool_dir)

    def info(self) -> dict:
        # live progress rides every TaskInfo poll: the coordinator's
        # status loop folds it back into its own registry, so the
        # statement tier sees cross-worker heartbeats without a second
        # protocol (registry lock nests inside the task lock and never
        # takes it back -- no cycle)
        from ..exec.progress import get_progress
        ent = get_progress(self.task_id)
        with self.lock:
            doc = {
                "taskId": self.task_id,
                "state": self.state,
                "error": self.error,
                "bufferedPages": sum(len(p) for p in self.buffers.values()),
                "spooledBytes": sum(b.spooled_bytes
                                    for b in self.buffers.values()),
                "noMorePages": self.no_more_pages,
                "stats": dict(self.stats),
                "elapsedSeconds": round(time.time() - self.created_at, 3),
            }
            if self.moved_to is not None:
                doc["movedTo"] = self.moved_to
            if ent is not None:
                doc["progress"] = ent.snapshot()
            if self.spans:
                # populated only at terminal state, so in-flight status
                # polls stay small and the final poll carries the spans
                doc["spans"] = list(self.spans)
            return doc


class TaskManager:
    """createOrUpdateTask / result-buffer bookkeeping (TaskManager.cpp:506
    analog). Execution admits through a bounded slot pool
    (`task_concurrency` concurrent plans, the TaskExecutor analog of
    execution/executor/TaskExecutor.java:87): a long task occupies one
    slot while short tasks proceed through the others, and HBM admission
    stays with the shared MemoryPool each run_query reserves from. XLA
    serializes actual device streams; overlapping tasks still overlap
    their host-side staging, serde, and compile phases, which dominate
    short-task latency."""

    # `draining`/`drained` ride the tasks lock: create_or_update reads
    # them under _tasks_lock to make the refuse-new-tasks decision
    # atomic with task creation (write paths: drain(), mark_drained())
    _GUARDED_BY = {"_tasks_lock": ("tasks", "draining", "drained"),
                   "_counters_lock": ("counters",)}

    def __init__(self, sf: float = 0.01, mesh=None,
                 memory_bytes: int = 12 << 30,
                 task_ttl_s: float = 600.0,
                 task_concurrency: int = 4,
                 output_spool_threshold_bytes: int = 64 << 20,
                 output_spool_dir: Optional[str] = None):
        from ..exec.memory import MemoryPool
        self.sf = sf
        self.mesh = mesh
        self.tasks: Dict[str, _Task] = {}
        # concurrent tasks contend for HBM admission: waits (bounded)
        # beat failing a query that fit fine under serial execution
        self.memory_pool = MemoryPool(memory_bytes,
                                      admission_timeout_s=60.0)
        self.draining = False  # GracefulShutdownHandler state
        self.drained = False   # drain complete: pages replayed/migrated
        self.task_ttl_s = task_ttl_s
        self.task_concurrency = max(1, int(task_concurrency))
        self.output_spool_threshold_bytes = output_spool_threshold_bytes
        self.output_spool_dir = output_spool_dir
        self._exec_slots = threading.BoundedSemaphore(self.task_concurrency)
        self._tasks_lock = OrderedLock("worker.TaskManager._tasks_lock")
        self.fragment_cache = FragmentResultCache()
        from ..connectors.system import register_task_manager
        register_task_manager(self)  # system.tasks introspection
        # lifetime counters for /v1/metrics (Prometheus)
        self.counters: Dict[str, int] = {"tasks_created": 0,
                                         "tasks_finished": 0,
                                         "tasks_failed": 0,
                                         "tasks_aborted": 0,
                                         "tasks_adopted": 0,
                                         "pages_migrated": 0,
                                         "rows_produced": 0,
                                         "exchange_bytes": 0,
                                         "compile_us": 0,
                                         "execute_us": 0}
        self._counters_lock = OrderedLock("worker.TaskManager._counters_lock")

    def _count(self, name: str, delta: int = 1):
        with self._counters_lock:
            self.counters[name] = self.counters.get(name, 0) + delta

    def drain(self) -> None:
        """Enter SHUTTING_DOWN (GracefulShutdownHandler): stop accepting
        NEW tasks, let running ones finish. Under the tasks lock so the
        flag flip is atomic with in-flight create_or_update decisions."""
        with self._tasks_lock:
            self.draining = True

    def mark_drained(self) -> None:
        """Drain complete: every buffered page was replayed or migrated
        (TpuWorkerServer._drain's terminal step)."""
        with self._tasks_lock:
            self.draining = True
            self.drained = True

    @property
    def drain_state(self) -> str:
        """ACTIVE | DRAINING | DRAINED -- the fleet state /v1/status,
        /v1/cluster and ptop render (the legacy flat `state` key keeps
        its SHUTTING_DOWN spelling for older pollers)."""
        with self._tasks_lock:
            if self.drained:
                return "DRAINED"
            return "DRAINING" if self.draining else "ACTIVE"

    def unreplayed_pages(self) -> int:
        """Buffered result pages still owned by THIS worker (migrated
        tasks excluded): the quantity graceful drain must bring to zero
        before the node unannounces."""
        with self._tasks_lock:
            tasks = list(self.tasks.values())
        total = 0
        for t in tasks:
            with t.lock:
                if t.moved_to is None:
                    total += sum(len(b) for b in t.buffers.values())
        return total

    def migrate_buffers(self, peer_url: str, timeout: float = 30.0,
                        secret: Optional[str] = None) -> int:
        """Migrate every finished task's remaining buffered pages to
        `peer_url` (SpoolingOutputBuffer tail included); returns pages
        moved. The export + moved_to flip happen under the task lock in
        ONE critical section, so no consumer can ack a local page after
        its copy shipped (the duplicate-replay hazard); a failed POST
        rolls the flip back and the pages stay served locally -- drain
        degrades to waiting, never loses or doubles a page."""
        from .client import WorkerClient
        from .flight_recorder import record_event
        from .metrics import record_suppressed
        with self._tasks_lock:
            tasks = list(self.tasks.values())
        # the migration hop is an internal hop like any other: it must
        # carry the cluster secret or secured peers 401 every adopt
        client = WorkerClient(peer_url, timeout=timeout,
                              shared_secret=secret)
        moved = 0
        for task in tasks:
            with task.lock:
                if task.moved_to is not None or task.state != "FINISHED" \
                        or not task.no_more_pages:
                    continue
                npages = sum(len(b) for b in task.buffers.values())
                if npages == 0:
                    continue
                doc = {
                    "state": task.state,
                    "noMorePages": True,
                    "stats": dict(task.stats),
                    "firstToken": {str(b): task.first_token.get(b, 0)
                                   for b in task.buffers},
                    "buffers": {str(b): buf.export_pages()
                                for b, buf in task.buffers.items()},
                }
                # optimistic flip: consumers redirect from here on (the
                # peer's adopt races them by at most one short retry)
                task.moved_to = peer_url.rstrip("/")
            try:
                client.migrate(task.task_id, doc)
            except Exception as e:  # noqa: BLE001 - peer refused/died
                record_suppressed("worker", "migrate_task", e)
                # a timed-out POST may still have LANDED: rolling back
                # while the peer serves the adopted copy would let two
                # nodes serve the same pages. Probe before deciding --
                # only a confirmed-absent adopt rolls the flip back
                # (keep serving locally); a confirmed/ambiguous adopt
                # stays moved (consumers redirect, worst case they wait
                # out the adopt exactly like the in-flight window).
                adopted = False
                try:
                    adopted = client.task_info(task.task_id) is not None
                except Exception as pe:  # noqa: BLE001 - 404 or dead
                    # peer: no adopted copy is reachable -> roll back
                    record_suppressed("worker", "migrate_probe", pe)
                if not adopted:
                    with task.lock:
                        task.moved_to = None
                    continue
            with task.lock:
                for b in task.buffers.values():
                    b.clear()
                task.buffers = {}
            moved += npages
            self._count("pages_migrated", npages)
            record_event("buffer_migrate", query_id=task.task_id,
                         pages=npages, to=peer_url)
        return moved

    def adopt_task(self, task_id: str, doc: dict) -> dict:
        """Adopt a draining peer's finished task: restore its buffered
        pages (at their original absolute token offsets) so redirected
        consumers resume their pull streams here. Idempotent; refused
        while this worker is itself draining (like new tasks)."""
        from .flight_recorder import record_event
        with self._tasks_lock:
            self._prune_locked()
            task = self.tasks.get(task_id)
            if task is None:
                if self.draining:
                    raise RuntimeError(
                        "worker is SHUTTING_DOWN: not adopting tasks")
                task = _Task(task_id, self.output_spool_threshold_bytes,
                             self.output_spool_dir)
                self.tasks[task_id] = task
                adopted = True
            else:
                adopted = False
        if not adopted:
            return task.info()
        # restore (and possibly re-spool to disk) OUTSIDE the task
        # lock: only this thread adopts (the `adopted` flag is flipped
        # under _tasks_lock), and a consumer that races the attach sees
        # the same fresh-empty state it could already see between task
        # creation and the old in-lock restore -- its 404-retry covers
        # the window. Holding task.lock across file I/O stalled every
        # /v1/task status poll behind a slow disk (tpulint C003).
        total = 0
        buffers: Dict[int, SpoolingOutputBuffer] = {}
        for bid, pages in (doc.get("buffers") or {}).items():
            buf = task._new_buffer()
            total += buf.restore_pages(pages)
            buffers[int(bid)] = buf
        with task.lock:
            task.buffers = buffers or {0: task._new_buffer()}
            task.first_token = {int(b): int(t) for b, t in
                                (doc.get("firstToken") or {}).items()}
            task.no_more_pages = bool(doc.get("noMorePages", True))
            task.stats = dict(doc.get("stats") or {})
            task.state = str(doc.get("state", "FINISHED"))
            task.finished_at = time.time()
        # already accounted (finished) by the origin worker: only the
        # adoption itself counts
        task._accounted = True
        self._count("tasks_adopted")
        record_event("task_adopt", query_id=task_id, bytes=total)
        return task.info()

    def _prune_locked(self):
        """Drop terminal tasks (and their buffered pages) older than the
        TTL -- coordinators DELETE tasks after consumption, this is the
        backstop against leaked ones growing worker memory forever. Runs
        opportunistically on task lookups AND submissions so an idle-but-
        polled worker also reclaims."""
        cutoff = time.time() - self.task_ttl_s
        for tid in [tid for tid, t in self.tasks.items()
                    if t.finished_at is not None and t.finished_at < cutoff]:
            del self.tasks[tid]

    def create_or_update(self, task_id: str, body: dict) -> dict:
        with self._tasks_lock:
            self._prune_locked()
            task = self.tasks.get(task_id)
            if task is None:
                # drain refuses only NEW tasks; idempotent re-POSTs of
                # running tasks still succeed (create-or-UPDATE contract)
                if self.draining:
                    raise RuntimeError(
                        "worker is SHUTTING_DOWN: not accepting tasks")
                sess = body.get("session") \
                    if isinstance(body.get("session"), dict) else {}
                task = _Task(task_id, self.output_spool_threshold_bytes,
                             self.output_spool_dir,
                             session_stuck_ms=(sess or {}).get(
                                 "stuck_query_threshold_ms"))
                self.tasks[task_id] = task
                self._count("tasks_created")
                threading.Thread(target=self._run, args=(task, body),
                                 daemon=True).start()
        return task.info()

    def active_task_count(self) -> int:
        with self._tasks_lock:
            self._prune_locked()
            return sum(1 for t in self.tasks.values()
                       if t.state in ("PLANNED", "RUNNING"))

    def _stuck_candidates(self):
        """RUNNING tasks offered to the stuck-progress watchdog
        (server/watchdog.py): threshold from the task body's session
        (env fallback resolved at scan time, so a live env flip takes
        effect for already-running tasks), last advance from the live
        progress entry (falling back to task creation -- a task wedged
        before the runner registered anything is exactly the case the
        detector exists for)."""
        from ..exec.progress import get_progress
        from .watchdog import StuckCandidate, resolve_stuck_threshold_ms
        with self._tasks_lock:
            tasks = list(self.tasks.values())
        out = []
        for t in tasks:
            with t.lock:
                state = t.state
            if state != "RUNNING":
                continue
            sess = None if t.session_stuck_ms is None else \
                {"stuck_query_threshold_ms": t.session_stuck_ms}
            thr = resolve_stuck_threshold_ms(sess)
            if thr <= 0:
                continue
            ent = get_progress(t.task_id)
            snap = ent.snapshot() if ent is not None else None
            out.append(StuckCandidate(
                t.task_id, thr,
                snap["lastAdvanceTsUs"] / 1e6 if snap else t.created_at,
                trace_id=snap["query"] if snap else None,
                extra={"stage": snap["stage"] if snap else "start"}))
        return out

    def _run(self, task: _Task, body: dict):
        try:
            # per-task failpoint schedule (the `failpoints` session
            # property): armed for this task's whole scope -- remote
            # fetch, serde, execution -- and restored afterwards
            spec = (body.get("session") or {}).get("failpoints") \
                if isinstance(body.get("session"), dict) else None
            with failpoints.session_scope(spec):
                self._run_inner(task, body)
        finally:
            # every exit path accounts the task exactly once; the
            # mid-execution ABORT early-returns land here uncounted
            if not getattr(task, "_accounted", False):
                task._accounted = True
                with task.lock:
                    state = task.state
                if state == "ABORTED":
                    self._count("tasks_aborted")
                    from .events import event_listeners
                    event_listeners().task_completed(task.task_id,
                                                     "ABORTED")

    def _run_inner(self, task: _Task, body: dict):
        """Trace plumbing around one task execution: parse the
        propagated context (body ``traceparent``, with the legacy
        ``traceId`` as fallback trace grouping), run the task with a
        thread-local SpanBuffer + ambient context installed (so stage
        spans AND outbound exchange fetches carry the trace), then emit
        the task span and pin every locally recorded span onto the task
        for the final-status piggyback the coordinator stitches."""
        from .flight_recorder import get_flight_recorder
        from .tracing import (TraceContext, emit_span, new_span_id,
                              parse_traceparent, span_buffer,
                              trace_context)
        ctx = parse_traceparent(body.get("traceparent"))
        trace_id = (ctx.trace_id if ctx else None) or \
            body.get("traceId") or task.task_id
        task_ctx = TraceContext(trace_id, new_span_id())
        t_start = time.time()
        with span_buffer() as buf, trace_context(task_ctx):
            try:
                self._run_task(task, body, task_ctx)
            finally:
                # the task state machine (not the runner) owns task
                # finality: force the progress entry terminal so a
                # crashed/aborted task never lingers "RUNNING" on the
                # live surfaces
                from ..exec.progress import finish_task
                with task.lock:
                    state = task.state
                    tstats = dict(task.stats)
                finish_task(task.task_id, state)
                emit_span(trace_id, f"task.{task.task_id}",
                          t_start, time.time(),
                          {"state": state,
                           "rows": tstats.get("outputRows", 0),
                           "bytes": tstats.get("outputBytes", 0)},
                          span_id=task_ctx.span_id,
                          parent_id=ctx.span_id if ctx else None)
                # task-lifetime distribution (/v1/metrics histogram),
                # exemplar'd with the propagated trace id
                from .metrics import observe_histogram
                observe_histogram("presto_tpu_task_seconds",
                                  time.time() - t_start,
                                  trace_id=trace_id)
        with task.lock:
            task.spans = buf.spans
        if state == "FAILED":
            # task-tier flight dump: the worker's view of a failed task
            # (the coordinator separately dumps per query)
            get_flight_recorder().maybe_dump(task.task_id, "failed")

    def _run_task(self, task: _Task, body: dict, task_ctx):
        from .flight_recorder import record_event
        try:
            with task.lock:
                task.state = "RUNNING"
            record_event("task_state", query_id=task.task_id,
                         state="RUNNING")
            # progress heartbeat entry registered BEFORE any failpoint/
            # staging work: a task wedged right here (the `hang` site
            # below) is still visible -- with a stalling last-advance
            # age -- to status polls and the stuck-progress watchdog
            from ..exec.progress import begin as progress_begin
            progress_begin(task.task_id, kind="task",
                           query=task_ctx.trace_id)
            if failpoints.ARMED:
                # error = crash mid-task (-> FAILED -> coordinator
                # resubmit); hang/delay = wedged or slow worker
                failpoints.hit("worker.run_task")
            plan = N.from_json(body["plan"])
            session = Session(body.get("session", {}))
            if not session.get("tpu_execution_enabled"):
                raise RuntimeError(
                    "tpu_execution_enabled=false: fragment refused by the "
                    "TPU worker (route to a row-engine cluster)")
            sf = float(body.get("sf", self.sf))
            codec = PageCodec(
                compression=(session.get("exchange_compression")
                             if session.get("exchange_compression") != "none"
                             else None))
            scan_ranges = {k: tuple(v) for k, v in
                           body.get("scanRanges", {}).items()}
            remote_sources = {}
            pad = (self.mesh.devices.size if self.mesh is not None else 1) * 8
            exchange_unpack_s = 0.0
            exchange_in_rows = 0
            for node_id, spec in body.get("remoteSources", {}).items():
                # pull upstream pages peer-to-peer (PrestoExchangeSource);
                # the pull + page decode is the host-visible exchange
                # *unpack* boundary -- timed into the task's QueryStats
                from ..types import parse_type
                from .http_exchange import fetch_remote_batch
                from .tracing import emit_span
                t_ex0 = time.time()
                remote_sources[node_id] = fetch_remote_batch(
                    spec["sources"], spec["taskIds"],
                    [parse_type(t) for t in spec["types"]],
                    pad_multiple=pad,
                    buffer_id=int(spec.get("bufferId", 0)),
                    ack=bool(spec.get("ack", True)),
                    merge_keys=spec.get("mergeKeys"),
                    timeout=float(spec.get("timeoutS", 60.0)))
                exchange_unpack_s += time.time() - t_ex0
                rows_in = int(
                    np.asarray(remote_sources[node_id].active).sum())
                exchange_in_rows += rows_in
                # the pull+decode is a real hop on the query's critical
                # path: one child span per remote source under the task
                emit_span(task_ctx.trace_id, "exchange.fetch",
                          t_ex0, time.time(),
                          {"node": node_id, "rows": rows_in,
                           "upstreams": len(spec.get("taskIds", []))},
                          parent_id=task_ctx.span_id)
            from ..exec.runner import run_query
            # fragment result cache: identical leaf fragments (same
            # canonical plan, splits, data versions) replay their
            # serialized pages without touching the chip
            from ..utils.config import session_flag
            cache_on = session_flag(session, "fragment_result_cache", True)
            ckey = None
            if cache_on and not body.get("remoteSources"):
                ckey = FragmentResultCache.key_of(
                    plan, sf, scan_ranges, body.get("outputPartitions"),
                    session.get("exchange_compression"))
            if ckey is not None:
                hit = self.fragment_cache.get(ckey)
                record_event("fragment_cache",
                             query_id=task.task_id,
                             hit=hit is not None)
                if hit is not None:
                    # a replay produced rows without touching the chip:
                    # re-shipping the ORIGINAL run's compile/execute
                    # micros would attribute device time to a query
                    # that did none -- keep rows/bytes, mark the replay
                    replay_stats = {k: v for k, v in hit["stats"].items()
                                    if k != "queryStats"}
                    orig_qs = hit["stats"].get("queryStats") or {}
                    replay_stats["queryStats"] = {
                        "wallUs": 0,
                        "outputRows": int(orig_qs.get("outputRows", 0)),
                        "outputBytes": int(orig_qs.get("outputBytes", 0)),
                        "taskCount": 1,
                        "counters": {"fragment_cache_replay": 1}}
                    with task.lock:
                        if task.state == "ABORTED":
                            return
                        for pid, pages in hit["buffers"].items():
                            task.buffers.setdefault(
                                pid, task._new_buffer()).extend(pages)
                        task.no_more_pages = True
                        task.stats = {**replay_stats,
                                      "fragmentCacheHit": 1}
                        task.state = "FINISHED"
                        task.finished_at = time.time()
                    task._accounted = True
                    self._count("tasks_finished")
                    self._count("rows_produced", hit["rows"])
                    record_event("task_state", query_id=task.task_id,
                                 state="FINISHED", cache_replay=True)
                    from .events import event_listeners
                    event_listeners().task_completed(task.task_id,
                                                     "FINISHED",
                                                     hit["rows"])
                    return
            t0 = time.time()
            with self._exec_slots:
                # trace context: the coordinator propagates one trace
                # per query; stage spans parent under THIS task's span
                res = run_query(plan, sf=sf, mesh=self.mesh,
                                scan_ranges=scan_ranges,
                                remote_sources=remote_sources,
                                memory_pool=self.memory_pool,
                                query_id=task.task_id,
                                session=session,
                                trace_id=task_ctx)
            wall = time.time() - t0
            with task.lock:
                if task.state == "ABORTED":
                    return  # abandoned by the coordinator: drop results
            types = plan.output_types()
            out_part = body.get("outputPartitions")
            total_bytes = 0
            built: Dict[int, List[bytes]] = {}
            t_pack0 = time.time()
            if out_part:
                # PartitionedOutputBuffer analog: rows hash to one page
                # per destination partition (same hash as the engine's
                # exchanges -> consistent routing across tiers).
                # Serialize OUTSIDE the lock: status polls keep flowing.
                nparts = int(out_part["count"])
                channels = list(out_part["channels"])
                parts = _hash_partition_rows(res, channels, nparts)
                pages = []
                for pid in range(nparts):
                    sel = parts[pid]
                    cols = [(types[i], res.columns[i][sel],
                             res.nulls[i][sel])
                            for i in range(len(res.columns))]
                    page = serialize_page(cols, codec)
                    total_bytes += len(page)
                    pages.append(page)
                with task.lock:
                    if task.state == "ABORTED":
                        return
                    for pid, page in enumerate(pages):
                        task.buffers.setdefault(
                            pid, task._new_buffer()).append(page)
                built = {pid: [page] for pid, page in enumerate(pages)}
            else:
                cols = [(types[i], res.columns[i], res.nulls[i])
                        for i in range(len(res.columns))]
                page = serialize_page(cols, codec)
                total_bytes = len(page)
                with task.lock:
                    if task.state == "ABORTED":
                        return
                    task.buffers[0].append(page)
                built = {0: [page]}
            pack_s = time.time() - t_pack0
            # exchange boundaries are host-visible on the HTTP tier:
            # fold the pack (serialize) and unpack (remote pull) sides
            # into the task's structured stats before they ship to the
            # coordinator via the task status path
            qs = getattr(res, "query_stats", None)
            if qs is not None:
                from ..exec.stats import StageStats
                ex = StageStats("exchange",
                                wall_us=int((pack_s + exchange_unpack_s)
                                            * 1e6),
                                invocations=1 + len(remote_sources),
                                rows=exchange_in_rows,
                                bytes=total_bytes)
                qs.stages["exchange"] = ex.merge(qs.stages["exchange"]) \
                    if "exchange" in qs.stages else ex
                qs.output_bytes = max(qs.output_bytes, total_bytes)
            with task.lock:
                if task.state == "ABORTED":
                    return
                task.no_more_pages = True
                task.stats = {"wallSeconds": round(wall, 4),
                              "outputRows": res.row_count,
                              "outputBytes": total_bytes}
                if qs is not None:
                    task.stats["queryStats"] = qs.to_json()
                task.state = "FINISHED"
                task.finished_at = time.time()
            task._accounted = True
            self._count("tasks_finished")
            self._count("rows_produced", res.row_count)
            self._count("exchange_bytes", total_bytes)
            if qs is not None:
                self._count("compile_us", qs.compile_us)
                self._count("execute_us", qs.stage_us("execute"))
            record_event("task_state", query_id=task.task_id,
                         state="FINISHED", rows=res.row_count)
            # (the task span itself is emitted by _run_inner's wrapper,
            # parented under the coordinator's propagated span)
            if ckey is not None:
                self.fragment_cache.put(ckey, built, res.row_count,
                                        task.stats)
            from .events import event_listeners
            event_listeners().task_completed(task.task_id, "FINISHED",
                                             res.row_count)
        except Exception as e:  # noqa: BLE001 - task failure is data
            with task.lock:
                aborted = task.state == "ABORTED"
                if not aborted:
                    task.state = "FAILED"
                    task.error = f"{type(e).__name__}: {e}"
                task.finished_at = time.time()
            # a failure AFTER coordinator abort is a routine cancellation,
            # not a task failure -- count/report what the status says
            task._accounted = True
            self._count("tasks_aborted" if aborted else "tasks_failed")
            record_event("task_state", query_id=task.task_id,
                         state="ABORTED" if aborted else "FAILED",
                         error=None if aborted else
                         f"{type(e).__name__}: {e}")
            from .events import event_listeners
            event_listeners().task_completed(
                task.task_id, "ABORTED" if aborted else "FAILED")

    def get(self, task_id: str) -> Optional[_Task]:
        with self._tasks_lock:
            return self.tasks.get(task_id)

    def results(self, task_id: str, token: int, buffer_id: int = 0):
        """-> (page_bytes|None, next_token, complete). Tokens are absolute
        per buffer; acked pages are dropped but their tokens remain
        consumed. Unknown task ids raise (the HTTP layer 404s, matching
        the task-info endpoint, so a typo'd id is distinguishable from an
        empty result)."""
        task = self.get(task_id)
        if task is None:
            raise KeyError(task_id)
        with task.lock:
            if task.moved_to is not None:
                # pages migrated during graceful drain: point the
                # consumer at the adopting peer (same absolute tokens)
                raise _MovedError(task.moved_to)
            pages = task.buffers.get(buffer_id)
            npages = 0 if pages is None else len(pages)
            first = task.first_token.get(buffer_id, 0)
            if token < first:
                # a prior consumer attempt acked past this token and the
                # pages are gone; surface it (HTTP 410) so a retried
                # consumer fails fast instead of polling forever
                raise _GoneError(
                    f"token {token} below acked prefix {first} of "
                    f"{task_id}/{buffer_id}")
            idx = token - first
            if idx < npages:
                return pages.get(idx), token + 1, False
            done = task.no_more_pages or task.state in ("FAILED", "ABORTED")
            return None, token, done and idx >= npages

    def acknowledge(self, task_id: str, token: int, buffer_id: int = 0):
        task = self.get(task_id)
        if task is None:
            return
        with task.lock:
            if task.moved_to is not None:
                return  # pages live at the peer now; acks land there
            first = task.first_token.get(buffer_id, 0)
            drop = token - first
            pages = task.buffers.get(buffer_id)
            if drop > 0 and pages is not None:
                pages.drop_prefix(drop)
                task.first_token[buffer_id] = token

    def abort(self, task_id: str):
        task = self.get(task_id)
        if task is not None:
            with task.lock:
                if task.state not in ("FINISHED", "FAILED"):
                    task.state = "ABORTED"
                    from .flight_recorder import record_event
                    record_event("task_state", query_id=task_id,
                                 state="ABORTED")
                for b in task.buffers.values():
                    b.clear()
                task.buffers = {0: task._new_buffer()}
                task.first_token = {}
                if task.finished_at is None:
                    task.finished_at = time.time()


class _Handler(BaseHTTPRequestHandler):
    server_version = "presto-tpu/0.1"
    protocol_version = "HTTP/1.1"

    # injected by TpuWorkerServer
    manager: TaskManager = None
    node_id: str = ""
    started_at: float = 0.0
    authenticator = None  # InternalAuthenticator when a secret is set
    worker_server = None  # the owning TpuWorkerServer (drain endpoints)

    def log_message(self, fmt, *args):  # quiet
        pass

    def _authorized(self) -> bool:
        """InternalAuthenticationFilter analog: with a cluster secret
        configured, every endpoint requires a valid internal bearer."""
        from .auth import authorize_request
        return authorize_request(self, self.authenticator, self._send_json)

    def _send_json(self, obj, code=200):
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _send_bytes(self, body: bytes, headers: Dict[str, str], code=200):
        self.send_response(code)
        if "Content-Type" not in headers:
            self.send_header("Content-Type", "application/x-presto-pages")
        for k, v in headers.items():
            self.send_header(k, v)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _failpoint_gate(self, site: str) -> bool:
        """Evaluate a server-side failpoint; False when this request was
        already answered (injected error -> 500) or deliberately severed
        (drop_conn -> socket closed without a response, the shape a
        crashed peer leaves behind)."""
        from .metrics import record_suppressed
        try:
            failpoints.hit(site)
        except failpoints.InjectedConnDrop:
            self.close_connection = True
            try:
                self.connection.close()
            except Exception as e:  # noqa: BLE001 - already severing
                record_suppressed("worker", "failpoint_drop", e)
            return False
        except Exception as e:  # noqa: BLE001 - injected server error
            self._send_json({"error": f"failpoint {site}: "
                                      f"{type(e).__name__}: {e}"}, 500)
            return False
        return True

    def _metric_families(self):
        """Worker-side metric families (shared emitter: metrics.py)."""
        from .metrics import (MetricFamily as MF, narrowing_families,
                              plan_cache_families, uptime_family)
        m = self.manager
        fams = [
            MF("presto_tpu_active_tasks", "gauge",
               "tasks in PLANNED/RUNNING state").add(m.active_task_count()),
            MF("presto_tpu_memory_reserved_bytes", "gauge",
               "admission pool reserved").add(m.memory_pool.reserved_bytes),
            MF("presto_tpu_memory_capacity_bytes", "gauge",
               "admission pool capacity").add(m.memory_pool.capacity),
            MF("presto_tpu_memory_revoked_bytes", "gauge",
               "bytes freed by spill revocation").add(
                   m.memory_pool.revoked_bytes),
            MF("presto_tpu_memory_peak_bytes", "gauge",
               "admission pool high-water mark").add(
                   m.memory_pool.peak_bytes),
            uptime_family(self.started_at, "worker"),
            MF("presto_tpu_fragment_cache_hits_total", "counter",
               "fragment result cache hits").add(m.fragment_cache.hits),
            MF("presto_tpu_fragment_cache_misses_total", "counter",
               "fragment result cache misses").add(m.fragment_cache.misses),
        ]
        with m._counters_lock:
            counters = dict(m.counters)
        for k in sorted(counters):
            if k in ("compile_us", "execute_us"):
                # export in seconds, matching the coordinator's
                # *_seconds_total families (one unit across tiers)
                fams.append(MF(
                    f"presto_tpu_{k[:-3]}_seconds_total", "counter",
                    f"lifetime task {k[:-3]} time").add(
                        counters[k] / 1e6))
                continue
            fams.append(MF(f"presto_tpu_{k}_total", "counter",
                           f"lifetime {k}").add(counters[k]))
        fams.extend(plan_cache_families())
        fams.extend(narrowing_families())
        from .metrics import (accuracy_families, batching_families,
                              datapath_families)
        fams.extend(batching_families())
        fams.extend(datapath_families())
        fams.extend(accuracy_families())
        from .metrics import (donation_families, failpoint_families,
                              flight_recorder_families,
                              histogram_families, kernel_audit_families,
                              suppressed_error_families,
                              tracing_families)
        fams.extend(suppressed_error_families())
        fams.extend(tracing_families())
        fams.extend(flight_recorder_families())
        fams.extend(kernel_audit_families())
        fams.extend(donation_families())
        fams.extend(failpoint_families())
        from .metrics import lock_families
        fams.extend(lock_families())
        from .metrics import (fleet_families,
                              live_introspection_families,
                              query_history_families)
        fams.extend(query_history_families())
        # a worker's "alive" view is itself (the statement tier reports
        # its probed fleet count through the same builder); its
        # draining gauge is its own drain state
        fams.extend(live_introspection_families(workers_alive=1))
        # DRAINED is not DRAINING: once the drain completes the gauge
        # drops back to zero (matching the statement tier's count)
        fams.extend(fleet_families(
            workers_draining=1 if m.drain_state == "DRAINING" else 0))
        fams.extend(histogram_families())
        return fams

    def do_GET(self):  # noqa: N802
        if not self._authorized():
            return
        parts = [p for p in self.path.split("/") if p]
        if parts == ["v1", "info"]:
            return self._send_json({
                "nodeId": self.node_id, "nodeVersion": {"version": "0.1"},
                "environment": "tpu", "coordinator": False,
                "uptime": round(time.time() - self.started_at, 1),
                "state": "ACTIVE"})
        if parts in (["v1", "metrics"], ["v1", "info", "metrics"]):
            # Prometheus text format (PrometheusStatsReporter.cpp /
            # PrestoServer.cpp:562 registerHttpEndpoints analog);
            # /v1/info/metrics is the legacy alias. Exemplars render
            # only under negotiated OpenMetrics (classic 0.0.4 scrapers
            # reject the suffix).
            from .metrics import negotiate_exposition, render_prometheus
            om, ctype = negotiate_exposition(self.headers.get("Accept"))
            body = render_prometheus(self._metric_families(),
                                     openmetrics=om)
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if parts == ["v1", "datapath"]:
            # this worker's per-hop data-path slice (the statement
            # tier pulls + merges these cluster-wide, same path shape;
            # exec/datapath.py)
            from ..exec.datapath import datapath_doc
            return self._send_json(datapath_doc())
        if parts == ["v1", "accuracy"]:
            # this worker's estimate-accuracy slice (the statement
            # tier pulls + stitches per-query records cluster-wide;
            # exec/accuracy.py)
            from ..exec.accuracy import accuracy_doc
            return self._send_json(accuracy_doc())
        if parts == ["v1", "history"]:
            # this process's completed-query archive slice (the
            # statement tier merges these cluster-wide like /v1/datapath;
            # server/history.py)
            from .history import get_history_archive
            return self._send_json(get_history_archive().history_doc())
        if parts == ["v1", "failpoint"]:
            # live fault-injection admin surface (failpoints/): armed
            # table + lifetime hit counters + the site catalog
            return self._send_json(failpoints.admin_get_doc())
        if len(parts) == 3 and parts[:2] == ["v1", "trace"]:
            # worker-local slice of a distributed trace (the coordinator
            # serves the stitched whole; this answers "what did THIS
            # node record" when a stitch looks incomplete)
            from .tracing import get_tracer, trace_doc_of
            doc = trace_doc_of(get_tracer(), parts[2])
            return self._send_json(
                doc if doc else {"error": f"no trace {parts[2]}"},
                200 if doc else 404)
        if parts == ["v1", "worker", "drain"]:
            # live drain progress (state machine + unreplayed pages)
            return self._send_json(self.worker_server.drain_status())
        if parts == ["v1", "status"]:
            # enriched NodeStatus (the /v1/cluster fleet overview's
            # per-worker row): uptime, engine version, running tasks,
            # memory-pool occupancy. The legacy flat memory keys stay
            # for older pollers.
            m = self.manager
            pool = m.memory_pool
            return self._send_json({
                "nodeId": self.node_id,
                "nodeVersion": {"version": "presto-tpu-0.4"},
                "activeTasks": m.active_task_count(),
                "runningTasks": m.active_task_count(),
                "uptimeSeconds": round(time.time() - self.started_at, 1),
                "state": ("SHUTTING_DOWN" if m.draining
                          else "ACTIVE"),
                # the elastic-fleet state machine (/v1/cluster + ptop
                # render this; the flat `state` keeps its legacy
                # SHUTTING_DOWN spelling for older pollers)
                "fleetState": m.drain_state,
                "unreplayedPages": m.unreplayed_pages(),
                "memory": {"reservedBytes": pool.reserved_bytes,
                           "capacityBytes": pool.capacity,
                           "peakBytes": pool.peak_bytes,
                           "revokedBytes": pool.revoked_bytes},
                "memoryReservedBytes": pool.reserved_bytes,
                "memoryCapacityBytes": pool.capacity})
        if len(parts) == 3 and parts[:2] == ["v1", "task"]:
            tid, _, query = parts[2].partition("?")
            task = self.manager.get(tid)
            if task is None:
                return self._send_json({"error": "no such task"}, 404)
            if "format=spec" in query:
                # spec-shaped TaskInfo (main/tests/data/TaskInfo.json)
                from .protocol import task_info_json
                tstats = task.stats if isinstance(
                    getattr(task, "stats", None), dict) else {}
                return self._send_json(task_info_json(
                    tid, task.state, f"http://{self.node_id}",
                    self.node_id, int(time.time() * 1000),
                    rows=tstats.get("outputRows", 0),
                    query_stats=tstats.get("queryStats")))
            return self._send_json(task.info())
        if len(parts) == 4 and parts[:2] == ["v1", "task"] and \
                parts[3] == "status":
            # spec-shaped TaskStatus long-poll target (TaskResource
            # status:182 analog; the reference coordinator polls this)
            task = self.manager.get(parts[2])
            if task is None:
                return self._send_json({"error": "no such task"}, 404)
            from .protocol import task_status_json
            doc = task_status_json(
                parts[2], task.state, f"http://{self.node_id}",
                failures=[task.error] if getattr(task, "error", None)
                else None)
            if "application/x-thrift" in self.headers.get("Accept", ""):
                # the reference's optional thrift transport for the hot
                # status poll (ThriftTaskClient; JSON parse dominates at
                # cluster scale)
                from ..serde.thrift import encode_task_status
                return self._send_bytes(
                    encode_task_status(doc, parts[2]),
                    {"Content-Type": "application/x-thrift"})
            return self._send_json(doc)
        if len(parts) == 7 and parts[:2] == ["v1", "task"] and \
                parts[3] == "results" and parts[6] == "acknowledge":
            self.manager.acknowledge(parts[2], int(parts[5]), int(parts[4]))
            return self._send_json({"acknowledged": True})
        if len(parts) == 6 and parts[:2] == ["v1", "task"] and parts[3] == "results":
            if failpoints.ARMED and not self._failpoint_gate(
                    "exchange.serve"):
                return
            task_id, buffer_id, token = parts[2], int(parts[4]), int(parts[5])
            try:
                page, next_token, complete = self.manager.results(
                    task_id, token, buffer_id)
            except KeyError:
                return self._send_json({"error": f"no such task {task_id}"}, 404)
            except _GoneError as e:
                return self._send_json({"error": str(e)}, 410)
            except _MovedError as e:
                # drained-away pages: the consumer resumes its token
                # stream against the adopting peer (client.fetch_results
                # follows this header transparently)
                return self._send_bytes(b"", {
                    "X-Presto-Task-Instance-Id": task_id,
                    "X-Presto-Task-Moved": str(e),
                    "X-Presto-Page-Token": str(token),
                    "X-Presto-Page-Next-Token": str(token),
                    "X-Presto-Buffer-Complete": "false"})
            task = self.manager.get(task_id)
            if task is not None and task.state == "FAILED":
                return self._send_json({"error": task.error}, 500)
            headers = {
                "X-Presto-Task-Instance-Id": task_id,
                "X-Presto-Page-Token": str(token),
                "X-Presto-Page-Next-Token": str(next_token),
                "X-Presto-Buffer-Complete": str(complete).lower(),
            }
            return self._send_bytes(page or b"", headers)
        return self._send_json({"error": f"unknown path {self.path}"}, 404)

    def do_POST(self):  # noqa: N802
        if not self._authorized():
            return
        parts = [p for p in self.path.split("/") if p]
        if parts == ["v1", "failpoint"]:
            # arm a site ({site, spec}) or a whole schedule ({config})
            # on a RUNNING worker -- the chaos driver's live flip
            length = int(self.headers.get("Content-Length", "0"))
            body = json.loads(self.rfile.read(length) or b"{}")
            doc, code = failpoints.admin_post(body)
            return self._send_json(doc, code)
        if parts == ["v1", "worker", "drain"]:
            # graceful drain: refuse new tasks, finish running ones,
            # migrate remaining buffered pages ({"migrateTo": url}),
            # unannounce when empty (GracefulShutdownHandler, grown up)
            length = int(self.headers.get("Content-Length", "0"))
            body = json.loads(self.rfile.read(length) or b"{}")
            timeout_ms = body.get("timeoutMs")
            return self._send_json(self.worker_server.begin_drain(
                migrate_to=body.get("migrateTo"),
                timeout_s=(float(timeout_ms) / 1000.0
                           if timeout_ms is not None else None)))
        if len(parts) == 4 and parts[:2] == ["v1", "task"] and \
                parts[3] == "migrate":
            # adopt a draining peer's finished task (buffered pages at
            # their original token offsets)
            length = int(self.headers.get("Content-Length", "0"))
            body = json.loads(self.rfile.read(length) or b"{}")
            try:
                return self._send_json(
                    self.manager.adopt_task(parts[2], body))
            except RuntimeError as e:  # this worker is draining too
                return self._send_json({"error": str(e)}, 503)
        if len(parts) == 3 and parts[:2] == ["v1", "task"]:
            length = int(self.headers.get("Content-Length", "0"))
            body = json.loads(self.rfile.read(length) or b"{}")
            from .tracing import TRACE_HEADER
            hdr = self.headers.get(TRACE_HEADER)
            if hdr and "traceparent" not in body:
                # header-propagated context (a reference coordinator or
                # proxy that cannot amend the body still stitches)
                body["traceparent"] = hdr
            if "outputIds" in body or "extraCredentials" in body:
                # a REFERENCE-protocol TaskUpdateRequest (the document a
                # Presto coordinator POSTs): translate its PlanFragment
                # into the engine vocabulary; unsupported constructs are
                # rejected with the PlanChecker contract (400 + reason)
                from ..plan import nodes as _N
                from ..plan.validator import validate_plan
                from .protocol import (ProtocolUnsupported,
                                       parse_task_update_request)
                try:
                    parsed = parse_task_update_request(body)
                except (ProtocolUnsupported, KeyError, TypeError) as e:
                    # malformed documents (missing fields, unresolved
                    # variables) reject with the same contract as
                    # out-of-slice constructs
                    return self._send_json(
                        {"error": f"plan not executable: "
                                  f"{type(e).__name__}: {e}",
                         "retriable": False}, 400)
                if parsed["plan"] is None:
                    return self._send_json(
                        {"error": "TaskUpdateRequest without fragment"}, 400)
                violations = validate_plan(parsed["plan"])
                if violations:
                    return self._send_json(
                        {"error": f"plan not executable: {violations}",
                         "retriable": False}, 400)
                body = {"plan": _N.to_json(parsed["plan"]),
                        # coordinator session properties flow through
                        "session": parsed["session"].get(
                            "systemProperties", {}),
                        # keep the propagated trace context (body- or
                        # header-injected above) across the translation
                        "traceparent": body.get("traceparent"),
                        "traceId": body.get("traceId")}
                sf = parsed["fragmentInfo"].get("scaleFactor")
                if sf is not None:  # else the worker's configured sf
                    body["sf"] = sf
            try:
                info = self.manager.create_or_update(parts[2], body)
            except RuntimeError as e:  # draining
                return self._send_json({"error": str(e)}, 503)
            return self._send_json(info)
        return self._send_json({"error": f"unknown path {self.path}"}, 404)

    def do_PUT(self):  # noqa: N802  graceful shutdown (worker drain)
        if not self._authorized():
            return
        parts = [p for p in self.path.split("/") if p]
        if parts == ["v1", "info", "state"]:
            length = int(self.headers.get("Content-Length", "0"))
            body = json.loads(self.rfile.read(length) or b'""')
            if str(body).upper().replace('"', "") == "SHUTTING_DOWN":
                # GracefulShutdownHandler: stop accepting, finish running
                self.manager.drain()
                return self._send_json({"state": "SHUTTING_DOWN"})
            return self._send_json({"error": f"unknown state {body}"}, 400)
        return self._send_json({"error": f"unknown path {self.path}"}, 404)

    def do_DELETE(self):  # noqa: N802
        if not self._authorized():
            return
        parts = [p for p in self.path.split("/") if p]
        if parts[:2] == ["v1", "failpoint"] and len(parts) in (2, 3):
            return self._send_json(failpoints.admin_delete(
                parts[2] if len(parts) == 3 else None))
        if len(parts) == 3 and parts[:2] == ["v1", "task"]:
            self.manager.abort(parts[2])
            task = self.manager.get(parts[2])
            return self._send_json(task.info() if task else {"aborted": True})
        return self._send_json({"error": f"unknown path {self.path}"}, 404)


class TpuWorkerServer:
    """HTTP worker shell (PrestoServer.cpp:493 registerHttpEndpoints
    analog). start() binds a port and serves on background threads."""

    # drain lifecycle state shared between the drain thread and the
    # HTTP handlers (tpulint C001)
    _GUARDED_BY = {"_drain_lock": ("_drain_thread", "_drain_migrated")}

    def __init__(self, port: int = 0, sf: float = 0.01, mesh=None,
                 node_id: Optional[str] = None,
                 discovery_url: Optional[str] = None,
                 announce_interval_s: float = 1.0,
                 shared_secret: Optional[str] = None,
                 task_concurrency: int = 4,
                 tls: Optional[tuple] = None):
        from .auth import make_authenticator
        # structured log correlation on the worker tier too: task
        # threads log under the propagated trace context (utils/log.py)
        from ..utils.log import ensure_log_context
        ensure_log_context()
        self.manager = TaskManager(sf=sf, mesh=mesh,
                                   task_concurrency=task_concurrency)
        self.node_id = node_id or f"tpu-worker-{uuid.uuid4().hex[:8]}"
        auth = make_authenticator(shared_secret, self.node_id)
        handler = type("BoundHandler", (_Handler,), {
            "manager": self.manager, "node_id": self.node_id,
            "started_at": time.time(), "authenticator": auth,
            "worker_server": self})
        self.httpd = ThreadingHTTPServer(("127.0.0.1", port), handler)
        scheme = "http"
        if tls is not None:
            # https internal transport (internal-communication.https
            # mode; the JWT layer still authenticates peers)
            from .tls import server_context
            self.httpd.socket = server_context(*tls).wrap_socket(
                self.httpd.socket, server_side=True)
            scheme = "https"
        self.port = self.httpd.server_address[1]
        self.url = f"{scheme}://127.0.0.1:{self.port}"
        # a fresh worker on this url supersedes any drained
        # predecessor's goodbye mark (explicit-url clusters never
        # announce, so nothing else would clear it)
        from .discovery import clear_unannounced
        clear_unannounced(self.url)
        self._thread: Optional[threading.Thread] = None
        # stuck-progress watchdog (server/watchdog.py): scans this
        # manager's RUNNING tasks; disabled per task unless the session
        # property / PRESTO_TPU_STUCK_MS arms a threshold
        from .watchdog import StuckProgressWatchdog
        self._watchdog = StuckProgressWatchdog(
            self.manager._stuck_candidates, tier="worker")
        self._announcer = None
        self._shared_secret = shared_secret  # drain-migration hops
        self._drain_thread: Optional[threading.Thread] = None
        self._drain_lock = OrderedLock("worker.TpuWorkerServer._drain_lock")
        self._drain_migrated = 0
        self._stop_drain = threading.Event()  # server teardown signal
        if discovery_url:
            from .discovery import Announcer
            self._announcer = Announcer(
                discovery_url, self.node_id, self.url,
                interval_s=announce_interval_s,
                shared_secret=shared_secret)

    def start(self):
        from ..utils.compile_cache import setup_compile_cache
        setup_compile_cache()
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        self._watchdog.start()
        if self._announcer:
            self._announcer.start()
        return self

    def stop(self, unannounce: bool = True):
        self._stop_drain.set()  # release a waiting drain thread
        if self._announcer:
            self._announcer.stop(unannounce=unannounce)
        self._watchdog.stop()
        self.httpd.shutdown()
        self.httpd.server_close()

    def kill(self):
        """Ungraceful stop (a crash, not a goodbye): the HTTP server
        dies WITHOUT unannouncing, so discovery only notices when the
        announcement ages out -- the failure-detection path the chaos
        harness's kill rounds exercise, as opposed to stop()'s
        graceful goodbye."""
        self.stop(unannounce=False)

    # -- graceful drain (POST /v1/worker/drain) -------------------------

    def begin_drain(self, migrate_to: Optional[str] = None,
                    timeout_s: Optional[float] = None) -> dict:
        """Start the drain state machine (idempotent): refuse new
        tasks, announce DRAINING, then -- on a background thread --
        wait for running tasks, migrate remaining buffered pages to
        `migrate_to` (when given), and unannounce only once no
        unreplayed page remains (or the drain budget runs out: pages
        then stay served locally until consumed)."""
        with self._drain_lock:
            already = self._drain_thread is not None
            if not already:
                self.manager.drain()
                if self._announcer is not None:
                    self._announcer.set_state("DRAINING")
                t = threading.Thread(
                    target=self._drain, args=(migrate_to, timeout_s),
                    name=f"drain-{self.node_id}", daemon=True)
                self._drain_thread = t
        if already:
            return self.drain_status()
        from .metrics import record_suppressed
        if self._announcer is not None:
            try:
                # a DRAINING announcement lands NOW, not at the next
                # interval tick: placement filters react immediately.
                # (A loop-thread announcement serialized just before
                # set_state can land after this one and read ACTIVE for
                # up to one interval -- harmless: the drain refusal +
                # submit failover cover the window, and the next tick
                # re-announces DRAINING.)
                self._announcer.announce_once()
            except Exception as e:  # noqa: BLE001 - discovery may be
                # down; the drain itself must still proceed
                record_suppressed("worker", "drain_announce", e)
        t.start()
        return self.drain_status()

    def _drain(self, migrate_to: Optional[str],
               timeout_s: Optional[float]) -> None:
        from .flight_recorder import record_event
        from .metrics import record_suppressed
        if timeout_s is None:
            # the drain_timeout_ms session-property SPEC is the single
            # source of the default budget (callers override per
            # request via the body's timeoutMs)
            from ..utils.config import Session
            timeout_s = float(Session({}).get("drain_timeout_ms")) / 1e3
        budget = max(float(timeout_s), 0.0)
        deadline = time.time() + budget
        record_event("worker_drain", query_id=self.node_id,
                     phase="start", migrateTo=migrate_to)
        # 1. let running tasks finish (drain refuses only NEW ones)
        while time.time() < deadline and \
                self.manager.active_task_count() > 0:
            time.sleep(0.05)
        # 2. migrate the remaining buffered pages to the peer
        moved = 0
        try:
            if failpoints.ARMED:
                # delay/hang = a drain stuck behind a slow peer; error
                # = the migration hop dies (pages stay local + served)
                failpoints.hit("worker.drain_stall")
            if migrate_to:
                moved = self.manager.migrate_buffers(
                    migrate_to, secret=self._shared_secret)
        except Exception as e:  # noqa: BLE001 - a failed migration
            # degrades drain to serve-until-consumed, never data loss
            record_suppressed("worker", "drain_migrate", e)
        with self._drain_lock:
            self._drain_migrated = moved
        # 3. unannounce only when empty (pages all migrated/consumed).
        # The budget bounds how long we expect the fast path to take;
        # past it the node logs budget_exhausted (operator-visible) but
        # KEEPS waiting at a relaxed cadence -- a slow consumer must
        # not wedge the worker in DRAINING forever after it finally
        # drains the remainder
        exhausted = False
        while self.manager.unreplayed_pages() > 0 or \
                self.manager.active_task_count() > 0:
            if not exhausted and time.time() >= deadline:
                exhausted = True
                record_event("worker_drain", query_id=self.node_id,
                             phase="budget_exhausted",
                             migratedPages=moved,
                             unreplayedPages=self.manager
                             .unreplayed_pages())
            if self._stop_drain.wait(0.25 if exhausted else 0.05):
                return  # server stopping: leave the state as-is
        self.manager.mark_drained()
        if self._announcer is not None:
            self._announcer.stop(unannounce=True)
        # explicit-url clusters have no announcer: the process-wide
        # goodbye registry still drops this node from /v1/cluster
        # probes immediately (idempotent with the discovery DELETE)
        from .discovery import note_unannounced
        note_unannounced(self.url)
        record_event("worker_drain", query_id=self.node_id,
                     phase="complete", migratedPages=moved,
                     unreplayedPages=0)

    def drain_status(self) -> dict:
        """The drain state machine's live document (POST/GET
        /v1/worker/drain): ACTIVE | DRAINING | DRAINED plus the page
        accounting the chaos gate audits (a DRAINED worker must report
        zero unreplayed pages)."""
        m = self.manager
        with self._drain_lock:
            migrated = self._drain_migrated
        with m._counters_lock:
            adopted = m.counters.get("tasks_adopted", 0)
        return {"nodeId": self.node_id,
                "state": m.drain_state,
                "activeTasks": m.active_task_count(),
                "unreplayedPages": m.unreplayed_pages(),
                "migratedPages": migrated,
                "adoptedTasks": adopted}
