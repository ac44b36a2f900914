"""Client statement protocol: the coordinator's POST /v1/statement seam.

Reference surface: the REST protocol every Presto client speaks --
QueuedStatementResource (presto-main/.../server/protocol/
QueuedStatementResource.java:210 `POST /v1/statement` -> QueryResults
with a `nextUri` into the queued resource, redirecting to
ExecutingStatementResource once dispatch completes) and
StatementClientV1 (presto-client/.../StatementClientV1.java:88,365 --
advance() polls nextUri until it disappears). Response documents carry
{id, infoUri, nextUri, partialCancelUri, columns, data, stats, error,
updateType}; session mutations ride response headers
(X-Presto-Set-Session / X-Presto-Started-Transaction-Id / ...).

This server fronts the engine: queries admit through the Dispatcher
(resource groups + events), transact through the TransactionManager,
progress through a QueryStateMachine (query_state.py), and execute on a
background thread -- the LocalDispatchQuery.startWaitingForPrerequisites
-> SqlQueryExecution.start pipeline condensed to one process. Results
page out `page_rows` rows per nextUri hop, values rendered with the
reference's JSON conventions (decimals/dates/timestamps as strings).
"""

from __future__ import annotations

import dataclasses
import json
import re
import threading
import time
import uuid
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import failpoints
from .. import types as T
from ..exec.stats import QueryStats, StatsCollector, collecting, stage
from ..transaction import TransactionManager
from ..utils.locks import OrderedLock
from .dispatcher import Dispatcher, QueryRejected
from .flight_recorder import get_flight_recorder, record_event
from .query_state import QueryState, QueryStateMachine, TERMINAL_STATES
from .tracing import TraceContext, new_span_id

__all__ = ["StatementServer", "render_value"]


def render_value(v, null: bool, ty: T.Type):
    """Engine-native value -> client JSON (the reference's column
    rendering: decimals and temporals as strings)."""
    if null or v is None:
        return None
    if ty.is_decimal:
        s = ty.scale
        v = int(v)
        if s == 0:
            return str(v)
        sign = "-" if v < 0 else ""
        a = abs(v)
        return f"{sign}{a // 10**s}.{a % 10**s:0{s}d}"
    if ty.base == "date":
        return str(np.datetime64("1970-01-01") + int(v))
    if ty.base == "timestamp":
        us = int(v)
        base = np.datetime64("1970-01-01T00:00:00") + np.timedelta64(us, "us")
        return str(base).replace("T", " ")
    if ty.base == "array":
        return [render_value(e, e is None, ty.element_type) for e in v]
    if ty.is_floating:
        return float(v)
    if ty.base == "boolean":
        return bool(v)
    if ty.is_integral:
        return int(v)
    return str(v)


_ERROR_CODES = {
    "SYNTAX_ERROR": (1, "USER_ERROR"),
    "USER_CANCELED": (20000, "USER_ERROR"),
    "QUERY_QUEUE_FULL": (131075, "INSUFFICIENT_RESOURCES"),
    "GENERIC_INTERNAL_ERROR": (65536, "INTERNAL_ERROR"),
}


def _max_q_error_of(query_id: str):
    """Worst finalized q-error for one query id, or None (pre-close
    and on any registry hiccup -- a cluster frame must never fail on
    its garnish)."""
    try:
        from ..exec.accuracy import query_max_q_error
        q = query_max_q_error(query_id)
        return round(q, 2) if q is not None else None
    except Exception:  # noqa: BLE001
        return None


def _error_doc(name: str, message: str) -> dict:
    code, etype = _ERROR_CODES.get(name, _ERROR_CODES["GENERIC_INTERNAL_ERROR"])
    return {"message": message, "errorCode": code, "errorName": name,
            "errorType": etype,
            "failureInfo": {"type": name, "message": message}}


class _Query:
    """One statement's server-side lifecycle + result store."""

    def __init__(self, query_id: str, slug: str, text: str,
                 session_values: Dict, user: str, txn_id: Optional[str],
                 client_ctx: Optional[TraceContext] = None):
        self.id = query_id
        self.slug = slug
        self.text = text
        self.session_values = session_values
        self.user = user
        self.txn_id = txn_id
        self.machine = QueryStateMachine(query_id)
        # the statement's one collector, opened where the POST lands:
        # every span from here to the last rendered row is recorded on
        # it (exec/stats.py), `queue` being the first
        self.created_at = time.time()
        self.collector = StatsCollector(query_id)
        # this query's trace identity: the client's propagated trace id
        # when an X-Presto-Trace header arrived, else the query id
        # itself (so GET /v1/trace/{queryId} resolves without a lookup
        # table); span_id is the query ROOT span every other span of
        # the query ultimately parents to
        self.trace_ctx = TraceContext(
            client_ctx.trace_id if client_ctx else query_id,
            new_span_id())
        self.client_parent = client_ctx.span_id if client_ctx else None
        self.columns: Optional[List[dict]] = None
        self.rows: List[list] = []
        # client result-drain window (the trace's "client fetch" leg):
        # set by the executing resource, read once at final-page serve
        self.first_fetch_at: Optional[float] = None
        self.fetch_span_done = False
        self.update_type: Optional[str] = None
        self.update_count: Optional[int] = None
        # structured execution stats (QueryStats) once the engine ran
        self.result_stats = None
        # client-visible progress high-water marks: the live registry's
        # per-task aggregate can transiently dip when the task set
        # changes (a new task joins at 0%), but the PROTOCOL promises
        # monotonically non-decreasing progress on every poll -- the
        # max is taken here, per query (benign last-writer race: both
        # writers only raise it)
        self.progress_hwm = {"pct": 0.0, "rows": 0, "bytes": 0,
                             "peak": 0}
        # response-header mutations for the client to apply
        self.set_session: Dict[str, str] = {}
        self.started_txn: Optional[str] = None
        self.clear_txn: bool = False
        # admission attribution: the resource group the dispatcher
        # routed this query to, and (after execution) the size of the
        # batched dispatch that served it (0 = serial)
        self.resource_group: str = ""
        self.batch_size: int = 0


_SESSION_STMT = re.compile(
    r"\s*(start\s+transaction|commit|rollback|set\s+session)\b",
    re.IGNORECASE)


class StatementServer:
    """Coordinator statement resource over the local engine (or any
    executor callable). `executor(text, session_values, query_id,
    txn_id)` returns an object with .rows()/.names/.types (QueryResult);
    default executes through the SQL front door."""

    # request-handler threads share the query registry and the metrics
    # roll-ups; writes go through these locks (tpulint C001)
    _GUARDED_BY = {"_qlock": ("_queries",),
                   "_metrics_lock": ("_queries_by_state", "_totals",
                                     "_workers_alive",
                                     "_workers_draining")}

    def __init__(self, port: int = 0, sf: float = 0.01,
                 dispatcher: Optional[Dispatcher] = None,
                 executor=None, page_rows: int = 1024,
                 queue_poll_s: float = 1.0,
                 query_ttl_s: float = 600.0,
                 tls: Optional[tuple] = None,
                 profile_workers=None):
        """`profile_workers`: worker base URLs (list, or zero-arg
        callable returning one) whose GET /v1/datapath, /v1/accuracy
        and /v1/history slices the cluster-merged documents of the
        same routes on THIS server fold in --
        wire the coordinator's worker view here on the distributed
        tier; None serves this process's slice alone."""
        self.sf = sf
        self._profile_workers = profile_workers
        # structured log correlation: every engine log record carries
        # the ambient trace/query ids from here on (utils/log.py)
        from ..utils.log import ensure_log_context
        ensure_log_context()
        from ..sql.statements import PreparedStatements
        # per-user registries (the reference scopes prepared statements
        # per session via X-Presto-Prepared-Statement headers)
        self._prepared: Dict[str, PreparedStatements] = {}
        self.page_rows = page_rows
        self.queue_poll_s = queue_poll_s
        self.query_ttl_s = query_ttl_s
        self.dispatcher = dispatcher or Dispatcher()
        self.transactions = TransactionManager()
        self._executor = executor or self._default_executor
        self._queries: Dict[str, _Query] = {}
        self._qlock = OrderedLock("statement.StatementServer._qlock")
        self._started_at = time.time()
        # lifetime roll-ups for /v1/metrics (terminal queries only;
        # accounted exactly once per query in _run's finally)
        self._metrics_lock = OrderedLock("statement.StatementServer._metrics_lock")
        self._queries_by_state: Dict[str, int] = {}
        self._totals = {"rows": 0, "bytes": 0, "wall_us": 0,
                        "compile_us": 0, "execute_us": 0,
                        "peak_memory_bytes": 0}
        # fleet liveness cache: refreshed by every /v1/cluster probe;
        # None = never probed (the gauge then reports the configured
        # count optimistically rather than paying an HTTP probe per
        # metrics scrape)
        self._workers_alive: Optional[int] = None
        self._workers_draining = 0  # DRAINING rows of the last probe
        # stuck-progress watchdog (server/watchdog.py): scans live
        # queries; per query disabled unless stuck_query_threshold_ms /
        # PRESTO_TPU_STUCK_MS arms a threshold
        from .watchdog import StuckProgressWatchdog
        self._watchdog = StuckProgressWatchdog(
            self._stuck_candidates, tier="statement")
        handler = _make_handler(self)
        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), handler)
        scheme = "http"
        if tls is not None:
            from .tls import server_context
            self._httpd.socket = server_context(*tls).wrap_socket(
                self._httpd.socket, server_side=True)
            scheme = "https"
        self.port = self._httpd.server_address[1]
        self.url = f"{scheme}://127.0.0.1:{self.port}"
        self._thread: Optional[threading.Thread] = None

    # -- lifecycle ------------------------------------------------------

    def start(self):
        from ..utils.compile_cache import setup_compile_cache
        setup_compile_cache()
        from ..connectors.system import register_statement_server
        register_statement_server(self)  # system.queries introspection
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        self._watchdog.start()
        return self

    def stop(self):
        self._watchdog.stop()
        self._httpd.shutdown()
        self._httpd.server_close()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # -- execution ------------------------------------------------------

    def _default_executor(self, text: str, session_values: Dict,
                          query_id: str, txn_id: Optional[str]):
        from ..sql import sql as run_sql
        from ..sql.statements import preprocess
        sf = float(session_values.get("sf", self.sf))
        kwargs = {}
        if "max_groups" in session_values:
            kwargs["max_groups"] = int(session_values["max_groups"])
        if "join_capacity" in session_values:
            kwargs["join_capacity"] = int(session_values["join_capacity"])
        # SHOW/DESCRIBE rewrites + per-server prepared statements (the
        # coordinator session analog of X-Presto-Prepared-Statement)
        from ..sql.statements import PreparedStatements
        user = self._user_of(query_id)
        with stage("plan"):
            pre = preprocess(
                text, catalog=session_values.get("catalog", "tpch"),
                prepared=self._prepared.setdefault(
                    user, PreparedStatements()))
        if pre.ack is not None:
            from ..exec.runner import QueryResult
            return QueryResult([], [], [pre.ack], 0)
        kwargs["session"] = dict(session_values)
        kwargs["session"].setdefault("user", user)
        # the engine's stage spans must land under THIS query's trace
        # (same id _emit_trace uses for the state spans -> one trace
        # per query, and no shared default-"query" trace growing forever)
        kwargs["query_id"] = query_id
        ctx = self._trace_ctx_of(query_id)
        if ctx is not None:
            # stage spans become children of the query root span
            kwargs["trace_id"] = ctx
        # concurrent-query batching (exec/batching.py): co-batchable
        # statements that form a batch are served by ONE vmapped
        # dispatch and return here; everything else (not batchable,
        # batching off, no batch formed) runs the serial path below
        from ..exec.batching import get_batching_executor
        res = get_batching_executor().try_execute(
            pre.text, sf=sf, session=kwargs["session"],
            query_id=query_id, trace_id=kwargs.get("trace_id"),
            max_groups=kwargs.get("max_groups"),
            join_capacity=kwargs.get("join_capacity"),
            catalog=session_values.get("catalog", "tpch"))
        if res is not None:
            return res
        return run_sql(pre.text, sf=sf, **kwargs)

    def _user_of(self, query_id: str) -> str:
        with self._qlock:
            q = self._queries.get(query_id)
        return q.user if q is not None else ""

    def _trace_ctx_of(self, query_id: str) -> Optional[TraceContext]:
        with self._qlock:
            q = self._queries.get(query_id)
        return q.trace_ctx if q is not None else None

    def _emit_trace(self, q: "_Query") -> None:
        """Terminal-state hook: the query ROOT span (queued->terminal)
        plus per-state child spans (QueryStateTracingListener analog).
        Everything the query recorded elsewhere -- engine stage spans,
        coordinator/worker spans on the distributed tier -- parents
        into this root, so GET /v1/trace/{queryId} serves ONE tree."""
        from .tracing import emit_span, get_tracer, \
            spans_from_state_timings
        if get_tracer() is None:
            return
        try:
            timings = q.machine.timings()
            start = timings.get(QueryState.QUEUED, time.time())
            end = timings.get(q.machine.state, time.time())
            emit_span(q.trace_ctx.trace_id, "query", start, end,
                      {"queryId": q.id, "user": q.user,
                       "state": q.machine.state,
                       "query": q.text[:200]},
                      span_id=q.trace_ctx.span_id,
                      parent_id=q.client_parent)
            spans_from_state_timings(
                q.trace_ctx.trace_id, timings,
                ["QUEUED", "PLANNING", "RUNNING", "FINISHING",
                 "FINISHED", "FAILED"],
                {"user": q.user},
                parent_id=q.trace_ctx.span_id)
        except Exception as e:  # noqa: BLE001 - tracing must never
            # fail a query, but a tracer that stops shipping spans
            # should show on /v1/metrics
            from .metrics import record_suppressed
            record_suppressed("statement", "trace_spans", e)

    def _reap_locked(self) -> None:
        """Drop terminal queries (and their materialized result rows)
        older than query_ttl_s -- QueryTracker's expiration (the worker
        side reaps tasks the same way)."""
        import time as _time
        cutoff = _time.time() - self.query_ttl_s
        for qid in [qid for qid, q in self._queries.items()
                    if q.machine.is_done()
                    and q.machine.timings().get(q.machine.state, 0) < cutoff]:
            del self._queries[qid]

    def create_query(self, text: str, user: str,
                     session_values: Dict, txn_id: Optional[str],
                     client_ctx: Optional[TraceContext] = None) -> _Query:
        # rule-based session defaults (SessionPropertyConfigurationManager
        # analog): manager defaults under, client values over
        from .session_properties import get_session_property_manager
        mgr = get_session_property_manager()
        if mgr is not None:
            session_values = {**mgr.defaults_for(
                user, session_values.get("source", ""),
                session_values.get("clientTags")), **session_values}
        q = _Query(f"20260730_{uuid.uuid4().hex[:12]}",
                   uuid.uuid4().hex[:12], text, session_values, user,
                   txn_id, client_ctx=client_ctx)
        # every state transition lands on the flight-recorder timeline
        # (the ring a slow/failed dump replays)
        q.machine.add_listener(
            lambda old, new, qid=q.id: record_event(
                "query_state", query_id=qid, frm=old, to=new))
        with self._qlock:
            self._reap_locked()
            self._queries[q.id] = q
        threading.Thread(target=self._run, args=(q,), daemon=True).start()
        return q

    def inflight_doc(self) -> List[dict]:
        """In-flight statement manifest (one entry per non-terminal
        query): what a ClusterStateSender heartbeats to the resource
        manager so a StandbyCoordinator can adopt these statements
        when this coordinator's heartbeat lapses."""
        with self._qlock:
            queries = list(self._queries.values())
        out = []
        for q in queries:
            state = q.machine.state
            if state in TERMINAL_STATES:
                continue
            out.append({"queryId": q.id, "slug": q.slug,
                        "query": q.text, "user": q.user, "state": state,
                        "sessionProperties": q.session_values})
        return out

    def adopt_query(self, query_id: str, slug: str, text: str,
                    user: str, session_values: Dict) -> _Query:
        """Failover adoption: run `text` on THIS server under the
        ORIGINAL query id + slug, so a client re-resolving its polls
        here (via the router, or the standby url) drains the same
        statement. Idempotent per query id -- a re-fired failover
        never double-runs an adopted statement."""
        q = _Query(query_id, slug, text, dict(session_values or {}),
                   user, None)
        with self._qlock:
            existing = self._queries.get(query_id)
            if existing is not None:
                return existing
            self._reap_locked()
            self._queries[query_id] = q
        record_event("query_adopt", query_id=query_id, user=user)
        q.machine.add_listener(
            lambda old, new, qid=q.id: record_event(
                "query_state", query_id=qid, frm=old, to=new))
        threading.Thread(target=self._run, args=(q,), daemon=True).start()
        return q

    def _run(self, q: _Query):
        try:
            self._run_inner(q)
        finally:
            if q.machine.is_done():
                if not q.collector.closed:
                    # FAILED: the statement never reached _close_stats,
                    # and the spans it opened before the fault are what
                    # /v1/trace and presto_tpu_stage_seconds owe it
                    q.collector.close(q.trace_ctx)
                self._emit_trace(q)
                self._account_query(q)
                self._maybe_flight_dump(q)
                self._record_history(q)

    def _slow_threshold_ms(self, q: _Query) -> float:
        """slow_query_threshold_ms session property, env fallback
        PRESTO_TPU_SLOW_QUERY_MS; 0 / unset disables slow dumps."""
        import os
        raw = q.session_values.get(
            "slow_query_threshold_ms",
            os.environ.get("PRESTO_TPU_SLOW_QUERY_MS", "0"))
        try:
            return float(raw)
        except (TypeError, ValueError):
            return 0.0

    def _maybe_flight_dump(self, q: _Query) -> None:
        """Auto-dump the flight-recorder ring for a failed or slow
        query -- exactly once per query (the recorder dedups by key),
        counted per reason on /v1/metrics. Never fails the query."""
        try:
            state = q.machine.state
            reason = None
            if state == QueryState.FAILED:
                reason = "failed"
            else:
                thresh = self._slow_threshold_ms(q)
                if thresh > 0 and q.machine.elapsed_ms() >= thresh:
                    reason = "slow"
            if reason is None:
                return
            get_flight_recorder().maybe_dump(
                q.id, reason,
                extra={"state": state, "user": q.user,
                       "elapsedMs": q.machine.elapsed_ms(),
                       "traceId": q.trace_ctx.trace_id,
                       "query": q.text[:200]})
        except Exception as e:  # noqa: BLE001 - a dump problem is
            # telemetry loss, not a query failure; leave a counted trace
            from .metrics import record_suppressed
            record_suppressed("statement", "flight_dump", e)

    def _record_history(self, q: _Query) -> None:
        """Archive one terminal query into the process history archive
        (server/history.py) -- the record the perf sentinel gates and
        GET /v1/history / system.query_history serve. Runs AFTER the
        flight-dump check so a failed/slow dump wins the per-query dump
        slot over a perf-regression dump. Never fails the query."""
        try:
            from .history import QueryHistoryArchive, get_history_archive
            # the EFFECTIVE scale factor salts the sentinel fingerprint:
            # the server-constructor sf applies when the client set no
            # session property, and cross-sf runs of the same SQL must
            # not share a baseline (a workload change is not a
            # regression)
            session = dict(q.session_values)
            session.setdefault("sf", self.sf)
            record = QueryHistoryArchive.record_of(
                q.id, q.machine.state, q.user, q.text,
                q.machine.elapsed_ms(), q.trace_ctx.trace_id,
                query_stats=q.result_stats, session=session)
            # the batch-template fingerprint (exec/batching.py) rides
            # the record so the archive's per-fingerprint frequency
            # can drive batch-formation windows across restarts
            from ..exec.batching import template_fp_of
            bfp = template_fp_of(q.id)
            if bfp:
                record["batchFingerprint"] = bfp
                record["batchSize"] = q.batch_size
            get_history_archive().add(record)
        except Exception as e:  # noqa: BLE001 - history is telemetry;
            # a malformed executor result (query_stats of a foreign
            # type) must not kill the query thread's terminal path
            from .metrics import record_suppressed
            record_suppressed("statement", "record_history", e)

    def _account_query(self, q: _Query) -> None:
        """Roll a terminal query into the /v1/metrics lifetime totals
        (exactly once: _run's finally is the single terminal seam) and
        feed the latency distributions: end-to-end wall plus one
        observation per traversed state, exemplar'd with the query's
        trace id so a p99 bucket links straight to its waterfall."""
        from .metrics import observe_histogram
        tid = q.trace_ctx.trace_id
        observe_histogram("presto_tpu_query_latency_seconds",
                          q.machine.elapsed_ms() / 1e3, trace_id=tid)
        timings = q.machine.timings()
        entered = sorted(((s, t) for s, t in timings.items()),
                         key=lambda x: x[1])
        for i, (state, start) in enumerate(entered):
            if state not in ("QUEUED", "PLANNING", "RUNNING",
                             "FINISHING"):
                continue
            end = entered[i + 1][1] if i + 1 < len(entered) \
                else time.time()
            observe_histogram("presto_tpu_query_state_seconds",
                              max(end - start, 0.0),
                              labels={"state": state}, trace_id=tid)
        qs = q.result_stats
        with self._metrics_lock:
            st = q.machine.state
            self._queries_by_state[st] = \
                self._queries_by_state.get(st, 0) + 1
            self._totals["rows"] += len(q.rows)
            self._totals["wall_us"] += q.machine.elapsed_ms() * 1000
            if qs is not None:
                self._totals["bytes"] += qs.output_bytes
                self._totals["compile_us"] += qs.compile_us
                self._totals["execute_us"] += qs.stage_us("execute")
                self._totals["peak_memory_bytes"] = max(
                    self._totals["peak_memory_bytes"],
                    qs.peak_memory_bytes)

    def _run_inner(self, q: _Query):
        m = _SESSION_STMT.match(q.text)
        try:
            if m:
                self._run_session_statement(q, m.group(1).lower())
                return
            # per-query failpoint schedule (`failpoints` session
            # property): armed for this query's dispatch + execution
            # scope, restored afterwards
            q.resource_group = self.dispatcher.select_group(
                {"user": q.user, **q.session_values})
            with failpoints.session_scope(
                    q.session_values.get("failpoints")):
                self.dispatcher.submit(
                    lambda qid: self._run_engine(q),
                    session={"user": q.user, **q.session_values},
                    query_text=q.text, query_id=q.id,
                    queue_timeout=float(q.session_values.get(
                        "queue_timeout_s", 60.0)))
        except QueryRejected as e:
            q.machine.to_failed(_error_doc("QUERY_QUEUE_FULL", str(e)))
        except Exception as e:  # noqa: BLE001
            name = "SYNTAX_ERROR" if "parse" in type(e).__name__.lower() \
                or "Syntax" in str(e) else "GENERIC_INTERNAL_ERROR"
            q.machine.to_failed(_error_doc(name, f"{type(e).__name__}: {e}"))

    def _run_engine(self, q: _Query):
        """The statement on its engine thread, under its collector:
        `queue` is POST accepted to here (thread start, admission)."""
        q.collector.record_stage("queue", q.created_at, time.time())
        with collecting(q.collector):
            return self._run_statement(q)

    def _close_stats(self, q: _Query, res=None) -> None:
        """The last step before FINISHED: the client's final document
        carries the collector's stats, and /v1/trace its spans. A batch
        member's or a custom executor's own document takes the server's
        stages (queue, batch, render) and counters in."""
        qs = q.collector.stats
        own = getattr(res, "query_stats", None)
        if isinstance(own, QueryStats) and own is not qs:
            qs = dataclasses.replace(own.merge(qs),
                                     task_count=own.task_count)
        q.result_stats = own if own is not None \
            and not isinstance(own, QueryStats) else qs
        q.collector.close(q.trace_ctx)

    def _run_statement(self, q: _Query):
        if failpoints.ARMED:
            # hang = a wedged statement tier (the client poll deadline's
            # test surface); error = a query failed before planning
            failpoints.hit("statement.execute")
        q.machine.to_planning()
        m = re.match(r"\s*explain(\s+analyze)?\b", q.text, re.IGNORECASE)
        if m:
            # EXPLAIN [ANALYZE]: one varchar plan-text column (the
            # reference's EXPLAIN output shape)
            from ..plan import explain as explain_plan
            from ..plan import explain_analyze
            from ..sql import plan_sql
            inner = q.text[m.end():].strip()
            sf = float(q.session_values.get("sf", self.sf))
            q.machine.to_running()
            text = explain_analyze(plan_sql(inner), sf=sf,
                                   session=q.session_values) \
                if m.group(1) \
                else explain_plan(plan_sql(inner), regions=True,
                                  session=q.session_values, sf=sf)
            q.columns = [{"name": "Query Plan", "type": "varchar"}]
            q.rows = [[line] for line in text.splitlines()]
            q.machine.to_finishing()
            self._close_stats(q)
            q.machine.to_finished()
            return
        q.machine.to_running()
        if q.txn_id is not None:
            self.transactions.get(q.txn_id)  # validates + touches
            if re.match(r"\s*(insert|create\s+table|drop\s+table|delete|"
                        r"update)\b", q.text, re.IGNORECASE):
                # checkConnectorWrite: writes refuse READ ONLY txns
                self.transactions.access_check_write(q.txn_id, "memory")
            res = self._executor(q.text, q.session_values, q.id, q.txn_id)
        else:
            res = self.transactions.run_autocommit(
                lambda tid: self._executor(q.text, q.session_values, q.id,
                                           tid))
        q.machine.to_finishing()
        wm = re.match(r"\s*(insert|create\s+table|drop\s+table|delete|"
                      r"update)\b", q.text, re.IGNORECASE)
        if wm:
            kind = " ".join(wm.group(1).upper().split())
            q.update_type = {"INSERT": "INSERT",
                             "CREATE TABLE": "CREATE TABLE AS",
                             "DROP TABLE": "DROP TABLE",
                             "DELETE": "DELETE",
                             "UPDATE": "UPDATE"}[kind]
            if res.types and res.types[0].base == "bigint" and \
                    res.row_count == 1:
                q.update_count = int(res.columns[0][0])
        from ..exec.batching import batch_size_of
        q.batch_size = batch_size_of(q.id)
        q.columns = [{"name": n, "type": str(t)}
                     for n, t in zip(res.names, res.types)]
        # M001: protocol rendering of the FINAL RESULT the client
        # asked for -- output cardinality, already materialized
        _BOUNDED_BY = {"rendered": "final result rows (protocol "
                                   "rendering)"}
        rendered = []
        with stage("render"):
            for i in range(res.row_count):
                rendered.append([
                    render_value(res.columns[c][i],
                                 bool(res.nulls[c][i]), res.types[c])
                    for c in range(len(res.types))])
        q.rows = rendered
        self._close_stats(q, res)
        q.machine.to_finished()
        return res

    def _run_session_statement(self, q: _Query, kind: str):
        q.machine.to_planning()
        q.machine.to_running()
        kind = " ".join(kind.split())
        if kind == "start transaction":
            if q.txn_id is not None:
                raise RuntimeError("already in a transaction")
            read_only = bool(re.search(r"read\s+only", q.text, re.I))
            q.started_txn = self.transactions.begin(read_only=read_only)
            q.update_type = "START TRANSACTION"
        elif kind in ("commit", "rollback"):
            if q.txn_id is None:
                raise RuntimeError(f"{kind.upper()} outside a transaction")
            if kind == "commit":
                self.transactions.commit(q.txn_id)
            else:
                self.transactions.rollback(q.txn_id)
            q.clear_txn = True
            q.update_type = kind.upper()
        else:  # SET SESSION k = v
            m = re.match(r"\s*set\s+session\s+([A-Za-z_][\w.]*)\s*=\s*(.+?)\s*$",
                         q.text, re.IGNORECASE)
            if not m:
                raise ValueError(f"cannot parse SET SESSION: {q.text!r}")
            key, raw = m.group(1), m.group(2).strip().rstrip(";").strip()
            if raw.startswith("'") and raw.endswith("'"):
                raw = raw[1:-1]
            q.set_session[key] = raw
            q.update_type = "SET SESSION"
        q.columns = [{"name": "result", "type": "boolean"}]
        q.rows = [[True]]
        q.machine.to_finishing()
        q.machine.to_finished()

    # -- document assembly ---------------------------------------------

    def get_query(self, query_id: str, slug: str) -> Optional[_Query]:
        with self._qlock:
            q = self._queries.get(query_id)
        if q is None or q.slug != slug:
            return None
        return q

    def queued_doc(self, q: _Query, token: int) -> dict:
        state = q.machine.state
        doc = self._base_doc(q, state)
        if state == QueryState.QUEUED:
            doc["nextUri"] = \
                f"{self.url}/v1/statement/queued/{q.id}/{q.slug}/{token + 1}"
        elif state in (QueryState.FAILED, QueryState.CANCELED):
            doc["error"] = q.machine.error or \
                _error_doc("USER_CANCELED", "query was canceled")
        else:
            doc["nextUri"] = \
                f"{self.url}/v1/statement/executing/{q.id}/{q.slug}/0"
        return doc

    def executing_doc(self, q: _Query, token: int) -> dict:
        state = q.machine.state
        doc = self._base_doc(q, state)
        if state in (QueryState.FAILED, QueryState.CANCELED):
            doc["error"] = q.machine.error or \
                _error_doc("USER_CANCELED", "query was canceled")
            return doc
        if state != QueryState.FINISHED:
            # results not materialized yet: poll the same token
            doc["nextUri"] = \
                f"{self.url}/v1/statement/executing/{q.id}/{q.slug}/{token}"
            return doc
        doc["columns"] = q.columns
        if q.first_fetch_at is None:
            q.first_fetch_at = time.time()
        lo = token * self.page_rows
        hi = lo + self.page_rows
        page = q.rows[lo:hi]
        if page:
            doc["data"] = page
        if q.update_type:
            doc["updateType"] = q.update_type
        if q.update_count is not None:
            doc["updateCount"] = q.update_count
        if hi < len(q.rows):
            doc["nextUri"] = \
                f"{self.url}/v1/statement/executing/{q.id}/{q.slug}/{token + 1}"
        elif not q.fetch_span_done:
            # final page served: the client-drain leg of the trace
            # (first results poll -> last page out the door). The flag
            # check is best-effort: a concurrent re-drain could emit a
            # second span, acceptable for telemetry.
            q.fetch_span_done = True
            from .tracing import emit_span
            emit_span(q.trace_ctx.trace_id, "client.fetch",
                      q.first_fetch_at, time.time(),
                      {"rows": len(q.rows), "pages": token + 1},
                      parent_id=q.trace_ctx.span_id)
        return doc

    def _progress_doc(self, q: _Query) -> Optional[dict]:
        """The query's live progress aggregate: its own engine entry
        plus every remote task entry tagged with its trace id
        (exec/progress.py -- fed locally by run_query, cross-worker by
        the coordinator's status polls)."""
        from ..exec.progress import aggregate_query_progress
        return aggregate_query_progress({q.id, q.trace_ctx.trace_id})

    def _base_doc(self, q: _Query, state: str) -> dict:
        queued = state == QueryState.QUEUED
        doc = {
            "id": q.id,
            "infoUri": f"{self.url}/v1/query/{q.id}",
            "stats": {
                "state": state,
                "queued": queued,
                "scheduled": state not in (QueryState.QUEUED,
                                           QueryState.PLANNING),
                "elapsedTimeMillis": q.machine.elapsed_ms(),
                "processedRows": len(q.rows),
                "processedBytes": 0,
                "peakMemoryBytes": 0,
            },
        }
        stats = doc["stats"]
        prog = self._progress_doc(q)
        hwm = q.progress_hwm
        if prog is not None:
            # live heartbeats: an IN-FLIGHT poll sees real movement
            # (the round-1 protocol hardcoded zeros until FINISHED).
            # Counters clamp to the per-query high-water mark so the
            # client-visible sequence is monotonic even when the task
            # set changes under the aggregate.
            hwm["rows"] = max(hwm["rows"], prog["rows"])
            hwm["bytes"] = max(hwm["bytes"], prog["bytes"])
            hwm["peak"] = max(hwm["peak"], prog["peakMemoryBytes"])
            hwm["pct"] = max(hwm["pct"], prog["progressPercent"])
            stats["stage"] = prog["stage"]
            stats["lastAdvanceAgeMs"] = prog["lastAdvanceAgeMs"]
            stats["liveTasks"] = prog["runningTasks"]
            stats["splitsDone"] = prog["splitsDone"]
            stats["splitsPlanned"] = prog["splitsPlanned"]
        stats["processedRows"] = max(len(q.rows), hwm["rows"])
        stats["processedBytes"] = hwm["bytes"]
        stats["peakMemoryBytes"] = hwm["peak"]
        stats["progressPercent"] = 100.0 \
            if state == QueryState.FINISHED else round(hwm["pct"], 1)
        qs = q.result_stats
        if qs is not None:
            # the engine's structured stats populate the client
            # protocol's stats field (StatementStats analog), with the
            # full stage/operator document alongside for rich clients
            stats["processedBytes"] = max(stats["processedBytes"],
                                          qs.output_bytes)
            stats["peakMemoryBytes"] = max(stats["peakMemoryBytes"],
                                           qs.peak_memory_bytes)
            stats["compileTimeMicros"] = qs.compile_us
            stats["executeTimeMicros"] = qs.stage_us("execute")
            stats["queryStats"] = qs.to_json()
        return doc

    def cancel(self, q: _Query) -> None:
        q.machine.to_canceled()

    def admin_doc(self, query_id: str) -> Optional[dict]:
        with self._qlock:
            q = self._queries.get(query_id)
        if q is None:
            return None
        return {"queryId": q.id, "state": q.machine.state,
                "query": q.text, "user": q.user,
                "sessionProperties": q.session_values,
                "timings": q.machine.timings(),
                "elapsedTimeMillis": q.machine.elapsed_ms(),
                "errorInfo": q.machine.error,
                "resourceGroup": q.resource_group,
                "batchSize": q.batch_size,
                # the live-progress aggregate (None before anything
                # registered): system.queries' progress columns and the
                # per-query admin page read it mid-flight
                "progress": self._progress_doc(q),
                "queryStats": q.result_stats.to_json()
                if q.result_stats is not None else None}

    def queries_doc(self) -> List[dict]:
        with self._qlock:
            ids = list(self._queries)
        return [self.admin_doc(i) for i in ids]

    def trace_doc(self, query_or_trace_id: str) -> Optional[dict]:
        """The stitched one-trace-per-query document for GET
        /v1/trace/{queryId}. Accepts a query id (resolved to its trace
        id) or, for reaped queries, a raw trace id."""
        from .tracing import get_tracer, trace_doc_of
        with self._qlock:
            q = self._queries.get(query_or_trace_id)
        tid = q.trace_ctx.trace_id if q is not None else query_or_trace_id
        doc = trace_doc_of(get_tracer(), tid)
        if doc is not None and q is not None:
            doc["queryId"] = q.id
            doc["state"] = q.machine.state
        return doc

    def _stuck_candidates(self):
        """Live queries offered to the stuck-progress watchdog: every
        non-terminal query past QUEUED (queued waits are the
        dispatcher's business), threshold from its session (env
        fallback), last advance = the freshest of its state transitions
        and its progress entries' heartbeats -- so a query wedged
        before the engine registered anything still ages from the
        moment it entered RUNNING."""
        from ..exec.progress import aggregate_query_progress
        from .watchdog import StuckCandidate, resolve_stuck_threshold_ms
        with self._qlock:
            queries = list(self._queries.values())
        out = []
        now = time.time()
        for q in queries:
            state = q.machine.state
            if state == QueryState.QUEUED or state in TERMINAL_STATES:
                continue
            thr = resolve_stuck_threshold_ms(q.session_values)
            if thr <= 0:
                continue
            last = max(q.machine.timings().values())
            prog = aggregate_query_progress({q.id,
                                             q.trace_ctx.trace_id})
            if prog is not None:
                last = max(last, now - prog["lastAdvanceAgeMs"] / 1000.0)
            out.append(StuckCandidate(
                q.id, thr, last, trace_id=q.trace_ctx.trace_id,
                extra={"state": state, "user": q.user,
                       "query": q.text[:200]}))
        return out

    def cluster_doc(self) -> dict:
        """The fleet overview ``GET /v1/cluster`` serves (the reference
        coordinator's ClusterStatsResource analog): live query counts +
        per-query progress, per-worker liveness/occupancy rows probed
        over ``GET /v1/status``, aggregate throughput, resource-group
        queue depths, and the stuck-progress watchdog total. One
        probe refreshes the workers-alive gauge cache."""
        from ..exec.progress import live_snapshots, live_task_count
        from .client import pull_worker_docs
        from .watchdog import stuck_totals
        now = time.time()
        with self._qlock:
            queries = list(self._queries.values())
        queued = running = 0
        running_docs = []
        for q in queries:
            state = q.machine.state
            if state in TERMINAL_STATES:
                continue
            if state == QueryState.QUEUED:
                queued += 1
            else:
                running += 1
            running_docs.append({
                "queryId": q.id, "user": q.user, "state": state,
                "elapsedMs": q.machine.elapsed_ms(),
                "query": q.text[:120],
                "traceId": q.trace_ctx.trace_id,
                "progress": self._progress_doc(q),
                # worst finalized q-error (None until the estimate
                # ledger closed out -- FINISHING queries show it while
                # the client still drains); the ptop per-query column
                "maxQError": _max_q_error_of(q.id)})
        groups = self.dispatcher.group_stats()
        blocked = sum(int(g.get("queued", 0)) for g in groups.values())
        from .discovery import recently_unannounced
        all_urls = self._worker_urls()
        # a worker that UNANNOUNCED (graceful goodbye / completed
        # drain) drops out of the probed set IMMEDIATELY -- probing it
        # until some ttl expired made drained workers flap
        # dead-then-alive in the workers-alive gauge. The goodbye
        # registry is PROCESS-local (worker drains and the discovery
        # DELETE handler feed it); statement tiers running in their
        # own process should wire `profile_workers` to a discovery-
        # backed callable instead -- alive_nodes drops unannounced
        # nodes immediately by construction
        gone = set(recently_unannounced())
        urls = [u for u in all_urls if str(u).rstrip("/") not in gone]
        workers, alive = pull_worker_docs(
            urls, 2.0, lambda c: {**c.status(), "uri": c.base},
            "statement", "cluster_status", parallel=True,
            placeholder=lambda u: {"uri": u, "nodeId": u,
                                   "state": "DEAD",
                                   "fleetState": "DEAD", "memory": {}})
        for w in workers:
            # older workers without the elastic state machine map their
            # legacy flat state onto it
            w.setdefault("fleetState",
                         "DRAINING" if w.get("state") == "SHUTTING_DOWN"
                         else str(w.get("state", "ACTIVE")))
        draining = sum(1 for w in workers
                       if w.get("fleetState") == "DRAINING")
        with self._metrics_lock:
            self._workers_alive = alive
            self._workers_draining = draining
            by_state = dict(self._queries_by_state)
            totals = dict(self._totals)
        live = live_snapshots()
        rows_per_s = sum(e["rows"] / max(e["elapsedMs"] / 1000.0, 1e-3)
                         for e in live)
        return {
            "tsUs": int(now * 1e6),
            "nodeVersion": {"version": "presto-tpu-0.4"},
            "uptimeSeconds": round(now - self._started_at, 1),
            "queries": {"queued": queued, "running": running,
                        "blocked": blocked,
                        "finishedTotal": by_state.get("FINISHED", 0),
                        "failedTotal": by_state.get("FAILED", 0),
                        "canceledTotal": by_state.get("CANCELED", 0)},
            "runningQueries": running_docs,
            "liveTasks": live_task_count(),
            "rowsPerSecond": round(rows_per_s, 1),
            "totals": {"rows": totals["rows"], "bytes": totals["bytes"],
                       "wallSeconds": round(totals["wall_us"] / 1e6, 3)},
            "resourceGroups": groups,
            # live batching view: per-group queue depth rides
            # resourceGroups above; this is the dispatch-amortization
            # side (current occupancy, forming queues, collapses)
            "batching": self._batching_doc(),
            "workers": workers,
            "workersAlive": alive,
            # the CONFIGURED count keeps counting unannounced workers
            # (they are configured, just gone): ptop's alive/configured
            # ratio is where a missing worker shows
            "workersConfigured": len(all_urls),
            "workersDraining": draining,
            "workersDead": sum(1 for w in workers
                               if w.get("fleetState") == "DEAD"),
            "workersUnannounced": len(all_urls) - len(urls),
            "stuckQueriesTotal": stuck_totals(),
            # data-path staging rate + cached bottleneck hop (the ptop
            # header; a cluster frame never pays the ceilings probe)
            "datapath": self._datapath_summary(),
            # estimate-accuracy lifetime summary (worst q-error + its
            # node): the ptop header's accuracy line
            "accuracy": self._accuracy_summary(),
        }

    def _accuracy_summary(self) -> dict:
        """The cheap per-frame accuracy embed (never fails the fleet
        overview)."""
        try:
            from ..exec.accuracy import accuracy_summary
            return accuracy_summary()
        except Exception as e:  # noqa: BLE001 - introspection must not
            # take down the fleet overview
            from .metrics import record_suppressed
            record_suppressed("statement", "accuracy_summary", e)
            return {}

    def _datapath_summary(self) -> dict:
        """The cheap per-frame datapath embed (never fails the fleet
        overview)."""
        try:
            from ..exec.datapath import staging_summary
            return staging_summary()
        except Exception as e:  # noqa: BLE001 - introspection must not
            # take down the fleet overview
            from .metrics import record_suppressed
            record_suppressed("statement", "datapath_summary", e)
            return {}

    def _batching_doc(self) -> dict:
        """The batching executor's live snapshot for /v1/cluster
        (never fails the cluster doc)."""
        try:
            from ..exec.batching import batching_snapshot
            return batching_snapshot()
        except Exception as e:  # noqa: BLE001 - introspection must not
            # take down the fleet overview
            from .metrics import record_suppressed
            record_suppressed("statement", "batching_doc", e)
            return {}

    def _workers_alive_view(self) -> int:
        """The workers-alive gauge value: the last /v1/cluster probe's
        count, or the configured count before any probe (metrics
        scrapes never pay an HTTP probe themselves)."""
        with self._metrics_lock:
            alive = self._workers_alive
        return len(self._worker_urls()) if alive is None else alive

    def metric_families(self):
        """Coordinator-side /v1/metrics families (shared emitter:
        metrics.py; the worker serves its own set through the same
        module so format/naming cannot drift)."""
        from .metrics import MetricFamily as MF
        with self._qlock:
            live = [q.machine.state for q in self._queries.values()]
        queued = sum(1 for s in live if s == QueryState.QUEUED)
        running = sum(1 for s in live
                      if s not in (QueryState.QUEUED, *TERMINAL_STATES))
        with self._metrics_lock:
            by_state = dict(self._queries_by_state)
            totals = dict(self._totals)
        fam_q = MF("presto_tpu_queries_total", "counter",
                   "terminal queries by final state")
        for st in sorted(by_state):
            fam_q.add(by_state[st], {"state": st})
        if not by_state:
            fam_q.add(0, {"state": "FINISHED"})
        fams = [
            fam_q,
            MF("presto_tpu_queries_queued", "gauge",
               "queries currently QUEUED").add(queued),
            MF("presto_tpu_queries_running", "gauge",
               "queries currently executing").add(running),
            MF("presto_tpu_query_rows_total", "counter",
               "result rows returned to clients").add(totals["rows"]),
            MF("presto_tpu_query_bytes_total", "counter",
               "result bytes produced").add(totals["bytes"]),
            MF("presto_tpu_query_wall_seconds_total", "counter",
               "wall time of terminal queries").add(
                   totals["wall_us"] / 1e6),
            MF("presto_tpu_query_compile_seconds_total", "counter",
               "XLA compile time across queries").add(
                   totals["compile_us"] / 1e6),
            MF("presto_tpu_query_execute_seconds_total", "counter",
               "device execute time across queries").add(
                   totals["execute_us"] / 1e6),
            MF("presto_tpu_query_peak_memory_bytes", "gauge",
               "largest per-query peak memory seen").add(
                   totals["peak_memory_bytes"]),
        ]
        from .metrics import (batching_families, datapath_families,
                              donation_families, failpoint_families,
                              fleet_families, flight_recorder_families,
                              histogram_families, kernel_audit_families,
                              live_introspection_families,
                              narrowing_families, plan_cache_families,
                              query_history_families,
                              suppressed_error_families,
                              tracing_families, uptime_family)
        fams.append(uptime_family(self._started_at, "coordinator"))
        fams.extend(live_introspection_families(
            workers_alive=self._workers_alive_view()))
        with self._metrics_lock:
            draining = self._workers_draining
        fams.extend(fleet_families(workers_draining=draining))
        fams.extend(plan_cache_families())
        fams.extend(narrowing_families())
        fams.extend(datapath_families())
        from .metrics import accuracy_families
        fams.extend(accuracy_families())
        fams.extend(batching_families())
        fams.extend(suppressed_error_families())
        fams.extend(tracing_families())
        fams.extend(flight_recorder_families())
        fams.extend(kernel_audit_families())
        fams.extend(donation_families())
        fams.extend(failpoint_families())
        from .metrics import lock_families
        fams.extend(lock_families())
        fams.extend(query_history_families())
        fams.extend(histogram_families())
        return fams

    def history_doc(self) -> dict:
        """Cluster-merged completed-query history for GET /v1/history
        (server/history.py): this process's archive slice plus every
        configured worker's, newest-first, deduplicated by processId
        (an in-process worker is not counted twice)."""
        from .history import cluster_history_doc
        return cluster_history_doc(self._worker_urls())

    def datapath_doc(self) -> dict:
        """Cluster-merged per-hop data-path ledger for GET
        /v1/datapath: this process's slice plus every configured
        worker's, folded by hop (exec/datapath.py; processId dedup
        keeps an in-process worker from double-counting)."""
        from ..exec.datapath import cluster_datapath_doc
        return cluster_datapath_doc(self._worker_urls())

    def accuracy_doc(self) -> dict:
        """Cluster-merged estimate-accuracy ledger for GET
        /v1/accuracy: this process's slice plus every configured
        worker's, per-query records stitched by the NodeAccuracy merge
        law (exec/accuracy.py; processId dedup keeps an in-process
        worker from double-counting)."""
        from ..exec.accuracy import cluster_accuracy_doc
        return cluster_accuracy_doc(self._worker_urls())

    def _worker_urls(self) -> list:
        """The worker base URLs the cluster-merged surfaces
        (/v1/datapath, /v1/accuracy, /v1/history) pull slices from."""
        pw = self._profile_workers
        return list(pw() if callable(pw) else (pw or ()))


def _render_ui(server: "StatementServer", parts: List[str]) -> str:
    """Minimal coordinator UI (presto-ui's QueryList/QueryDetail pages,
    server-rendered): /ui lists queries, /ui/query/<id> shows one."""
    import html as H

    style = ("<style>body{font-family:monospace;margin:2em}"
             "table{border-collapse:collapse}"
             "td,th{border:1px solid #999;padding:4px 8px;text-align:left}"
             "th{background:#eee}.FINISHED{color:#080}"
             ".FAILED{color:#b00}.RUNNING{color:#06c}</style>")
    if len(parts) == 2 and parts[0] == "query":
        doc = server.admin_doc(parts[1])
        if doc is None:
            return f"{style}<h2>query {H.escape(parts[1])} not found</h2>"
        rows = "".join(
            f"<tr><th>{H.escape(str(k))}</th>"
            f"<td><pre>{H.escape(json.dumps(v, indent=1, default=str))}"
            f"</pre></td></tr>" for k, v in doc.items())
        return (f"{style}<h2>query {H.escape(parts[1])}</h2>"
                f"<p><a href='/ui'>&larr; queries</a></p>"
                f"<table>{rows}</table>")
    docs = sorted(server.queries_doc(),
                  key=lambda d: d.get("timings", {}).get("QUEUED", 0),
                  reverse=True)
    rows = "".join(
        f"<tr><td><a href='/ui/query/{H.escape(d['queryId'])}'>"
        f"{H.escape(d['queryId'])}</a></td>"
        f"<td class='{H.escape(d['state'])}'>{H.escape(d['state'])}</td>"
        f"<td>{H.escape(d['user'])}</td>"
        f"<td>{d.get('elapsedTimeMillis', 0)} ms</td>"
        f"<td>{H.escape(d['query'][:120])}</td></tr>" for d in docs)
    return (f"{style}<h2>presto-tpu coordinator</h2>"
            f"<p>{len(docs)} queries (TTL {server.query_ttl_s:.0f}s)</p>"
            f"<table><tr><th>query</th><th>state</th><th>user</th>"
            f"<th>elapsed</th><th>sql</th></tr>{rows}</table>")


def _parse_session_header(value: str) -> Dict[str, str]:
    out = {}
    for part in value.split(","):
        part = part.strip()
        if part and "=" in part:
            k, v = part.split("=", 1)
            out[k.strip()] = v.strip()
    return out


def _make_handler(server: StatementServer):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, fmt, *args):  # quiet
            pass

        def _send(self, doc, code=200, headers: Optional[Dict] = None):
            body = json.dumps(doc).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):  # noqa: N802
            parts = [p for p in self.path.split("/") if p]
            if parts == ["v1", "failpoint"]:
                length = int(self.headers.get("Content-Length", "0") or 0)
                body = json.loads(self.rfile.read(length) or b"{}")
                doc, code = failpoints.admin_post(body)
                self._send(doc, code)
                return
            if self.path.rstrip("/") != "/v1/statement":
                self._send({"error": "not found"}, 404)
                return
            length = int(self.headers.get("Content-Length", "0") or 0)
            text = self.rfile.read(length).decode("utf-8", "replace")
            if not text.strip():
                self._send(_error_doc("SYNTAX_ERROR", "empty statement"),
                           400)
                return
            user = self.headers.get("X-Presto-User", "anonymous")
            session_values = _parse_session_header(
                self.headers.get("X-Presto-Session", ""))
            src = self.headers.get("X-Presto-Source")
            if src:
                session_values.setdefault("source", src)
            tags = self.headers.get("X-Presto-Client-Tags")
            if tags:
                session_values.setdefault(
                    "clientTags", [t.strip() for t in tags.split(",")
                                   if t.strip()])
            txn = self.headers.get("X-Presto-Transaction-Id")
            if txn in (None, "", "NONE"):
                txn = None
            from .tracing import TRACE_HEADER, parse_traceparent
            client_ctx = parse_traceparent(
                self.headers.get(TRACE_HEADER))
            q = server.create_query(text, user, session_values, txn,
                                    client_ctx=client_ctx)
            # give fast statements a beat to leave QUEUED (the reference
            # responds immediately; one poll saves a client round trip)
            q.machine.wait_past_queued(0.05)
            self._send(server.queued_doc(q, 0))

        def do_GET(self):  # noqa: N802
            parts = [p for p in self.path.split("/") if p]
            # /v1/statement/{queued|executing}/{id}/{slug}/{token}
            if len(parts) == 6 and parts[:2] == ["v1", "statement"] and \
                    parts[2] in ("queued", "executing"):
                q = server.get_query(parts[3], parts[4])
                if q is None:
                    self._send({"error": "query not found"}, 404)
                    return
                token = int(parts[5])
                headers = {}
                if parts[2] == "queued":
                    q.machine.wait_past_queued(server.queue_poll_s)
                    doc = server.queued_doc(q, token)
                else:
                    q.machine.wait_done(server.queue_poll_s)
                    doc = server.executing_doc(q, token)
                    if q.machine.is_done():
                        for k, v in q.set_session.items():
                            headers["X-Presto-Set-Session"] = f"{k}={v}"
                        if q.started_txn:
                            headers["X-Presto-Started-Transaction-Id"] = \
                                q.started_txn
                        if q.clear_txn:
                            headers["X-Presto-Clear-Transaction-Id"] = "true"
                self._send(doc, headers=headers)
                return
            if parts == ["v1", "cluster"]:
                # fleet overview: live query/task progress + per-worker
                # liveness/occupancy (ClusterStatsResource analog; the
                # document scripts/ptop.py renders)
                self._send(server.cluster_doc())
                return
            if parts == ["v1", "datapath"]:
                # cluster-merged per-hop byte/throughput ledger with
                # roofline bottleneck verdicts (exec/datapath.py)
                self._send(server.datapath_doc())
                return
            if parts == ["v1", "accuracy"]:
                # cluster-merged per-plan-node estimate-vs-actual
                # ledger with misestimate verdicts (exec/accuracy.py)
                self._send(server.accuracy_doc())
                return
            if parts == ["v1", "history"]:
                # cluster-merged completed-query archive (the perf
                # sentinel's raw material; server/history.py)
                self._send(server.history_doc())
                return
            if parts == ["v1", "failpoint"]:
                # fault-injection admin surface (mirrors the worker's)
                self._send(failpoints.admin_get_doc())
                return
            if len(parts) == 3 and parts[:2] == ["v1", "trace"]:
                doc = server.trace_doc(parts[2])
                self._send(doc if doc else
                           {"error": f"no trace for {parts[2]} (is a "
                                     f"tracer installed?)"},
                           200 if doc else 404)
                return
            if len(parts) == 3 and parts[:2] == ["v1", "query"]:
                doc = server.admin_doc(parts[2])
                self._send(doc if doc else {"error": "not found"},
                           200 if doc else 404)
                return
            if parts == ["v1", "query"]:
                self._send(server.queries_doc())
                return
            if parts == ["v1", "info"]:
                self._send({"nodeVersion": {"version": "presto-tpu-0.4"},
                            "coordinator": True, "starting": False,
                            "uptime": "0m"})
                return
            if parts == ["v1", "metrics"]:
                from .metrics import (negotiate_exposition,
                                      render_prometheus)
                om, ctype = negotiate_exposition(
                    self.headers.get("Accept"))
                body = render_prometheus(server.metric_families(),
                                         openmetrics=om)
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return
            if parts[:1] == ["ui"]:
                self._send_html(_render_ui(server, parts[1:]))
                return
            self._send({"error": "not found"}, 404)

        def _send_html(self, html: str, code: int = 200):
            body = html.encode()
            self.send_response(code)
            self.send_header("Content-Type", "text/html; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_DELETE(self):  # noqa: N802
            parts = [p for p in self.path.split("/") if p]
            if parts[:2] == ["v1", "failpoint"] and len(parts) in (2, 3):
                self._send(failpoints.admin_delete(
                    parts[2] if len(parts) == 3 else None))
                return
            if len(parts) >= 5 and parts[:2] == ["v1", "statement"]:
                q = server.get_query(parts[3], parts[4])
                if q is None:
                    self._send({"error": "query not found"}, 404)
                    return
                server.cancel(q)
                self._send({"id": q.id, "canceled": True}, 200)
                return
            self._send({"error": "not found"}, 404)

    return Handler
