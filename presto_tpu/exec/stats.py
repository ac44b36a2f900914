"""RuntimeStats + structured query telemetry (OperatorStats/StageStats/
QueryStats).

Reference surface: presto-common's RuntimeStats (named add/merge
counters recorded anywhere and returned to clients in QueryStats), the
per-operator OperatorStats wall/cpu/rows plumbing (OperatorContext ->
TaskStats -> QueryStats merge chain), and the cross-worker merge the
coordinator performs when assembling QueryStats from TaskStatus.
Device-side per-operator timing inside one fused XLA program is not
observable (that's the point of fusion); stats here are the
host-visible boundaries: staging, XLA compile, device execute,
exchange pack/unpack, result fetch, rows/bytes -- the numbers EXPLAIN
ANALYZE, /v1/metrics, and the UI surface.

Structure:

  * ``RuntimeStats`` -- free-form named counters (unchanged API).
  * ``OperatorStats`` -- per plan node, where host-visible (scans,
    exchanges, the output root); fused interior nodes carry only
    rows when derivable.
  * ``StageStats`` -- one per host-visible stage boundary: ``staging``,
    ``compile`` (with FLOPs / bytes-accessed from XLA's
    ``cost_analysis``), ``execute``, ``exchange``, ``fetch``.
  * ``QueryStats`` -- the merge root shipped worker -> coordinator in
    TaskStatus and surfaced on the client protocol's ``stats`` field.

The merge law (``QueryStats.merge``) is associative AND commutative:
counters/sums add, ``max`` fields take max, stages/operators merge by
key. That is what lets per-task stats from any number of workers fold
in any order into one query-level document (the reference's
QueryStateMachine::updateQueryInfo aggregation contract).

Compile-time capture rides ``jax.monitoring``: a process-level listener
forwards ``/jax/core/compile/*`` event durations into the ambient
thread-local collector, so cache-hit dispatches naturally report zero
compile micros without instrumenting jit call sites.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from .accuracy import NodeAccuracy, merge_record_maps, \
    record_map_from_json, record_map_to_json
from .datapath import HopStats, hop_map_from_json, hop_map_to_json, \
    merge_hop_maps

__all__ = ["RuntimeStats", "OperatorStats", "StageStats",
           "QueryStats", "StatsCollector", "current_collector",
           "collecting", "joining", "stage", "span", "interval", "note",
           "note_max"]


@dataclasses.dataclass
class _Stat:
    count: int = 0
    total: float = 0.0
    max: float = 0.0

    def add(self, v: float):
        self.count += 1
        self.total += v
        self.max = max(self.max, v)


class RuntimeStats:
    def __init__(self):
        self._stats: Dict[str, _Stat] = {}
        self._lock = threading.Lock()

    def add(self, name: str, value: float):
        with self._lock:
            self._stats.setdefault(name, _Stat()).add(value)

    def merge(self, other: "RuntimeStats"):
        # lock both sides (ordered by id to avoid deadlock): _Stat.add is
        # multi-field, so reading `other` unlocked could tear mid-update
        first, second = sorted((self._lock, other._lock), key=id)
        with first, second:
            for k, s in other._stats.items():
                mine = self._stats.setdefault(k, _Stat())
                mine.count += s.count
                mine.total += s.total
                mine.max = max(mine.max, s.max)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {k: {"count": s.count, "total": round(s.total, 6),
                        "max": round(s.max, 6)}
                    for k, s in self._stats.items()}


# ---------------------------------------------------------------------------
# structured telemetry: OperatorStats / StageStats / QueryStats
# ---------------------------------------------------------------------------


def _us(seconds: float) -> int:
    return int(round(seconds * 1_000_000))


@dataclasses.dataclass
class OperatorStats:
    """Per-plan-node stats at the host-visible granularity (the
    OperatorStats analog; interior fused nodes carry rows only when the
    planner can derive them)."""
    node_id: str
    node_type: str = ""
    output_rows: int = 0
    output_bytes: int = 0
    wall_us: int = 0
    task_count: int = 1

    def merge(self, other: "OperatorStats") -> "OperatorStats":
        assert self.node_id == other.node_id, \
            f"merging operators {self.node_id} != {other.node_id}"
        return OperatorStats(
            node_id=self.node_id,
            node_type=self.node_type or other.node_type,
            output_rows=self.output_rows + other.output_rows,
            output_bytes=self.output_bytes + other.output_bytes,
            wall_us=self.wall_us + other.wall_us,
            task_count=self.task_count + other.task_count)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, doc: dict) -> "OperatorStats":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in doc.items() if k in known})


@dataclasses.dataclass
class StageStats:
    """One host-visible stage boundary: staging, compile, execute,
    exchange pack/unpack, fetch. ``flops``/``bytes_accessed`` come from
    XLA's ``cost_analysis`` of the jitted program (compile stage)."""
    name: str
    wall_us: int = 0
    compile_us: int = 0
    invocations: int = 0
    rows: int = 0
    bytes: int = 0
    flops: float = 0.0
    bytes_accessed: float = 0.0
    max_wall_us: int = 0

    def merge(self, other: "StageStats") -> "StageStats":
        assert self.name == other.name, \
            f"merging stages {self.name} != {other.name}"
        return StageStats(
            name=self.name,
            wall_us=self.wall_us + other.wall_us,
            compile_us=self.compile_us + other.compile_us,
            invocations=self.invocations + other.invocations,
            rows=self.rows + other.rows,
            bytes=self.bytes + other.bytes,
            flops=self.flops + other.flops,
            bytes_accessed=self.bytes_accessed + other.bytes_accessed,
            max_wall_us=max(self.max_wall_us, other.max_wall_us))

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, doc: dict) -> "StageStats":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in doc.items() if k in known})


@dataclasses.dataclass
class QueryStats:
    """The merge root: per-task stats fold into per-query stats through
    ``merge()`` (associative + commutative), shipped worker ->
    coordinator through the task status path and surfaced on the client
    protocol's ``stats`` field."""
    wall_us: int = 0
    output_rows: int = 0
    output_bytes: int = 0
    peak_memory_bytes: int = 0
    task_count: int = 1
    stages: Dict[str, StageStats] = dataclasses.field(default_factory=dict)
    operators: Dict[str, OperatorStats] = \
        dataclasses.field(default_factory=dict)
    # free-form summed counters (cache hits, capacity reruns, ...; for a
    # statement over a mesh `mesh_chips`, its program's `exchanges`,
    # `exchange.<kind>`, `exchange_bytes`, `exchange_slot_bytes`, kept
    # with the compiled plan and so noted on a plan-cache hit too, and
    # `exchange_row_bytes`, read beside the status word); merged by
    # addition
    counters: Dict[str, int] = dataclasses.field(default_factory=dict)
    # per-hop data-path ledger (exec/datapath.py): bytes/wall per hop,
    # merged by HopStats' own sums-add/maxes-max law -- this is how a
    # worker's hop slice stitches to the coordinator's through the
    # existing task-status path
    datapath: Dict[str, HopStats] = dataclasses.field(default_factory=dict)
    # per-plan-node estimate-vs-actual ledger (exec/accuracy.py):
    # est/actual rows or bytes per node, merged by NodeAccuracy's own
    # estimates-max/rows-add/peaks-max law -- worker slices of one
    # query stitch to the coordinator's through the same path
    accuracy: Dict[str, NodeAccuracy] = \
        dataclasses.field(default_factory=dict)

    # -- convenience accessors (the EXPLAIN ANALYZE / CLI summary view) --

    def stage_us(self, name: str) -> int:
        s = self.stages.get(name)
        return s.wall_us if s else 0

    @property
    def compile_us(self) -> int:
        return sum(s.compile_us for s in self.stages.values())

    @property
    def execute_us(self) -> int:
        return self.stage_us("execute")

    def merge(self, other: "QueryStats") -> "QueryStats":
        stages = dict(self.stages)
        for k, s in other.stages.items():
            stages[k] = stages[k].merge(s) if k in stages else s
        operators = dict(self.operators)
        for k, o in other.operators.items():
            operators[k] = operators[k].merge(o) if k in operators else o
        counters = dict(self.counters)
        for k, v in other.counters.items():
            counters[k] = counters.get(k, 0) + v
        return QueryStats(
            wall_us=self.wall_us + other.wall_us,
            output_rows=self.output_rows + other.output_rows,
            output_bytes=self.output_bytes + other.output_bytes,
            peak_memory_bytes=max(self.peak_memory_bytes,
                                  other.peak_memory_bytes),
            task_count=self.task_count + other.task_count,
            stages=stages, operators=operators, counters=counters,
            datapath=merge_hop_maps(self.datapath, other.datapath),
            accuracy=merge_record_maps(self.accuracy, other.accuracy))

    def to_json(self) -> dict:
        return {"wallUs": self.wall_us,
                "outputRows": self.output_rows,
                "outputBytes": self.output_bytes,
                "peakMemoryBytes": self.peak_memory_bytes,
                "taskCount": self.task_count,
                "stages": {k: s.to_json() for k, s in self.stages.items()},
                "operators": {k: o.to_json()
                              for k, o in self.operators.items()},
                "counters": dict(self.counters),
                "datapath": hop_map_to_json(self.datapath),
                "accuracy": record_map_to_json(self.accuracy)}

    @classmethod
    def from_json(cls, doc: dict) -> "QueryStats":
        return cls(
            wall_us=int(doc.get("wallUs", 0)),
            output_rows=int(doc.get("outputRows", 0)),
            output_bytes=int(doc.get("outputBytes", 0)),
            peak_memory_bytes=int(doc.get("peakMemoryBytes", 0)),
            task_count=int(doc.get("taskCount", 1)),
            stages={k: StageStats.from_json(s)
                    for k, s in doc.get("stages", {}).items()},
            operators={k: OperatorStats.from_json(o)
                       for k, o in doc.get("operators", {}).items()},
            counters={k: int(v)
                      for k, v in doc.get("counters", {}).items()},
            datapath=hop_map_from_json(doc.get("datapath", {})),
            # old-doc tolerance: records shipped before this field
            # existed deserialize to the empty map (merge identity);
            # a key this version no longer reads (an older worker's
            # `timeline`) is ignored
            accuracy=record_map_from_json(doc.get("accuracy", {})))

    def summary(self) -> str:
        """One-paragraph human summary (the CLI --stats shape)."""
        parts = [f"wall {self.wall_us / 1e6:.3f}s"]
        for name in ("staging", "compile", "execute", "exchange", "fetch"):
            us = self.stage_us(name)
            if us or name in self.stages:
                parts.append(f"{name} {us / 1e6:.3f}s")
        cu = self.compile_us
        if cu:
            parts.append(f"(xla compile {cu / 1e6:.3f}s)")
        parts.append(f"rows {self.output_rows}")
        parts.append(f"bytes {self.output_bytes}")
        if self.peak_memory_bytes:
            parts.append(f"peak mem {self.peak_memory_bytes >> 20}MB")
        if self.task_count > 1:
            parts.append(f"tasks {self.task_count}")
        return ", ".join(parts)


# ---------------------------------------------------------------------------
# ambient collector: stage spans + jax compile-time capture
# ---------------------------------------------------------------------------


class StatsCollector:
    """Per-statement collection context: the one span seam.

    The module's ``stage(name)`` and ``span(name)`` time an interval on
    the ambient collector and hand it to two sinks: the collector's span
    record ``(name, start_s, end_s, attrs, span, parent)`` -- summed
    into ``QueryStats.stages`` for a stage and shipped to ``/v1/trace``
    by :meth:`close` -- and the profiler's trace, as a ``TraceMe`` named
    ``presto:<name>`` (nanoseconds while no profile runs), so the
    program's spans share the device trace's clock and can never
    disagree with ``QueryStats`` about what they cover. ``parent`` is
    the span that was open on the calling thread when this one opened.
    Compile durations from jax.monitoring land on whichever stage is
    open when XLA compiles (the execute dispatch), attributed to the
    ``compile`` stage."""

    def __init__(self, query_id: str = "query"):
        self.query_id = query_id
        self.stats = QueryStats()
        # (name, start_s, end_s, attrs, span, parent): span numbers are
        # this collector's own; close() maps them to span ids
        self.spans: List[tuple] = []
        self._next_span = 0
        self._compile_s = 0.0
        self.closed = False
        self._lock = threading.Lock()

    # -- spans ------------------------------------------------------------

    def _new_span(self) -> int:
        with self._lock:
            self._next_span += 1
            return self._next_span

    def _record_span(self, name: str, start_s: float, end_s: float,
                     attrs: Optional[dict] = None,
                     span: Optional[int] = None,
                     parent: Optional[int] = None) -> None:
        if span is None:
            span = self._new_span()
        with self._lock:
            self.spans.append((name, start_s, end_s, dict(attrs or {}),
                               span, parent))

    def record_stage(self, name: str, start_s: float, end_s: float,
                     attrs: Optional[dict] = None,
                     span: Optional[int] = None,
                     parent: Optional[int] = None, **fields) -> None:
        wall = _us(end_s - start_s)
        with self._lock:
            st = self.stats.stages.get(name)
            if st is None:
                st = self.stats.stages[name] = StageStats(name)
            st.wall_us += wall
            st.max_wall_us = max(st.max_wall_us, wall)
            st.invocations += 1
            for k, v in fields.items():
                setattr(st, k, getattr(st, k) + v)
        self._record_span(name, start_s, end_s,
                          {**fields, **(attrs or {})}, span, parent)

    def bump_stage(self, name: str, **fields) -> None:
        """Add to a stage's summed fields without opening a timing span
        (rows/bytes learned after the span closed)."""
        with self._lock:
            st = self.stats.stages.get(name)
            if st is None:
                st = self.stats.stages[name] = StageStats(name)
            for k, v in fields.items():
                setattr(st, k, getattr(st, k) + v)

    def add_compile_seconds(self, seconds: float) -> None:
        with self._lock:
            self._compile_s += seconds

    def take_compile_us(self) -> int:
        """Drain accumulated jax compile time (monitoring events)."""
        with self._lock:
            us = _us(self._compile_s)
            self._compile_s = 0.0
            return us

    def stage_span_start(self, name: str) -> Optional[float]:
        """Start time of the most recent recorded span for `name`
        (anchors the synthetic compile span inside the execute window
        it actually happened in)."""
        with self._lock:
            for rec in reversed(self.spans):
                if rec[0] == name:
                    return rec[1]
        return None

    def operator(self, node_id: str, node_type: str = "", **fields) -> None:
        with self._lock:
            op = self.stats.operators.get(node_id)
            if op is None:
                op = self.stats.operators[node_id] = \
                    OperatorStats(node_id, node_type)
            elif node_type and not op.node_type:
                op.node_type = node_type
            for k, v in fields.items():
                setattr(op, k, getattr(op, k) + v)

    def note(self, name: str, delta: int = 1) -> None:
        """Bump a free-form summed counter (QueryStats.counters)."""
        with self._lock:
            self.stats.counters[name] = \
                self.stats.counters.get(name, 0) + delta

    def note_max(self, name: str, value: int) -> None:
        """Raise a counter to `value` where it is lower: a statement's
        largest of something (``program_hbm_bytes``). Across tasks the
        merge law still adds."""
        with self._lock:
            self.stats.counters[name] = \
                max(self.stats.counters.get(name, 0), value)

    def close(self, trace=None) -> None:
        """Called once, by whoever created the collector, when the
        statement's last span has closed, whether the statement
        finished or failed (`closed` is then set: an owner with two
        ways out asks before it calls). Ships the collected spans
        through the tracing emission seam, each under the span that
        caused it; the top-level ones hang under the enclosing
        task/query span. `trace` is a TraceContext (trace id + that
        parent span), a plain grouping string (legacy) or None (the
        query id). emit_span delivers to the process tracer AND any
        thread-local SpanBuffer, and never raises (broken tracers are
        counted, not fatal). Each stage's wall then feeds the
        ``presto_tpu_stage_seconds`` histogram of /v1/metrics,
        exemplar'd with the trace id so a p99 execute spike links to
        its waterfall."""
        from ..server.metrics import observe_histogram
        from ..server.tracing import TraceContext, emit_span, new_span_id
        self.closed = True
        if isinstance(trace, TraceContext):
            tid, root = trace.trace_id, trace.span_id
        else:
            tid, root = trace or self.query_id, None
        with self._lock:
            spans = list(self.spans)
        ids = {rec[4]: new_span_id() for rec in spans}
        for name, start_s, end_s, attrs, span, parent in spans:
            emit_span(tid, f"stage.{name}", start_s, end_s, attrs,
                      span_id=ids[span],
                      parent_id=ids.get(parent, root))
        with self._lock:
            walls = [(name, st.wall_us)
                     for name, st in self.stats.stages.items()]
        for name, wall_us in walls:
            if wall_us:
                observe_histogram("presto_tpu_stage_seconds",
                                  wall_us / 1e6, labels={"stage": name},
                                  trace_id=tid)


class _SpanTimer:
    def __init__(self, collector: Optional[StatsCollector], name: str,
                 fields: dict, attrs: Optional[dict], is_stage: bool):
        self.c = collector
        self.name = name
        self.fields = fields
        self.attrs = attrs
        self.is_stage = is_stage

    def __enter__(self):
        c = self.c
        if c is not None:
            stack = _open_spans()
            self.parent = stack[-1][1] if stack and stack[-1][0] is c \
                else None
            self.span = c._new_span()
            stack.append((c, self.span, self.name))
        self._trace = TraceAnnotation("presto:" + self.name,
                                      **(self.attrs or {}))
        self._trace.__enter__()
        self.t0 = time.time()
        return self

    def __exit__(self, *exc):
        t1 = self.t1 = time.time()
        self._trace.__exit__(*exc)
        c = self.c
        if c is None:
            return False
        stack = _open_spans()
        if stack and stack[-1][:2] == (c, self.span):
            stack.pop()
        if self.is_stage:
            c.record_stage(self.name, self.t0, t1, self.attrs, self.span,
                           self.parent, **self.fields)
        else:
            c._record_span(self.name, self.t0, t1, self.attrs, self.span,
                           self.parent)
        return False


_tls = threading.local()


def current_collector() -> Optional[StatsCollector]:
    return getattr(_tls, "collector", None)


def _open_spans() -> list:
    """This thread's open spans, innermost last: (collector, span,
    name)."""
    stack = getattr(_tls, "open", None)
    if stack is None:
        stack = _tls.open = []
    return stack


def stage(name: str, attrs: Optional[dict] = None, **fields):
    """Open a span of the ambient collector that is also summed into
    ``QueryStats.stages[name]``; `fields` add to the stage's counters,
    `attrs` ride the span. With no collector ambient, only the
    profiler's annotation."""
    return _SpanTimer(current_collector(), name, fields, attrs, True)


def span(name: str, attrs: Optional[dict] = None):
    """A span of the ambient collector that is recorded and annotated
    but summed nowhere (the datapath hops: their sums live in
    ``QueryStats.datapath``); see stage."""
    return _SpanTimer(current_collector(), name, {}, attrs, False)


def interval(name: str, start_s: float, end_s: float) -> None:
    """A span of the ambient collector from two readings of
    `time.time()` taken elsewhere, under this thread's open span: an
    interval whose work ran on threads that have no collector."""
    c = current_collector()
    if c is None:
        return
    stack = _open_spans()
    parent = stack[-1][1] if stack and stack[-1][0] is c else None
    c._record_span(name, start_s, end_s, parent=parent)


def note(name: str, delta: int = 1) -> None:
    """Bump a counter of the ambient collector, where there is one."""
    c = current_collector()
    if c is not None:
        c.note(name, delta)


def note_max(name: str, value: int) -> None:
    """Raise a counter of the ambient collector, where there is one."""
    c = current_collector()
    if c is not None:
        c.note_max(name, value)


class collecting:
    """Install `collector` as the ambient collector for this thread."""

    def __init__(self, collector: StatsCollector):
        self.collector = collector

    def __enter__(self):
        self.prev = current_collector()
        _tls.collector = self.collector
        _ensure_compile_listener()
        return self.collector

    def __exit__(self, *exc):
        _tls.collector = self.prev
        return False


class joining:
    """The statement's collector for this thread: the ambient one where
    a caller up the stack opened it (the statement server, ``sql()``,
    an outer ``run_query``), else a new one that is ambient inside the
    block and shipped to the tracer when the block ends, by a return
    or by an exception: a failed statement's spans are the ones its
    post-mortem wants. `trace` is what :meth:`StatsCollector.close`
    takes."""

    def __init__(self, query_id: str = "query", trace=None):
        self.query_id = query_id
        self.trace = trace
        self._own: Optional[collecting] = None

    def __enter__(self) -> StatsCollector:
        c = current_collector()
        if c is None:
            self._own = collecting(StatsCollector(self.query_id))
            c = self._own.__enter__()
        return c

    def __exit__(self, exc_type, *exc):
        if self._own is not None:
            self._own.__exit__(exc_type, *exc)
            self._own.collector.close(self.trace)
        return False


_listener_installed = False
_listener_lock = threading.Lock()

# jax.monitoring duration events counted as XLA compilation work.
# Deliberately NOT a "/jax/core/compile/" prefix match: the
# jaxpr_trace_duration events fire NESTED inside MLIR lowering (inner
# jits trace while the outer lowers), so summing every event
# double-counts and compile_us can exceed the dispatch wall that
# contains it. MLIR module conversion + backend compile are the two
# sequential top-level phases; the runner additionally clamps the sum
# to the enclosing execute wall as a backstop against nested-jit
# lowering overlap.
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = frozenset([
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    _BACKEND_COMPILE,
])


def _ensure_compile_listener() -> None:
    """Register the process-wide jax.monitoring listeners exactly once.
    Durations and counts route to the calling thread's ambient
    collector (jit compiles on the dispatching thread), so concurrent
    queries don't cross-attribute. ``xla_compiles`` counts the backend
    compile calls of the whole statement (a persistent-cache read is
    one too) and ``compile_cache_reads`` those the cache answered: a
    statement of a warmed server reads 0 and 0, one that reads 1 and 1
    traces a program anew every time (a fresh ``jax.jit``, a shape that
    varies) and is saved by the cache, and the difference is what XLA
    built. The benchmark's per-layer metrics of the same names are
    their mean per statement."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        try:
            import jax.monitoring as _mon

            def _on_duration(name, seconds, **_kw):
                if name not in _COMPILE_EVENTS:
                    return
                c = current_collector()
                if c is None:
                    return
                if name == _BACKEND_COMPILE:
                    c.note("xla_compiles")
                # the seconds are the `compile` stage's, carved out of
                # the `execute` wall they fall in: a planner's constant
                # fold compiles too, and is counted, not carved
                if any(col is c and open_name == "execute"
                       for col, _span, open_name in _open_spans()):
                    c.add_compile_seconds(float(seconds))

            def _on_event(name, **_kw):
                if name == "/jax/compilation_cache/cache_hits":
                    note("compile_cache_reads")

            _mon.register_event_duration_secs_listener(_on_duration)
            _mon.register_event_listener(_on_event)
        except Exception:  # noqa: BLE001 - telemetry must never break exec
            pass
        _listener_installed = True
