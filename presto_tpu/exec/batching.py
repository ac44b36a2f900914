"""Concurrent-query batching executor: N queries, ONE dispatch.

The throughput gap this closes: the "millions of users" workload is
thousands of small concurrent queries -- dashboards and point lookups
-- sharing a handful of plan shapes, yet every statement today stages
and dispatches its kernels alone. Like "Accelerating Presto with GPUs"
(PAPERS.md), the win is keeping the accelerator saturated with batched
work instead of serialized per-query dispatches: queries whose plans
differ only in literals collapse into one vmapped program execution.

Model:

  * **Parameterization** (:func:`parameterize_plan`): a prepared plan's
    Filter/Project expressions are rewritten bottom-up, lifting every
    Constant in a value-safe position (comparison/arithmetic arguments,
    BETWEEN bounds, IN list members; fixed-width non-string types only)
    into a ``BatchParam(index)`` leaf. The rewritten tree is the
    *template*; the lifted values are the query's *parameter vector*.
    Constants the compiler specializes at trace time (LIKE patterns,
    date_add units, casts of structure) are never lifted, so the
    template traces exactly like the original plan.

  * **Batch key**: ``(plan_fingerprint(template), kernel-mode envs, sf,
    join capacity)`` -- the exact identity ``exec/plan_cache.py``
    already keys on. Queries co-batch ONLY on key
    equality: differing string literals, differing plan shapes, or a
    kernel-mode env flip produce different keys by construction.

  * **Formation window**: the first arrival of a HOT fingerprint leads
    a forming batch and waits ``batch_window_ms`` for followers (or
    until ``batch_max_size``); cold fingerprints never pay the delay.
    Hotness is the fingerprint's recent submission frequency, seeded
    from the query-history archive's per-fingerprint counts
    (server/history.py) so a dashboard fingerprint is hot from the
    first poll after a restart.

  * **Batched dispatch**: the template compiles once through the plan
    cache (hit/miss accounting unchanged); the executable is wrapped as
    ``jax.vmap(fn, in_axes=(None, 0))`` -- scan batches broadcast,
    parameter vectors mapped -- and jitted, so XLA sees one program
    with a leading batch dimension. Scan staging happens ONCE per
    batch. Results fan back per member by slicing the batch axis;
    every member's rows are bit-identical to its serial execution
    (pinned by tests and the chaos ``batch`` round).

  * **Collapse**: any overflow flag, the ``dispatcher.batch_collapse``
    failpoint, or an unexpected batched-dispatch error falls back to
    serial per-query dispatch of every member (counted per reason on
    ``presto_tpu_batch_collapses_total``) -- batching is a fast path,
    never a correctness dependency.

Gating: session property ``query_batching`` / env ``PRESTO_TPU_BATCHING``
(registered in KERNEL_MODE_ENVS; the serial A/B control the loadgen
benchmark measures against).
"""

from __future__ import annotations

import collections
import dataclasses
import re
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from .. import types as T
from ..expr import ir as E
from ..expr.compile import bound_params
from ..plan import nodes as N
from ..utils.locks import OrderedLock
from .planner import split_flags
from .stats import collecting, stage

__all__ = ["BATCHING_ENV", "batching_enabled", "parameterize_plan",
           "BatchingExecutor", "get_batching_executor",
           "set_batching_executor", "batching_totals",
           "batching_snapshot", "batch_size_of", "template_fp_of",
           "clear_batching"]

BATCHING_ENV = "PRESTO_TPU_BATCHING"

# literal-masking for the pre-plan hotness gate: numbers and quoted
# strings collapse to "?" so every member of a parameterized family
# shares one shape key WITHOUT planning (the gate only decides whether
# planning for the batched path is worth paying at all)
_SHAPE_RE = re.compile(r"'[^']*'|\b\d+(?:\.\d+)?\b")

# collapse reasons with a stable /v1/metrics zero shape
COLLAPSE_REASONS = ("failpoint", "overflow", "error")


def batching_enabled(session) -> bool:
    """Session property ``query_batching``; process default from
    PRESTO_TPU_BATCHING (default ON). Spelled literally so tpulint R001
    proves the knob is registered in KERNEL_MODE_ENVS. Both the env and
    the session value parse with the registry's bool coercion, so
    'off'/'False'/'no' disable like '0' does."""
    import os
    from ..utils.config import _parse_bool, session_flag
    env_on = _parse_bool(os.environ.get("PRESTO_TPU_BATCHING", "1"))
    return session_flag(session, "query_batching", env_on)


# ---------------------------------------------------------------------------
# plan parameterization
# ---------------------------------------------------------------------------

# Calls whose Constant arguments are pure VALUES: evaluation reads them
# lane-wise, never as trace-time structure, so a BatchParam substitutes
# exactly. Everything else (LIKE patterns, date_add units, sequence
# bounds, row_field indices, ...) keeps its Constants and stays part of
# the template -- queries differing there never co-batch.
_SAFE_CALLS = frozenset({"eq", "ne", "lt", "le", "gt", "ge",
                         "add", "subtract", "multiply", "divide",
                         "modulus"})


def _parameterizable_type(ty: T.Type) -> bool:
    """Fixed-width scalar types whose constant blocks are a dtype'd
    broadcast -- exactly what a traced parameter scalar reproduces.
    Strings (shape-bearing) and long decimals (limb pairs) stay
    literal."""
    if ty.is_string or ty == T.UNKNOWN:
        return False
    if ty.base in ("array", "map", "row"):
        return False
    if ty.is_decimal and not ty.is_short_decimal:
        return False
    try:
        return ty.is_fixed_width
    except Exception:  # noqa: BLE001 - exotic logical type
        return False


def _normalize_param(c: E.Constant) -> Tuple[object, bool]:
    """Constant -> (host value, is_null), mirroring the conversions
    compile._constant_block applies at trace time (dates spelled as
    strings become epoch days) so the parameterized execution stages
    the same scalar the literal would have."""
    if c.value is None:
        return (False if c.type.base == "boolean" else 0), True
    v = c.value
    if c.type.base == "date" and isinstance(v, str):
        v = int((np.datetime64(v)
                 - np.datetime64("1970-01-01")).astype(int))
    if c.type.base == "boolean":
        v = bool(v)
    return v, False


def _null_hint(args) -> Optional[T.Type]:
    """The type an UNTYPED NULL literal (``x = NULL`` plans a
    Constant of UNKNOWN type) is lifted at: its first typed sibling.
    A NULL parameter at the sibling's type evaluates to the same
    all-NULL comparison, and ``x = NULL`` then shares a template with
    ``x = 42`` -- the NULL-parameter co-batching case."""
    for a in args:
        if a.type != T.UNKNOWN:
            return a.type
    return None


def _extract_expr(expr: E.RowExpression, params: List, liftable: bool,
                  hint: Optional[T.Type] = None) -> E.RowExpression:
    """Rewrite one expression tree, lifting value-position Constants
    into BatchParam leaves (preorder index order)."""
    if isinstance(expr, E.Constant):
        ty = expr.type
        if ty == T.UNKNOWN and expr.value is None and hint is not None:
            ty = hint
        if liftable and _parameterizable_type(ty):
            idx = len(params)
            params.append((_normalize_param(
                E.Constant(ty, expr.value)), ty))
            return E.BatchParam(ty, idx)
        return expr
    if isinstance(expr, E.Call):
        ok = expr.name.lower() in _SAFE_CALLS
        h = _null_hint(expr.arguments) if ok else None
        args = tuple(_extract_expr(a, params, ok, hint=h)
                     for a in expr.arguments)
        if all(a is b for a, b in zip(args, expr.arguments)):
            return expr
        return E.Call(expr.type, expr.name, args)
    if isinstance(expr, E.SpecialForm):
        if expr.form in ("BETWEEN", "IN"):
            # args[0] is the probed value (recurse normally); the
            # bounds / list members are pure values
            h = _null_hint(expr.arguments)
            args = tuple([_extract_expr(expr.arguments[0], params, False)]
                         + [_extract_expr(a, params, True, hint=h)
                            for a in expr.arguments[1:]])
        else:
            args = tuple(_extract_expr(a, params, False)
                         for a in expr.arguments)
        if all(a is b for a, b in zip(args, expr.arguments)):
            return expr
        return E.SpecialForm(expr.type, expr.form, args)
    # Lambda bodies / lambda variables: leave untouched (higher-order
    # kernels specialize their structure at trace time)
    return expr


def parameterize_plan(root: N.PlanNode
                      ) -> Tuple[N.PlanNode, List[Tuple[Tuple, T.Type]]]:
    """Prepared plan -> (template plan, parameter vector). The template
    shares every node the rewrite did not touch (scan leaves keep their
    width annotations and identity); parameters list ((value, is_null),
    type) in deterministic DFS-preorder-of-expressions order, so two
    plannings of the same SQL shape extract identically-ordered
    vectors. A plan with no liftable literal returns (root, [])."""
    params: List[Tuple[Tuple, T.Type]] = []
    memo: Dict[int, N.PlanNode] = {}

    def walk(n: N.PlanNode) -> N.PlanNode:
        if id(n) in memo:
            return memo[id(n)]
        new_sources = [walk(s) for s in n.sources]
        src_changed = any(a is not b
                          for a, b in zip(new_sources, n.sources))
        if isinstance(n, N.FilterNode):
            pred = _extract_expr(n.predicate, params, False)
            if pred is not n.predicate or src_changed:
                out = dataclasses.replace(n, source=new_sources[0],
                                          predicate=pred)
            else:
                out = n
        elif isinstance(n, N.ProjectNode):
            exprs = [_extract_expr(e, params, False)
                     for e in n.expressions]
            if src_changed or any(a is not b for a, b
                                  in zip(exprs, n.expressions)):
                out = dataclasses.replace(n, source=new_sources[0],
                                          expressions=exprs)
            else:
                out = n
        elif src_changed:
            from ..plan.rules import _replace_sources
            out = _replace_sources(n, new_sources)
        else:
            out = n
        memo[id(n)] = out
        return out

    return walk(root), params


# ---------------------------------------------------------------------------
# process totals (server/metrics.py batching_families reads these)
# ---------------------------------------------------------------------------

_TOTALS_LOCK = OrderedLock("batching._TOTALS_LOCK")
_TOTALS = {"batches": 0, "batched_queries": 0, "last_batch_size": 0,
           "max_batch_size": 0, "solo_dispatches": 0}
_COLLAPSES = {r: 0 for r in COLLAPSE_REASONS}

# query id -> size of the batch that served it (0/absent = unbatched);
# system.queries' batch_size column reads it. Bounded.
_QUERY_BATCH: "collections.OrderedDict[str, int]" = \
    collections.OrderedDict()
# query id -> template fingerprint (batchable queries, batched or not);
# the history archive attaches it to records so the formation window
# can be driven by archived per-fingerprint frequency
_QUERY_TEMPLATE: "collections.OrderedDict[str, str]" = \
    collections.OrderedDict()
_QUERY_MAP_MAX = 1024

# tpulint C001: module-global write barrier (the process-counter
# idiom; _EXECUTOR is the singleton swap under its own lock)
_GUARDED_BY = {"_TOTALS_LOCK": ("_TOTALS", "_COLLAPSES",
                                "_QUERY_BATCH", "_QUERY_TEMPLATE"),
               "_EXEC_LOCK": ("_EXECUTOR",)}


def _note_query(table: "collections.OrderedDict", query_id: str,
                value) -> None:
    with _TOTALS_LOCK:
        table[query_id] = value
        table.move_to_end(query_id)
        while len(table) > _QUERY_MAP_MAX:
            table.popitem(last=False)


def batch_size_of(query_id: str) -> int:
    with _TOTALS_LOCK:
        return _QUERY_BATCH.get(query_id, 0)


def template_fp_of(query_id: str) -> Optional[str]:
    with _TOTALS_LOCK:
        return _QUERY_TEMPLATE.get(query_id)


def batching_totals() -> dict:
    with _TOTALS_LOCK:
        out = dict(_TOTALS)
        out["collapses"] = dict(_COLLAPSES)
        return out


def reset_batching_totals() -> None:
    """Zero the process counters without dropping the executor (and
    its warm compiled-program cache) -- phase boundaries in benchmarks
    and tests that only assert deltas."""
    with _TOTALS_LOCK:
        for k in _TOTALS:
            _TOTALS[k] = 0
        for k in _COLLAPSES:
            _COLLAPSES[k] = 0
        _QUERY_BATCH.clear()
        _QUERY_TEMPLATE.clear()


def clear_batching() -> None:
    """Reset process totals + the executor (tests isolate state)."""
    global _EXECUTOR
    reset_batching_totals()
    with _EXEC_LOCK:
        _EXECUTOR = None


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------


class _Pending:
    """One member of a forming batch."""
    __slots__ = ("values", "root", "session", "query_id", "trace_id",
                 "event", "result", "error")

    def __init__(self, values, root, session, query_id, trace_id):
        self.values = values          # [(value, is_null), ...]
        self.root = root              # this query's OWN prepared plan
        self.session = session
        self.query_id = query_id
        self.trace_id = trace_id
        self.event = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None


class _Forming:
    """A batch being collected for one key (leader waits the window)."""
    __slots__ = ("key", "entries", "sealed", "full")

    def __init__(self, key):
        self.key = key
        self.entries: List[_Pending] = []
        self.sealed = False
        self.full = threading.Event()


class BatchingExecutor:
    """Process-wide batching executor in the statement dispatch path.

    ``try_execute`` returns a QueryResult when the query was served by
    a formed batch (leader or follower), or None when the caller should
    run the normal serial path (not batchable, batching disabled, or no
    batch formed). Thread-safe; statement _run threads are the
    callers."""

    # tpulint C001: formation/inflight/template registries are shared
    # across every statement _run thread
    _GUARDED_BY = {"_lock": ("_forming", "_inflight", "_recent",
                             "_shape_recent", "_vmapped", "_staged")}

    def __init__(self, window_ms: float = 5.0, max_batch: int = 64,
                 hot_min: int = 2, hot_window_s: float = 30.0,
                 follower_timeout_s: float = 300.0,
                 max_form_s: float = 1.0, max_inflight: int = 8):
        self.window_ms = window_ms
        self.max_batch = max_batch
        self.hot_min = hot_min
        self.hot_window_s = hot_window_s
        self.follower_timeout_s = follower_timeout_s
        # upper bound on formation wait while chained behind an
        # in-flight dispatch (the latency guardrail under saturation)
        self.max_form_s = max_form_s
        # concurrent dispatches allowed per key: dispatch itself is
        # serialized by the plan-cache call lock, but EXECUTION is
        # async -- a small overlap keeps the device fed while the next
        # batch forms, and the cap keeps occupancy adaptive (a full
        # pipeline makes the next leader keep collecting)
        self.max_inflight = max_inflight
        self._lock = OrderedLock("batching.BatchingExecutor._lock")
        self._forming: Dict[tuple, _Forming] = {}
        # key -> count of batched dispatches currently executing: a
        # forming batch keeps COLLECTING while its key's dispatch
        # pipeline is full (the inference-server chaining pattern --
        # occupancy adapts to load: under saturation batches chain
        # back-to-back and the formation window only bounds the idle
        # case), up to max_inflight overlapped executions per key
        self._inflight: Dict[tuple, int] = {}
        # fingerprint -> deque of recent submission times (hotness)
        self._recent: "collections.OrderedDict[str, collections.deque]" \
            = collections.OrderedDict()
        # masked text shape -> recent submissions: the pre-plan gate
        # (one-off statements skip the batched path's plan walk)
        self._shape_recent: \
            "collections.OrderedDict[str, collections.deque]" = \
            collections.OrderedDict()
        # batch key -> jitted vmapped wrapper (the per-shape XLA cache
        # lives inside the one jitted callable)
        self._vmapped: "collections.OrderedDict[tuple, tuple]" = \
            collections.OrderedDict()
        self._vmapped_max = 64
        # (batch key, data versions) -> staged scan Batches: repeat
        # batches of a hot template skip host->HBM staging entirely,
        # guarded by the connectors' data_version seam (the same
        # contract the worker fragment cache keys on)
        self._staged: "collections.OrderedDict[tuple, list]" = \
            collections.OrderedDict()
        self._staged_max = 16
        # exact statement text -> (prepared, template, values, key):
        # zipfian traffic repeats hot literals verbatim, so the plan /
        # prepare / parameterize walk -- pure Python on the per-query
        # hot path -- is paid once per distinct text
        self._plan_memo: "collections.OrderedDict[tuple, tuple]" = \
            collections.OrderedDict()
        self._plan_memo_max = 2048

    # -- knobs resolved per query --------------------------------------

    def _window_s(self, session) -> float:
        from ..utils.config import session_value
        return float(session_value(session, "batch_window_ms",
                                   self.window_ms) or 0.0) / 1e3

    def _max_batch(self, session) -> int:
        from ..utils.config import session_value
        return max(int(session_value(session, "batch_max_size",
                                     self.max_batch)), 1)

    def _hot_min(self, session) -> int:
        from ..utils.config import session_value
        return int(session_value(session, "batch_hot_min", self.hot_min))

    # -- hotness -------------------------------------------------------

    def _note_window(self, table, key: str) -> int:
        """Record one event for `key` in a bounded sliding-window
        table; returns the recent count (this event included)."""
        now = time.time()
        cutoff = now - self.hot_window_s
        with self._lock:
            q = table.get(key)
            if q is None:
                q = table[key] = collections.deque(maxlen=4096)
                while len(table) > 512:
                    table.popitem(last=False)
            else:
                table.move_to_end(key)
            q.append(now)
            while q and q[0] < cutoff:
                q.popleft()
            return len(q)

    def _note_recent(self, fp: str) -> int:
        """Record one submission of `fp`; returns the recent count
        (this submission included)."""
        return self._note_window(self._recent, fp)

    def _hot(self, fp: str, session) -> bool:
        """Whether this fingerprint deserves a formation window: its
        recent in-process frequency, seeded by the history archive's
        per-fingerprint counts (a hot dashboard fingerprint pays zero
        cold starts after a restart)."""
        hot_min = self._hot_min(session)
        n = self._note_recent(fp)
        if hot_min <= 1 or n >= hot_min:
            return True
        try:
            from ..server.history import get_history_archive
            n += get_history_archive().batch_fingerprint_count(fp)
        except Exception:  # noqa: BLE001 - the archive is telemetry;
            pass           # hotness degrades to in-process counts
        return n >= hot_min

    # -- batch key -----------------------------------------------------

    @staticmethod
    def _batch_key(template_fp: str, sf: float,
                   join_capacity: int) -> tuple:
        from .plan_cache import _kernel_mode
        # the exact identity the plan cache keys on:
        # (structural fingerprint, kernel-mode envs) -- plus the scale
        # factor and join capacity that select the staged data/program
        return (template_fp, _kernel_mode(), float(sf),
                int(join_capacity))

    # -- the public seam ----------------------------------------------

    def try_execute(self, text: str, *, sf: float, session: Dict,
                    query_id: str, trace_id=None,
                    max_groups: Optional[int] = None,
                    join_capacity: Optional[int] = None,
                    catalog: Optional[str] = None):
        """Plan `text`, and when it is batchable and a batch forms,
        execute it batched and return this query's QueryResult. Returns
        None whenever the normal serial path should run instead. Past
        the hot-shape check the call is the statement's ``batch`` stage:
        ``batch.prepare`` is this path's own plan_sql + prepare_plan
        (memoized by exact text), ``batch.wait`` the formation window
        or the wait for a leader."""
        if not batching_enabled(session):
            return None
        hot_min = self._hot_min(session)
        if hot_min > 1 and \
                self._note_window(self._shape_recent,
                                  _SHAPE_RE.sub("?", text)) < hot_min:
            # cold text SHAPE (literals masked): stay on the pure
            # serial path without paying the batched path's plan walk
            # -- one-off ad-hoc statements cost one regex here, not a
            # second full planning
            return None
        with stage("batch"):
            return self._try_batched(
                text, sf=sf, session=session, query_id=query_id,
                trace_id=trace_id, max_groups=max_groups,
                join_capacity=join_capacity, catalog=catalog)

    def _try_batched(self, text: str, *, sf: float, session: Dict,
                     query_id: str, trace_id, max_groups: Optional[int],
                     join_capacity: Optional[int],
                     catalog: Optional[str]):
        try:
            with stage("batch.prepare"):
                prepared, template, values, key = self._prepare(
                    text, sf=sf, session=session,
                    max_groups=max_groups, join_capacity=join_capacity,
                    catalog=catalog)
        except Exception:  # noqa: BLE001 - unparseable/unsupported SQL:
            # the serial path owns producing the real error
            return None
        if template is None:
            return None
        _note_query(_QUERY_TEMPLATE, query_id, key[0])
        entry = _Pending(values, prepared, session, query_id, trace_id)
        hot = self._hot(key[0], session)
        window_s = self._window_s(session)
        max_batch = self._max_batch(session)

        with self._lock:
            g = self._forming.get(key)
            if g is not None and not g.sealed \
                    and len(g.entries) < max_batch:
                g.entries.append(entry)
                if len(g.entries) >= max_batch:
                    g.full.set()
                leader = False
            elif hot and window_s > 0:
                g = _Forming(key)
                g.entries.append(entry)
                self._forming[key] = g
                leader = True
            else:
                return None  # cold fingerprint: never pay the window

        if not leader:
            # follower: the leader executes for us
            with stage("batch.wait"):
                led = entry.event.wait(self.follower_timeout_s)
            if not led:
                return None  # leader wedged: run serial (duplicate-safe)
            if entry.error is not None:
                raise entry.error
            return entry.result

        # leader: collect followers until the batch fills, or -- once
        # the window has elapsed -- until this key's dispatch pipeline
        # has a free slot (chaining: while max_inflight previous
        # batches execute, this one keeps collecting; max_form_s
        # bounds the wait)
        t_form = time.time()
        with stage("batch.wait"):
            while True:
                g.full.wait(window_s)
                with self._lock:
                    if len(g.entries) >= max_batch:
                        break
                    elapsed = time.time() - t_form
                    if elapsed >= window_s and \
                            self._inflight.get(key, 0) < self.max_inflight:
                        break
                    if elapsed >= self.max_form_s:
                        break
        with self._lock:
            g.sealed = True
            if self._forming.get(key) is g:
                del self._forming[key]
            entries = list(g.entries)
            counted_inflight = len(entries) > 1
            if counted_inflight:
                self._inflight[key] = self._inflight.get(key, 0) + 1
        if len(entries) == 1:
            # no batch formed. If this key's vmapped program is ALREADY
            # warm (a real batch or precompile built it), ride it as a
            # batch-of-1: the template amortizes the per-literal XLA
            # compile a cold literal would otherwise pay on the serial
            # path. Never COMPILE a program for a singleton -- with no
            # warm program the serial path owns the query (keeps cold
            # workloads, and the test suite's one-off statements, on
            # the exact serial path).
            with self._lock:
                have = self._vmapped.get(key)
                if have is None or have[0] is None:
                    return None
        try:
            self._execute_batch(key, entries, sf=sf,
                                join_capacity=key[3])
        except BaseException as e:  # noqa: BLE001 - every waiting
            # member must wake, whatever broke
            for m in entries:
                if m.result is None and m.error is None:
                    m.error = e
        finally:
            if counted_inflight:  # solo dispatches never incremented
                with self._lock:
                    n = self._inflight.get(key, 0) - 1
                    if n > 0:
                        self._inflight[key] = n
                    else:
                        self._inflight.pop(key, None)
            for m in entries:
                m.event.set()
        if entry.error is not None:
            raise entry.error
        return entry.result

    def precompile(self, text: str, *, sf: float,
                   session: Optional[Dict] = None,
                   sizes: Optional[List[int]] = None,
                   join_capacity: Optional[int] = None,
                   catalog: Optional[str] = None) -> int:
        """Compile (and stage) the vmapped programs for `text`'s
        template at each power-of-two batch-size bucket, so a measured
        or latency-sensitive phase never pays an XLA compile mid-batch
        (benchmark warm-up; a production tier would drive this from the
        history archive's hot fingerprints). Returns the number of
        bucket programs now warm (0 = not batchable)."""
        sess = dict(session or {})
        try:
            _prepared, template, values, key = self._prepare(
                text, sf=sf, session=sess, max_groups=None,
                join_capacity=join_capacity, catalog=catalog)
        except Exception:  # noqa: BLE001 - unbatchable text: nothing
            return 0       # to warm
        if template is None:
            return 0
        fn, plan, call_lock = self._compiled(key, key[3])
        batches = self._stage_inputs(key, plan, sf)
        if sizes is None:
            sizes, s = [], 2
            while s <= self._max_batch(sess):
                sizes.append(s)
                s *= 2
        warmed = 0
        for size in sizes:
            stub = _Pending(values, None, sess, "warm", None)
            params = self._stack_params([stub] * max(int(size), 1))
            with call_lock:
                out, _overflow = fn(tuple(batches), params)
            jax.block_until_ready(out)
            warmed += 1
        return warmed

    def bench_dispatch(self, texts: List[str], *, sf: float,
                       session: Optional[Dict] = None):
        """Execute co-batchable `texts` as ONE batched dispatch with no
        formation window, returning per-text QueryResults in order --
        the direct dispatch-path seam (scripts/loadgen.py's engine
        amortization A/B and white-box tests). Raises ValueError when
        the texts do not share a batch key."""
        sess = dict(session or {})
        entries: List[_Pending] = []
        key0 = None
        for i, text in enumerate(texts):
            _prepared, template, values, key = self._prepare(
                text, sf=sf, session=sess, max_groups=None,
                join_capacity=None, catalog=None)
            if template is None:
                raise ValueError(f"not batchable: {text!r}")
            if key0 is None:
                key0 = key
            elif key != key0:
                raise ValueError("texts do not share a batch key")
            entries.append(_Pending(values, _prepared, sess,
                                    f"bench-{i}", None))
        self._execute_batch(key0, entries, sf=sf,
                            join_capacity=key0[3])
        for m in entries:
            if m.error is not None:
                raise m.error
        return [m.result for m in entries]

    def _prepare(self, text: str, *, sf: float, session: Dict,
                 max_groups: Optional[int],
                 join_capacity: Optional[int],
                 catalog: Optional[str]):
        """Plan + prepare + parameterize one statement, memoized by
        exact text (zipfian repeats skip the whole walk). Returns
        (prepared plan, template-or-None, param values, batch key)."""
        from .plan_cache import _kernel_mode
        # plan-shaping session properties are part of the memo key --
        # two sessions disagreeing on (say) narrow_width_execution
        # must not share a prepared tree
        sess_bits = tuple(
            (k, str((session or {}).get(k)))
            for k in ("iterative_optimizer", "join_reordering_strategy",
                      "stats_capacity_refinement",
                      "narrow_width_execution")
            if (session or {}).get(k) is not None)
        memo_key = (text, float(sf), max_groups, join_capacity,
                    catalog, _kernel_mode(), sess_bits)
        with self._lock:
            hit = self._plan_memo.get(memo_key)
            if hit is not None:
                self._plan_memo.move_to_end(memo_key)
                return hit
        out = self._prepare_uncached(text, sf=sf, session=session,
                                     max_groups=max_groups,
                                     join_capacity=join_capacity,
                                     catalog=catalog)
        with self._lock:
            self._plan_memo[memo_key] = out
            self._plan_memo.move_to_end(memo_key)
            while len(self._plan_memo) > self._plan_memo_max:
                self._plan_memo.popitem(last=False)
        return out

    def _prepare_uncached(self, text: str, *, sf: float, session: Dict,
                          max_groups: Optional[int],
                          join_capacity: Optional[int],
                          catalog: Optional[str]):
        from ..sql import plan_sql
        from .runner import prepare_plan
        kw = {}
        if max_groups is not None:
            kw["max_groups"] = int(max_groups)
        root = plan_sql(text, join_capacity=join_capacity,
                        catalog=catalog, **kw)
        inner = root.source if isinstance(root, N.OutputNode) else root
        if isinstance(inner, (N.DdlNode, N.TableFinishNode,
                              N.TableWriterNode, N.TableRewriteNode)):
            return None, None, None, None
        from .runner import placement_mesh
        if placement_mesh(root) is not None:
            # a statement over tables spread over several chips runs as
            # one SPMD program of its own: no batch is formed of it
            return None, None, None, None
        # the batched path shares staged scans across members, so the
        # per-literal staging optimizations must not specialize them:
        # pushdown pruning and dynamic filters stage different rows for
        # different literals (results stay exact either way -- the
        # Filter above always applies; these only prune)
        bsession = dict(session or {})
        bsession["scan_predicate_pushdown"] = False
        bsession["dynamic_filtering"] = False
        prepared = prepare_plan(root, sf=sf, mesh=None, session=bsession)
        template, params = parameterize_plan(prepared)
        values = [v for v, _ty in params]
        from .plan_cache import plan_fingerprint
        cap = join_capacity if join_capacity is not None else 1 << 16
        key = self._batch_key(plan_fingerprint(template), sf, cap)
        # stash the template + batching session on the key's compile
        # path via instance state-free returns
        self._templates_put(key, template)
        return prepared, template, values, key

    # template per key (bounded; the leader compiles from it)
    def _templates_put(self, key, template) -> None:
        with self._lock:
            self._vmapped.setdefault(key, (None, None, None, None))
            fn, plan, lock, _ = self._vmapped[key]
            self._vmapped[key] = (fn, plan, lock, template)
            self._vmapped.move_to_end(key)
            while len(self._vmapped) > self._vmapped_max:
                self._vmapped.popitem(last=False)

    def _compiled(self, key, join_capacity: int):
        """(vmapped jitted fn, CompiledPlan, dispatch lock) for a batch
        key -- the base program rides the shared plan cache (hit/miss
        accounting identical to serial repeats of the template)."""
        with self._lock:
            fn, plan, lock, template = self._vmapped.get(
                key, (None, None, None, None))
        if fn is not None:
            return fn, plan, lock
        if template is None:  # evicted between prepare and compile
            raise RuntimeError("batch template evicted before compile")
        from .plan_cache import cached_compile
        plan, _jfn, lock = cached_compile(template, None, join_capacity)

        def bfn(batches, params):
            with bound_params(params):
                return plan.fn(batches)

        fn = jax.jit(jax.vmap(bfn, in_axes=(None, 0)))
        with self._lock:
            have = self._vmapped.get(key)
            if have is not None and have[0] is not None:
                return have[0], have[1], have[2]
            self._vmapped[key] = (fn, plan, lock, template)
            self._vmapped.move_to_end(key)
            while len(self._vmapped) > self._vmapped_max:
                self._vmapped.popitem(last=False)
        return fn, plan, lock

    # -- batched dispatch ---------------------------------------------

    def _execute_batch(self, key, entries: List[_Pending], *,
                       sf: float, join_capacity: int) -> None:
        """Run one formed batch: stage scans once, dispatch the vmapped
        program over the stacked parameter vectors, fan results back to
        every member. Any overflow / injected collapse / unexpected
        error falls back to serial per-member dispatch."""
        from .. import failpoints
        from ..server.flight_recorder import record_event
        t0 = time.time()
        nbatch = len(entries)
        if failpoints.ARMED:
            try:
                # a formed batch forced to collapse back to serial
                # dispatch mid-flight (chaos asserts every member still
                # matches its oracle and accounting balances)
                failpoints.hit("dispatcher.batch_collapse")
            except Exception:  # noqa: BLE001 - any injected error class
                record_event("batch_collapse", reason="failpoint",
                             size=nbatch, query_id=entries[0].query_id)
                self._serial_fallback(entries, sf, "failpoint")
                return
        try:
            fn, plan, call_lock = self._compiled(key, join_capacity)
            # ONE progress entry per dispatch (the leader's): per-member
            # entries would put B lock round-trips on a path whose whole
            # point is amortizing per-query cost
            from .progress import begin as progress_begin
            prog = progress_begin(entries[0].query_id)
            try:
                prog.advance(stage="staging")
                batches = self._stage_inputs(key, plan, sf)
                params = self._stack_params(entries)
                prog.advance(stage="execute")
                with call_lock:
                    out, overflow = fn(tuple(batches), params)
                    expand_steps = plan.expand_steps_of(batches)
                jax.block_until_ready(out)
            finally:
                prog.release(state="FINISHED")
            flags, steps, compacted, direct = split_flags(
                plan.split_status(overflow)[0])
            if int(flags.max()) != 0:
                # a member overflowed a static bucket: the serial
                # ladder owns adaptive reruns; collapse the whole batch
                record_event("batch_collapse", reason="overflow",
                             size=nbatch, query_id=entries[0].query_id)
                self._serial_fallback(entries, sf, "overflow")
                return
        except Exception as e:  # noqa: BLE001 - a vmap/trace corner the
            # serial path handles fine must not fail the members
            from ..server.metrics import record_suppressed
            record_suppressed("batching", "batched_dispatch", e)
            record_event("batch_collapse", reason="error",
                         size=nbatch, query_id=entries[0].query_id)
            self._serial_fallback(entries, sf, "error")
            return
        device_us = int((time.time() - t0) * 1e6)
        self._fan_out(out, plan, entries, device_us, steps, expand_steps,
                      compacted, direct)
        self._account(entries)

    def _stage_inputs(self, key, plan, sf: float) -> list:
        """Stage the template's scan batches, replayed from the staged
        cache when every leaf's connector proves its data unchanged
        (data_version -- the worker fragment cache's contract; volatile
        catalogs stage fresh every batch). A template that scans a
        memory table is not replayed here: its whole-table scans take
        the resident tier (exec/resident.py), the one cache of a memory
        table's staged columns, which evicts and invalidates them."""
        versions: Optional[list] = []
        for s in plan.scan_nodes:
            if isinstance(s, N.ValuesNode):
                # VALUES rows are part of the plan fingerprint: static
                versions.append(("values",))
                continue
            if not isinstance(s, N.TableScanNode):
                versions = None
                break
            from ..connectors import catalog
            conn = catalog(s.connector)
            fn = getattr(conn, "data_version", None)
            if fn is None or hasattr(conn, "scan_snapshot"):
                versions = None
                break
            versions.append((s.connector, s.table, fn(s.table)))
        ckey = (key, tuple(versions)) if versions is not None else None
        if ckey is not None:
            with self._lock:
                hit = self._staged.get(ckey)
                if hit is not None:
                    self._staged.move_to_end(ckey)
                    return hit
        from .runner import _scan_batch
        batches = [_scan_batch(s, sf, None, 8) for s in plan.scan_nodes]
        if ckey is not None:
            with self._lock:
                self._staged[ckey] = batches
                self._staged.move_to_end(ckey)
                while len(self._staged) > self._staged_max:
                    self._staged.popitem(last=False)
        return batches

    def _stack_params(self, entries: List[_Pending]) -> tuple:
        """Member parameter vectors -> tuple over parameter positions
        of ([B] values, [B] nulls) arrays. The batch is padded to a
        power-of-two size with copies of member 0 so XLA compiles one
        program per (template, size bucket), not per exact size."""
        nbatch = len(entries)
        padded = 2  # the smallest precompiled bucket (solo dispatches
        while padded < nbatch:  # of a warm template pad up to it)
            padded *= 2
        nparams = len(entries[0].values)
        out = []
        for pi in range(nparams):
            vals = [m.values[pi][0] for m in entries]
            nulls = [m.values[pi][1] for m in entries]
            vals += [vals[0]] * (padded - nbatch)
            nulls += [nulls[0]] * (padded - nbatch)
            out.append((np.asarray(vals), np.asarray(nulls, dtype=bool)))
        if not out:
            # parameterless batch (identical literal-free statements):
            # vmap still needs a mapped axis to size the batch
            out.append((np.zeros(padded, dtype=np.int32),
                        np.zeros(padded, dtype=bool)))
        return tuple(out)

    def _fan_out(self, out, plan, entries: List[_Pending],
                 device_us: int, search_steps, expand_steps,
                 compacted, direct) -> None:
        """Slice the batched output back into per-member QueryResults
        (member i owns batch row i -- ordering is positional by
        construction). ONE host conversion covers the whole batch;
        members then slice numpy views and row-select by their active
        mask BEFORE any per-row decode, so fan-out cost tracks result
        rows, not table capacity."""
        from ..block import Batch as _Batch
        from .runner import _batch_to_result
        from .stats import QueryStats
        nbatch = len(entries)
        host = jax.tree_util.tree_map(np.asarray, out)
        for i, m in enumerate(entries):
            idx = np.nonzero(host.active[i])[0]
            cols = tuple(
                jax.tree_util.tree_map(lambda x, _i=i: x[_i][idx], col)
                for col in host.columns)
            out_i = _Batch(cols, np.ones(len(idx), dtype=bool))
            res = _batch_to_result(out_i, plan.root)
            qs = QueryStats()
            qs.wall_us = device_us
            qs.output_rows = res.row_count
            qs.counters["batched_queries"] = 1
            qs.counters["batch_size"] = nbatch
            if search_steps[i] or direct[i] or expand_steps is not None:
                qs.counters["join_search_steps"] = int(search_steps[i])
                qs.counters["join_lookup_direct"] = int(direct[i])
            if expand_steps is not None:
                qs.counters["join_expand_steps"] = expand_steps
                qs.counters["join_probe_compacted"] = int(compacted[i])
            res.query_stats = qs
            res.stats = {"batch": {"size": float(nbatch),
                                   "device_us": float(device_us)}}
            m.result = res
            _note_query(_QUERY_BATCH, m.query_id, nbatch)

    def _account(self, entries: List[_Pending]) -> None:
        nbatch = len(entries)
        with _TOTALS_LOCK:
            if nbatch > 1:
                _TOTALS["batches"] += 1
                _TOTALS["batched_queries"] += nbatch
                _TOTALS["last_batch_size"] = nbatch
                _TOTALS["max_batch_size"] = max(
                    _TOTALS["max_batch_size"], nbatch)
            else:
                # a batch-of-1 riding a warm template program: counted
                # apart so occupancy stats keep meaning "co-batched"
                _TOTALS["solo_dispatches"] += 1
        if nbatch > 1:
            from ..server.metrics import observe_histogram
            first = entries[0]
            observe_histogram("presto_tpu_batch_occupancy_queries",
                              float(nbatch),
                              trace_id=_trace_str(first.trace_id,
                                                  first.query_id))

    def _serial_fallback(self, entries: List[_Pending], sf: float,
                         reason: str) -> None:
        """Collapse: run every member through the normal serial engine
        path on this thread (each result is exactly what the unbatched
        execution produces). Per-member errors stay per-member."""
        with _TOTALS_LOCK:
            _COLLAPSES[reason] = _COLLAPSES.get(reason, 0) + 1
        from .runner import run_query
        for m in entries:
            try:
                # every member's own collector, not the leader's: this
                # thread runs them all
                with collecting(None):
                    m.result = run_query(
                        m.root, sf=sf, session=m.session,
                        query_id=m.query_id, prepared=True,
                        trace_id=m.trace_id)
            except BaseException as e:  # noqa: BLE001 - deliver to the
                m.error = e             # member's waiting thread

    def snapshot(self) -> dict:
        """Live view for /v1/cluster: forming-queue depth per key plus
        the process totals."""
        with self._lock:
            pending = [{"fingerprint": k[0][:12],
                        "queued": len(g.entries)}
                       for k, g in self._forming.items()]
        t = batching_totals()
        avg = (t["batched_queries"] / t["batches"]) if t["batches"] \
            else 0.0
        return {"batchesDispatched": t["batches"],
                "queriesBatched": t["batched_queries"],
                "soloDispatches": t["solo_dispatches"],
                "collapses": t["collapses"],
                "lastBatchSize": t["last_batch_size"],
                "maxBatchSize": t["max_batch_size"],
                "avgOccupancy": round(avg, 2),
                "forming": pending}


def _trace_str(trace_id, query_id: str) -> str:
    from ..server.tracing import TraceContext
    if isinstance(trace_id, TraceContext):
        return trace_id.trace_id
    return str(trace_id or query_id)


_EXEC_LOCK = OrderedLock("batching._EXEC_LOCK")
_EXECUTOR: Optional[BatchingExecutor] = None


def get_batching_executor() -> BatchingExecutor:
    global _EXECUTOR
    with _EXEC_LOCK:
        if _EXECUTOR is None:
            _EXECUTOR = BatchingExecutor()
        return _EXECUTOR


def set_batching_executor(executor: Optional[BatchingExecutor]) -> None:
    global _EXECUTOR
    with _EXEC_LOCK:
        _EXECUTOR = executor


def batching_snapshot() -> dict:
    return get_batching_executor().snapshot()
