"""System connector: cluster introspection as queryable tables.

Reference surface: presto-main's system connector (runtime.queries /
runtime.tasks / runtime.nodes / metadata.catalogs system tables) and
the native worker's SystemConnector.cpp (task info served as tables).
Servers register themselves at start (statement servers, worker task
managers, discovery urls); scans snapshot live state host-side -- no
device work, these are control-plane reads.

    SELECT query_id, state, query FROM system.queries
    SELECT task_id, state, rows FROM system.tasks
    SELECT * FROM system.catalogs
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence

import numpy as np

from .. import types as T
from ..block import batch_from_numpy

__all__ = ["SCHEMA", "register_statement_server", "register_task_manager",
           "register_discovery", "reset", "table_row_count",
           "generate_columns", "generate_nulls", "generate_batch",
           "column_type"]

_lock = threading.Lock()
# weak references: registration must not keep dead servers alive (test
# suites churn through hundreds of them)
_statement_servers: List[weakref.ref] = []
_task_managers: List[weakref.ref] = []
_discovery_urls: List[str] = []


def _live(refs: List[weakref.ref]) -> List[object]:
    out = []
    dead = []
    for r in refs:
        o = r()
        (out if o is not None else dead).append(o if o is not None else r)
    for r in dead:
        refs.remove(r)
    return out


def register_statement_server(server) -> None:
    with _lock:
        if server not in _live(_statement_servers):
            _statement_servers.append(weakref.ref(server))


def register_task_manager(manager) -> None:
    with _lock:
        if manager not in _live(_task_managers):
            _task_managers.append(weakref.ref(manager))


def register_discovery(url: str) -> None:
    with _lock:
        if url not in _discovery_urls:
            _discovery_urls.append(url)


def reset() -> None:
    with _lock:
        _statement_servers.clear()
        _task_managers.clear()
        _discovery_urls.clear()


_V = T.varchar(256)
SCHEMA = {
    "queries": {"query_id": _V, "state": _V, "user": _V, "query": _V,
                "elapsed_ms": T.BIGINT,
                # structured-telemetry columns (QueryStats): result
                # bytes, high-water memory, XLA compile micros
                "cumulative_bytes": T.BIGINT,
                "peak_memory_bytes": T.BIGINT,
                "compile_us": T.BIGINT,
                # live-progress columns (exec/progress.py): real
                # movement for RUNNING queries, not just terminal stats
                "processed_rows": T.BIGINT,
                "processed_bytes": T.BIGINT,
                "progress_percent": T.DOUBLE,
                "stage": _V,
                "last_advance_age_ms": T.BIGINT,
                # admission + batching attribution (PR 13): the
                # resource group the dispatcher routed the query to
                # and the batched-dispatch occupancy that served it
                # (0 = serial dispatch)
                "resource_group": _V,
                "batch_size": T.BIGINT},
    # in-flight query/task progress heartbeats (exec/progress.py):
    # one row per live entry this process tracks -- local engine
    # queries, this worker's tasks, and remote tasks the coordinator's
    # status polls folded back in
    "live_tasks": {"task_id": _V, "query_id": _V, "kind": _V,
                   "worker": _V, "state": _V, "stage": _V,
                   "splits_done": T.BIGINT, "splits_planned": T.BIGINT,
                   "rows": T.BIGINT, "bytes": T.BIGINT,
                   "peak_memory_bytes": T.BIGINT,
                   "progress_percent": T.DOUBLE,
                   "elapsed_ms": T.BIGINT,
                   "last_advance_age_ms": T.BIGINT,
                   # straggler-mitigation provenance: TRUE when this
                   # entry is a speculative re-execution racing its
                   # original (coordinator `.spec` task ids)
                   "speculative": T.BOOLEAN},
    "tasks": {"task_id": _V, "state": _V, "rows": T.BIGINT,
              "buffered_pages": T.BIGINT, "elapsed_s": T.DOUBLE,
              "output_bytes": T.BIGINT, "peak_memory_bytes": T.BIGINT,
              "compile_us": T.BIGINT},
    "nodes": {"node_id": _V, "uri": _V, "coordinator": T.BOOLEAN,
              "age_seconds": T.DOUBLE},
    "catalogs": {"catalog_name": _V, "connector_id": _V},
    "tables": {"catalog_name": _V, "table_name": _V,
               "column_count": T.BIGINT},
    "plan_cache": {"entries": T.BIGINT, "hits": T.BIGINT,
                   "misses": T.BIGINT},
    # data-path waterfall (exec/datapath.py): one row per catalog hop,
    # data-path order -- lifetime bytes/wall, achieved B/s, the
    # measured ceiling it rooflines against, and the utilization ratio
    "datapath": {"hop": _V, "bytes": T.BIGINT, "wall_us": T.BIGINT,
                 "invocations": T.BIGINT,
                 "achieved_b_per_s": T.DOUBLE,
                 "ceiling_b_per_s": T.DOUBLE,
                 "utilization": T.DOUBLE},
    # estimate-accuracy observatory (exec/accuracy.py): one row per
    # (retained query, plan node) -- the planner's estimate beside what
    # the runtime measured, folded into a q-error with direction
    "cardinality": {"query_id": _V, "node": _V, "node_type": _V,
                    "unit": _V, "est": T.DOUBLE, "actual": T.DOUBLE,
                    "q_error": T.DOUBLE, "direction": _V,
                    "tasks": T.BIGINT},
    "session_properties": {"name": _V, "default_value": _V, "type": _V,
                           "description": _V},
    "functions": {"function_name": _V, "kind": _V},
    # completed-query archive (server/history.py): one row per retained
    # record, newest first -- the perf sentinel's raw material as SQL
    "query_history": {"query_id": _V, "state": _V, "user": _V,
                      "query": _V, "fingerprint": _V, "trace_id": _V,
                      "ts_us": T.BIGINT, "wall_us": T.BIGINT,
                      "compile_us": T.BIGINT, "execute_us": T.BIGINT,
                      "staged_bytes": T.BIGINT,
                      "narrowed_bytes_saved": T.BIGINT,
                      "retraces": T.BIGINT, "spill_bytes": T.BIGINT,
                      "peak_memory_bytes": T.BIGINT,
                      "output_rows": T.BIGINT,
                      "failpoint_hits": T.BIGINT,
                      "regressions": _V,
                      # estimate-accuracy columns appended at the END
                      # (generate_columns indexes SCHEMA order, so new
                      # columns must not shift existing ones)
                      "max_q_error": T.DOUBLE,
                      "misestimated_node": _V},
}


def _compile_us_of(query_stats_doc: dict) -> int:
    """Summed compile micros across a QueryStats json document's stages."""
    return sum(int(s.get("compile_us", 0))
               for s in (query_stats_doc.get("stages") or {}).values())


def _rows_of(table: str) -> List[tuple]:
    # M001: system tables surface CAPPED registries -- the history
    # archive is retention-capped, profiler/cache registries are
    # entry-capped -- so one snapshot list per request is bounded
    _BOUNDED_BY = {"out": "capped registry snapshot (history "
                          "retention / profiler entry caps)"}
    if table == "queries":
        out = []
        with _lock:
            servers = _live(_statement_servers)
        for s in servers:
            for doc in s.queries_doc():
                qs = doc.get("queryStats") or {}
                prog = doc.get("progress") or {}
                out.append((doc["queryId"], doc["state"], doc["user"],
                            doc["query"],
                            int(doc.get("elapsedTimeMillis", 0)),
                            int(qs.get("outputBytes", 0)),
                            int(qs.get("peakMemoryBytes", 0)),
                            _compile_us_of(qs),
                            int(prog.get("rows", 0)),
                            int(prog.get("bytes", 0)),
                            float(prog.get("progressPercent", 0.0)),
                            str(prog.get("stage", "")),
                            int(prog.get("lastAdvanceAgeMs", 0)),
                            str(doc.get("resourceGroup", "")),
                            int(doc.get("batchSize", 0))))
        return out
    if table == "live_tasks":
        from ..exec.progress import live_snapshots
        return [(e["key"], e["query"], e["kind"], e["worker"] or "",
                 e["state"], e["stage"], int(e["splitsDone"]),
                 int(e["splitsPlanned"]), int(e["rows"]),
                 int(e["bytes"]), int(e["peakMemoryBytes"]),
                 float(e["progressPercent"]), int(e["elapsedMs"]),
                 int(e["lastAdvanceAgeMs"]),
                 bool(e.get("speculative", False)))
                for e in live_snapshots()]
    if table == "tasks":
        out = []
        with _lock:
            managers = _live(_task_managers)
        for m in managers:
            with m._tasks_lock:
                infos = [t.info() for t in m.tasks.values()]
            for i in infos:
                st = i.get("stats", {}) or {}
                qs = st.get("queryStats") or {}
                out.append((i["taskId"], i["state"],
                            int(st.get("outputRows", 0)),
                            i["bufferedPages"], i["elapsedSeconds"],
                            int(st.get("outputBytes", 0)),
                            int(qs.get("peakMemoryBytes", 0)),
                            _compile_us_of(qs)))
        return out
    if table == "nodes":
        from ..server.discovery import alive_nodes
        out = []
        with _lock:
            urls = list(_discovery_urls)
        for url in urls:
            try:
                for n in alive_nodes(url, max_age_s=1e9):
                    out.append((n.get("nodeId", ""), n.get("uri", ""),
                                bool(n.get("coordinator", False)),
                                float(n.get("ageSeconds", 0.0))))
            except Exception:  # noqa: BLE001 - discovery may be down
                pass
        return out
    if table == "catalogs":
        from . import catalogs
        return [(name, name) for name in sorted(catalogs())]
    if table == "tables":
        from . import catalogs
        out = []
        for cat, mod in sorted(catalogs().items()):
            if cat == "system":
                sch = SCHEMA
            else:
                sch = mod.SCHEMA
            for t in sorted(sch.keys()):
                try:
                    out.append((cat, t, len(sch[t])))
                except Exception:  # noqa: BLE001 - live schemas may churn
                    pass
        return out
    if table == "session_properties":
        from ..utils.config import SESSION_PROPERTIES
        out = []
        for name, prop in sorted(SESSION_PROPERTIES.properties.items()):
            out.append((name, str(prop.default), prop.kind,
                        prop.description))
        return out
    if table == "functions":
        from ..expr.functions import REGISTRY
        from ..ops.aggregation import _AGGS
        out = [(n, "scalar") for n in sorted(REGISTRY)
               if not n.startswith("$")]
        out += [(n, "aggregate") for n in sorted(_AGGS)]
        from ..ops.window import _FUNCS as _WIN
        out += [(n, "window") for n in sorted(_WIN)]
        from ..sql.udf import get_function_namespace_manager
        out += [(f.qualified_name, "sql-invoked")
                for f in get_function_namespace_manager().list_functions()]
        return out
    if table == "plan_cache":
        from ..exec.plan_cache import cache_stats
        st = cache_stats()
        return [(st["entries"], st["hits"], st["misses"])]
    if table == "query_history":
        from ..server.history import get_history_archive
        out = []
        for r in get_history_archive().records():
            st = r.get("stats") or {}
            out.append((r.get("queryId", ""), r.get("state", ""),
                        r.get("user", ""), r.get("query", ""),
                        r.get("fingerprint", ""), r.get("traceId", ""),
                        int(r.get("tsUs", 0)),
                        int(st.get("wall_us", 0)),
                        int(st.get("compile_us", 0)),
                        int(st.get("execute_us", 0)),
                        int(st.get("staged_bytes", 0)),
                        int(st.get("narrowed_bytes_saved", 0)),
                        int(st.get("retraces", 0)),
                        int(st.get("spill_bytes", 0)),
                        int(st.get("peak_memory_bytes", 0)),
                        int(st.get("output_rows", 0)),
                        int(r.get("failpointHits", 0)),
                        ",".join(r.get("regressions") or ()),
                        float(st.get("max_q_error", 0.0)),
                        r.get("misestimatedNode", "")))
        return out
    if table == "datapath":
        from ..exec.datapath import snapshot as datapath_snapshot
        return [(r["hop"], int(r["bytes"]), int(r["wall_us"]),
                 int(r["invocations"]), float(r["achievedBPerS"]),
                 float(r["ceilingBPerS"]), float(r["utilization"]))
                for r in datapath_snapshot()]
    if table == "cardinality":
        from ..exec.accuracy import snapshot as accuracy_snapshot
        return [(r["queryId"], r["node"], r["node_type"], r["unit"],
                 float(r["est"]) if r["est"] is not None else 0.0,
                 float(r["actual"]) if r["actual"] is not None else 0.0,
                 float(r["qError"]) if r["qError"] is not None else 0.0,
                 r["direction"], int(r["tasks"]))
                for r in accuracy_snapshot()]
    raise KeyError(f"no system table {table!r}")


def column_type(table: str, column: str) -> T.Type:
    return SCHEMA[table][column]


def table_row_count(table: str, sf: float = 0.0) -> int:
    return len(_rows_of(table))


def generate_columns(table: str, sf: float, columns: Sequence[str],
                     start: int = 0, count: Optional[int] = None
                     ) -> Dict[str, np.ndarray]:
    rows = _rows_of(table)
    count = len(rows) - start if count is None else count
    rows = rows[start:start + count]
    names = list(SCHEMA[table])
    out = {}
    for c in columns:
        i = names.index(c)
        ty = SCHEMA[table][c]
        vals = [r[i] for r in rows]
        if ty.is_string:
            out[c] = np.array([str(v) for v in vals], dtype=object)
        else:
            out[c] = np.array(vals, dtype=ty.to_dtype())
    return out


def generate_nulls(table: str, columns: Sequence[str], start: int = 0,
                   count: Optional[int] = None) -> Dict[str, np.ndarray]:
    n = table_row_count(table) - start if count is None else count
    return {c: np.zeros(max(n, 0), dtype=bool) for c in columns}


def generate_batch(table: str, sf: float, columns: Sequence[str],
                   start: int = 0, count: Optional[int] = None,
                   capacity: Optional[int] = None):
    data = generate_columns(table, sf, columns, start, count)
    vals = [data[c] for c in columns]
    types = [SCHEMA[table][c] for c in columns]
    n = len(vals[0]) if vals else 0
    cap = capacity or max(n, 1)
    return batch_from_numpy(types, vals, capacity=cap)
