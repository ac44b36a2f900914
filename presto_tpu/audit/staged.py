"""Staging-time kernel auditing: the exec/runner.py <-> kernaudit seam.

When the ``kernel_audit`` session property (env
``PRESTO_TPU_KERNEL_AUDIT``, registered in
``exec.plan_cache.KERNEL_MODE_ENVS``) is on, the runner calls
:func:`audit_staged_query` right after staging and before dispatch:
the plan's fused function is traced to a closed jaxpr over the staged
batches (one extra trace -- which is why the result is memoized by
(plan fingerprint, mesh, kernel mode, batch shapes) and the memo is
cleared together with the plan cache) and every registered IR pass
runs over it.

Findings are telemetry, never failures: they are counted into
QueryStats counters (``kernel_audit.K001`` ...), bumped on the
process-lifetime totals behind
``presto_tpu_kernel_audit_findings_total{pass=...}`` on both tiers'
``/v1/metrics``, recorded as one flight-recorder ``kernel_audit``
event, and the K005 peak estimate feeds the memory pool's accounting.
The gate that FAILS on findings is ``scripts/kernaudit.py`` over the
TPC-H corpus.
"""

from __future__ import annotations

import collections
import threading
from typing import Dict, Optional

__all__ = ["kernel_audit_enabled", "audit_staged_query",
           "kernel_audit_totals", "clear_audit_memo", "AUDIT_ENV"]

AUDIT_ENV = "PRESTO_TPU_KERNEL_AUDIT"

# -- process-lifetime totals (/v1/metrics, both tiers) -------------------

_TOTALS_LOCK = threading.Lock()
_FINDINGS_TOTAL: Dict[str, int] = {}   # pass code -> findings surfaced
_KERNELS_TOTAL = {"audited": 0}        # fresh traces (memo hits excluded)


def kernel_audit_totals() -> Dict[str, object]:
    with _TOTALS_LOCK:
        return {"findings": dict(_FINDINGS_TOTAL),
                "kernels": _KERNELS_TOTAL["audited"]}


# -- per-(plan, shapes, mode) memo: audit once per compiled program ------

_MEMO: "collections.OrderedDict[tuple, dict]" = collections.OrderedDict()
_MEMO_MAX = 128
_MEMO_LOCK = threading.Lock()


def clear_audit_memo() -> None:
    """Drop memoized audit reports (called by
    exec.plan_cache.clear_plan_cache so the two lifecycles stay in
    sync: a cleared executable cache means the next submission
    re-traces, and should re-audit)."""
    with _MEMO_LOCK:
        _MEMO.clear()


def kernel_audit_enabled(session) -> bool:
    """Session property ``kernel_audit``; process default from
    ``PRESTO_TPU_KERNEL_AUDIT`` (registered in KERNEL_MODE_ENVS)."""
    import os
    env_on = os.environ.get(AUDIT_ENV, "0") not in ("0", "", "false")
    from ..utils.config import session_flag
    return session_flag(session, "kernel_audit", env_on)


def _budget(session) -> int:
    from ..utils.config import session_value
    try:
        return int(session_value(session, "kernel_audit_budget_bytes", 0)
                   or 0)
    except (TypeError, ValueError):
        return 0


def audit_staged_query(plan, batches, *, mesh=None, query_id: str = "query",
                       session=None, collector=None, stats=None,
                       memory_pool=None,
                       plan_fp: Optional[str] = None) -> Optional[dict]:
    """Audit one staged query's fused program. Returns the report dict
    ``{findings: {code: n}, suppressed, peak_bytes_estimate, memo_hit}``
    or None when auditing failed (counted suppressed -- telemetry must
    never fail the query)."""
    try:
        report = _audit_report(plan, batches, mesh, query_id, session,
                               plan_fp)
    except Exception as e:  # noqa: BLE001 - observability never fails a query
        from ..server.metrics import record_suppressed
        record_suppressed("kernel_audit", "staged_trace", e)
        return None
    # surface the report on this query's telemetry even for memo hits:
    # QueryStats is per-query, the memo only skips the re-trace
    by_code = report["findings"]
    total = sum(by_code.values())
    with _TOTALS_LOCK:
        for code, n in by_code.items():
            _FINDINGS_TOTAL[code] = _FINDINGS_TOTAL.get(code, 0) + n
    if collector is not None:
        collector.note("kernel_audit_kernels")
        for code, n in sorted(by_code.items()):
            collector.note(f"kernel_audit.{code}", n)
        if report["peak_bytes_estimate"]:
            # QueryStats counters merge by SUMMATION across tasks, so
            # on the fragment tier this reads as the sum of per-
            # fragment peak estimates -- an upper bound on cluster-
            # wide audit footprint, not any one device's peak. The
            # max-law per-device peak rides note_audit_estimate below
            # into QueryStats.peak_memory_bytes (which merges by max).
            collector.note("kernel_audit_peak_bytes_estimate",
                           report["peak_bytes_estimate"])
    if stats is not None and total:
        stats.add("kernel_audit_findings", total)
    over_capacity = False
    if memory_pool is not None and report["peak_bytes_estimate"]:
        note = getattr(memory_pool, "note_audit_estimate", None)
        if note is not None:
            # True where the estimate alone exceeds the WHOLE pool: this
            # plan cannot fit even an empty pool -- the flight event
            # below says so before execution proves it the hard way
            over_capacity = bool(note(query_id,
                                      report["peak_bytes_estimate"]))
    from ..server.flight_recorder import record_event
    record_event("kernel_audit", query_id=query_id, findings=total,
                 passes=",".join(f"{c}:{n}"
                                 for c, n in sorted(by_code.items())),
                 peak_bytes=report["peak_bytes_estimate"],
                 over_pool_capacity=over_capacity or None,
                 memo_hit=report["memo_hit"])
    return report


def _audit_report(plan, batches, mesh, query_id, session,
                  plan_fp) -> dict:
    from .core import KernelIR, run_audit
    if plan_fp is None:
        from ..exec.plan_cache import plan_fingerprint
        plan_fp = plan_fingerprint(plan.root)
    from ..exec.plan_cache import _kernel_mode, _mesh_key
    # the K005 budget is part of the key: the same program audited
    # under a different kernel_audit_budget_bytes must re-run the
    # passes, or a memo hit would serve the other budget's verdict.
    # Batch identity is the full leaf (shape, dtype) signature -- what
    # jit itself keys on: a staging-time range-guard widening (stale
    # stats after a write) changes lane dtypes WITHOUT changing the
    # plan fingerprint or capacities, and must re-audit
    import jax
    leaf_sig = tuple((tuple(l.shape), str(l.dtype))
                     for l in jax.tree_util.tree_leaves(tuple(batches)))
    key = (plan_fp, _mesh_key(mesh), _kernel_mode(), _budget(session),
           leaf_sig)
    with _MEMO_LOCK:
        hit = _MEMO.get(key)
        if hit is not None:
            _MEMO.move_to_end(key)
            return dict(hit, memo_hit=True)
    axes = tuple(mesh.axis_names) if mesh is not None else ()
    kernel = KernelIR.trace(plan.fn, (tuple(batches),), query_id,
                            exchange_axes=axes,
                            footprint_budget_bytes=_budget(session))
    result = run_audit([kernel])
    by_code: Dict[str, int] = {}
    for f in result.findings:
        by_code[f.code] = by_code.get(f.code, 0) + 1
    report = {"findings": by_code, "suppressed": result.suppressed,
              "peak_bytes_estimate":
                  kernel.notes.get("peak_bytes_estimate", 0),
              "memo_hit": False}
    with _TOTALS_LOCK:
        _KERNELS_TOTAL["audited"] += 1
    with _MEMO_LOCK:
        _MEMO[key] = report
        while len(_MEMO) > _MEMO_MAX:
            _MEMO.popitem(last=False)
    return report
