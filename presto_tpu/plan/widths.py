"""Narrow-width execution: plan-level physical-lane inference.

PERF.md's roofline shows the q1 hot path bandwidth-bound with int64
lanes everywhere (jax x64; v5e emulates int64 as i32 pairs): the staged
bytes -- and therefore the HBM reads the scan pipeline pays -- are 2-4x
wider than the value domains require. This pass derives, per scan
column, the narrowest PHYSICAL lane the catalog can PROVE safe:

  * dates stage as int32 epoch-days (already) or int16 when the date
    domain fits;
  * int64 key/measure columns whose value range provably fits stage as
    int32/int16/int8 lanes;
  * short-decimal (scaled int64) columns narrow by their scaled range.

Safety contract (what makes narrowed execution bit-exact):

  * narrowing applies ONLY to the staged representation. Every compute
    site that can overflow a narrow lane widens first: comparisons and
    decimal arithmetic upcast to int64 in expr/functions, aggregation
    sums upcast via ``_sum_dtype`` / 13-bit (or 8-bit) limb widening at
    accumulation (ops/aggregation.py), key words upcast to uint64
    (ops/keys.py). min/max/group-keys are order-preserving under a
    range-proven downcast.
  * a column narrows only when the connector proves its range
    (``column_range``); no stats -> the logical width stands.
  * the staging site re-checks the actual host array against the
    proven range (``checked_physical_dtypes``) so a stale statistic can
    never wrap values -- it falls back to the logical width instead.

Gates: env ``PRESTO_TPU_NARROW`` (default on; ``0`` = wide A/B) and the
``narrow_width_execution`` session property. The kernel-side forms
(bf16 one-hot operands, the fused cross-aggregate limb pool in
ops/aggregation.py) key off the same env flag at trace time.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import types as T
from . import nodes as N

__all__ = ["narrow_enabled", "kernel_narrow_enabled", "infer_column_width",
           "infer_scan_widths", "infer_table_widths", "annotate_widths",
           "checked_physical_dtypes", "batch_narrowed_bytes_saved",
           "note_narrowed", "narrowing_totals", "widths_summary"]


def narrow_enabled(session=None) -> bool:
    """Plan-level gate: env default-on, per-query session override."""
    if os.environ.get("PRESTO_TPU_NARROW", "1") == "0":
        return False
    from ..utils.config import session_flag
    return session_flag(session, "narrow_width_execution", True)


def kernel_narrow_enabled() -> bool:
    """Trace-time kernel gate (bf16 one-hot operands, fused limb pool).
    Env-only: kernels are compiled per backend, not per session."""
    return os.environ.get("PRESTO_TPU_NARROW", "1") != "0"


# physical candidates, narrowest first (never float -- bit-exactness)
_CANDIDATES = (np.dtype(np.int8), np.dtype(np.int16), np.dtype(np.int32))

# logical bases narrowing may apply to: fixed-width signed-int lanes
# whose every consumer either upcasts before arithmetic or is
# order/equality-preserving under a range-proven downcast
_NARROWABLE_BASES = ("tinyint", "smallint", "integer", "bigint", "date",
                     "time", "timestamp")


def _narrowable(ty: T.Type) -> bool:
    if ty.is_decimal:
        return ty.is_short_decimal  # int64 lanes; long decimals are 128-bit
    return ty.base in _NARROWABLE_BASES


def infer_column_width(ty: T.Type, lo: int, hi: int) -> Optional[str]:
    """Narrowest physical dtype name for a column of logical type `ty`
    whose values provably lie in [lo, hi]; None = keep the logical
    lane."""
    if not _narrowable(ty):
        return None
    logical = np.dtype(ty.to_dtype())
    for cand in _CANDIDATES:
        if cand.itemsize >= logical.itemsize:
            break
        info = np.iinfo(cand)
        if info.min <= lo and hi <= info.max:
            return cand.name
    return None


def _column_range(conn, table: str, column: str, sf: float
                  ) -> Optional[Tuple[int, int]]:
    fn = getattr(conn, "column_range", None)
    if fn is None:
        return None
    try:
        return fn(table, column, sf)
    except KeyError:
        return None


def infer_table_widths(connector: str, table: str, columns: Sequence[str],
                       column_types: Sequence[T.Type], sf: float
                       ) -> Optional[Tuple[Optional[str], ...]]:
    """Per-column physical dtype names (None = logical) for one scan;
    None overall when nothing narrows."""
    from ..connectors import catalog
    try:
        conn = catalog(connector)
    except KeyError:
        return None
    out: List[Optional[str]] = []
    for col, ty in zip(columns, column_types):
        rng = _column_range(conn, table, col, sf)
        if rng is None:
            out.append(None)  # stats can't prove the range: refuse
            continue
        out.append(infer_column_width(ty, int(rng[0]), int(rng[1])))
    if not any(out):
        return None
    return tuple(out)


def infer_scan_widths(node: N.TableScanNode, sf: float
                      ) -> Optional[Tuple[Optional[str], ...]]:
    return infer_table_widths(node.connector, node.table, node.columns,
                              node.column_types, sf)


def annotate_widths(root: N.PlanNode, sf: float, _memo=None) -> N.PlanNode:
    """Width-inference pass: rewrite every range-proven TableScanNode
    with its `physical_dtypes` annotation (identity-memoized so shared
    CTE subtrees stay shared). Runs after the logical optimizer so
    channel pruning has already dropped unused columns."""
    if _memo is None:
        _memo = {}
    if id(root) in _memo:
        return _memo[id(root)]
    orig = id(root)

    replaced = {}
    for f in dataclasses.fields(root):
        v = getattr(root, f.name)
        if isinstance(v, N.PlanNode):
            nv = annotate_widths(v, sf, _memo)
            if nv is not v:
                replaced[f.name] = nv
        elif isinstance(v, list) and v and isinstance(v[0], N.PlanNode):
            nl = [annotate_widths(s, sf, _memo) for s in v]
            if any(a is not b for a, b in zip(nl, v)):
                replaced[f.name] = nl
    if replaced:
        root = dataclasses.replace(root, **replaced)

    if isinstance(root, N.TableScanNode) and root.physical_dtypes is None:
        widths = infer_scan_widths(root, sf)
        if widths is not None:
            root = dataclasses.replace(root, physical_dtypes=widths)
    _memo[orig] = root
    return root


def lane_holds(dt, values: np.ndarray) -> bool:
    """Whether every one of `values` (integers) fits a lane of `dt`:
    the range proof of the staging-time guard, over one array."""
    if not len(values):
        return True
    info = np.iinfo(np.dtype(dt))
    return info.min <= int(values.min()) and int(values.max()) <= info.max


def checked_physical_dtypes(phys: Sequence[Optional[str]],
                            types: Sequence[T.Type],
                            arrays: Sequence[np.ndarray],
                            nulls: Optional[Sequence[
                                Optional[np.ndarray]]] = None
                            ) -> Tuple[Optional[str], ...]:
    """Staging-time guard: drop any narrowing the actual host values
    would overflow (stale statistics / mutated tables can never wrap --
    the column silently stages wide instead). NULL positions are
    excluded from the range check (mirroring column_range's non-null
    bounds; a null slot's stored payload is unspecified and narrowing
    may wrap it -- padded/null lanes are masked by every kernel)."""
    out: List[Optional[str]] = []
    for i, (dt, ty, arr) in enumerate(zip(phys, types, arrays)):
        if dt is None:
            out.append(None)
            continue
        if arr.dtype == object or arr.dtype.kind not in "iu" or not len(arr):
            out.append(None)
            continue
        live = arr
        if nulls is not None and nulls[i] is not None \
                and np.any(nulls[i]):  # no null: no copy to look through
            live = arr[~np.asarray(nulls[i], dtype=bool)]
            if not len(live):
                out.append(dt)  # all-null: any lane holds the mask
                continue
        out.append(dt if lane_holds(dt, live) else None)
    return tuple(out)


def batch_narrowed_bytes_saved(batch) -> Tuple[int, int]:
    """(columns narrowed, staged bytes saved vs logical lanes) for one
    staged Batch -- the QueryStats `narrowed_bytes_saved` source."""
    from ..block import Column
    cols = saved = 0
    for b in batch.columns:
        if not isinstance(b, Column) or not b.type.is_fixed_width:
            continue
        try:
            logical = np.dtype(b.type.to_dtype())
        except ValueError:
            continue
        phys = np.dtype(b.values.dtype)
        if phys.kind in "iu" and phys.itemsize < logical.itemsize:
            cols += 1
            saved += (logical.itemsize - phys.itemsize) * b.capacity
    return cols, saved


def widths_summary(node: N.TableScanNode) -> str:
    """`col:int16,...` rendering of a scan's narrowed lanes (EXPLAIN /
    EXPLAIN ANALYZE node annotation)."""
    phys = node.physical_dtypes
    if not phys:
        return ""
    parts = [f"{c}:{d}" for c, d in zip(node.columns, phys) if d]
    return ",".join(parts)


# --------------------------------------------------------------------------
# process-lifetime narrowing totals (the /v1/metrics families)
# --------------------------------------------------------------------------

_totals_lock = threading.Lock()
_TOTALS: Dict[str, int] = {"bytes_saved": 0, "columns": 0}


def note_narrowed(columns: int, bytes_saved: int) -> None:
    if not columns and not bytes_saved:
        return
    with _totals_lock:
        _TOTALS["columns"] += int(columns)
        _TOTALS["bytes_saved"] += int(bytes_saved)


def narrowing_totals() -> Dict[str, int]:
    with _totals_lock:
        return dict(_TOTALS)
