"""Writable in-memory connector: the presto-memory analog.

Reference surface: presto-memory (MemoryConnector: MemoryMetadata
creates/drops tables, MemoryPagesStore holds per-node page lists,
MemoryPageSinkProvider appends, reads scan the stored pages). This
engine's version stores numpy column vectors host-side; scans stage
them into HBM Batches exactly like the generator connectors, so the
whole read pipeline (stats, dynamic filtering, mesh sharding) treats a
written table no differently from tpch/tpcds. A string column is stored
as it is staged, bytes and lengths (`block.HostStrings`): a page that a
writer appends and a split that a scan reads are both slices of arrays,
with no Python string per row on either way.

Write protocol (the TableWriter/TableFinish contract):
    h = begin_insert(table[, create_columns=...])   # per query
    append(h, columns, nulls)                       # per task, any thread
    finish_insert(h) -> rows                        # atomic publish
    abort_insert(h)                                 # rollback: no trace
Appends stage into the handle, invisible to readers until
finish_insert -- the reference's ConnectorPageSink.finish() ->
ConnectorMetadata.finishInsert() publish point.
"""

from __future__ import annotations

import functools
import threading
import uuid
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from .. import types as T
from ..block import HostStrings, batch_from_numpy

__all__ = ["SCHEMA", "create_table", "drop_table", "reset",
           "table_row_count", "generate_columns", "generate_batch",
           "column_type", "begin_insert", "append", "finish_insert",
           "abort_insert", "table_names", "table_properties",
           "table_workers", "scan_snapshot", "on_publish"]


class _Table:
    def __init__(self, columns: List[str], types: List[T.Type],
                 workers: int = 1):
        self.columns = list(columns)
        self.types = list(types)
        # how many workers the table's rows are spread over (WITH
        # (workers = N)): N contiguous row ranges in the table's order,
        # as upstream's memory connector keeps a table's pages on the
        # workers that wrote them. No key is implied. A statement runs
        # over the chips its tables are spread over (exec/runner.
        # placement_mesh); 1 is one chip, as every table was before
        self.workers = workers
        # one column + null mask per column: HostStrings for strings,
        # object dtype for long decimals/arrays, native dtypes otherwise
        self.values: List[np.ndarray] = [_stored(t, []) for t in types]
        self.nulls: List[np.ndarray] = [
            np.array([], dtype=bool) for _ in types]
        # column index -> (lo, hi) or None, of the arrays now published
        self.ranges: Dict[int, Optional[tuple]] = {}

    @property
    def row_count(self) -> int:
        return len(self.values[0]) if self.values else 0


def _storage_dtype(ty: T.Type):
    if ty.base in ("array", "map", "row") or \
            (ty.is_decimal and not ty.is_short_decimal):
        return object
    return ty.to_dtype()


def _stored(ty: T.Type, chunks: Sequence):
    """`chunks` of one column as the one array the table keeps."""
    if ty.is_string:
        return HostStrings.concat(
            [HostStrings.from_objects(c) for c in chunks])
    dt = _storage_dtype(ty)
    if not chunks:
        return np.array([], dtype=dt)
    return np.concatenate([_to_object(c) if dt == object
                           else np.asarray(c, dtype=dt) for c in chunks])


_lock = threading.RLock()
_tables: Dict[str, _Table] = {}
_pending: Dict[str, dict] = {}  # handle id -> staging
_versions: Dict[str, int] = {}  # table -> mutation counter
# (table, new version) of each bump made under `_lock`, told to the
# listeners once it is released (`_publishes`)
_bumped: List[tuple] = []
_publish_listeners: List[Callable[[str, int], None]] = []


def table_version(name: str) -> int:
    """Monotonic per-table mutation counter: fragment-result caching
    and the resident tier (exec/resident.py) key on it, so what they
    keep of a table invalidates when the table changes."""
    with _lock:
        return _versions.get(name, 0)


def on_publish(listener: Callable[[str, int], None]) -> None:
    """Call `listener(table, version)` whenever `table` moves to a new
    version (create, publish, rewrite, drop), in the thread that moved
    it, once the store's lock is released: a listener may take locks
    of its own, and the store never waits on them."""
    with _lock:
        _publish_listeners.append(listener)


def _bump_version(name: str) -> None:
    _versions[name] = version = _versions.get(name, 0) + 1
    _bumped.append((name, version))


def _publishes(fn):
    """`fn` may bump versions under the store's lock: once it returns
    (or raises) and the lock is let go, tell each bump to the
    listeners. A thread may tell another's bump; a listener takes the
    newest version it is told."""
    @functools.wraps(fn)
    def told(*args, **kwargs):
        try:
            return fn(*args, **kwargs)
        finally:
            with _lock:
                bumped = list(_bumped)
                del _bumped[:]
                listeners = list(_publish_listeners)
            for name, version in bumped:
                for listener in listeners:
                    listener(name, version)
    return told


class SCHEMA(dict):  # noqa: N801 - registry expects a SCHEMA mapping
    """Live view: table -> {column: Type} (reads the store)."""

    def __getitem__(self, table):
        with _lock:
            t = _tables[table]
            return {c: ty for c, ty in zip(t.columns, t.types)}

    def __contains__(self, table):
        with _lock:
            return table in _tables

    def __iter__(self):
        with _lock:
            return iter(list(_tables))

    def __len__(self):
        with _lock:
            return len(_tables)

    def keys(self):
        with _lock:
            return list(_tables)

    def items(self):
        return [(t, self[t]) for t in self.keys()]

    def values(self):
        return [self[t] for t in self.keys()]


SCHEMA = SCHEMA()


def table_names() -> List[str]:
    with _lock:
        return sorted(_tables)


@_publishes
def reset() -> None:
    """Test hook: drop everything."""
    with _lock:
        for name in list(_tables):
            _bump_version(name)
        _tables.clear()
        _pending.clear()


def table_properties(given: dict) -> dict:
    """The properties of a CREATE TABLE as the store takes them: the
    one it has is `workers`, a positive integer; anything else, or
    another property, is the statement's error."""
    unknown = sorted(set(given) - {"workers"})
    if unknown:
        raise ValueError(f"catalog 'memory' has no table property "
                         f"{unknown[0]!r} (it has: workers)")
    if "workers" not in given:
        return {}
    text = str(given["workers"])
    if not text.isdigit() or int(text) < 1:
        raise ValueError(f"memory table property workers needs a "
                         f"positive integer, got {text!r}")
    return {"workers": int(text)}


def table_workers(table: str) -> int:
    """Workers `table`'s rows are spread over; 1 for an unknown table
    (the scan's own error says so where it is read)."""
    with _lock:
        t = _tables.get(table)
        return t.workers if t is not None else 1


def table_properties_of(table: str) -> dict:
    """What `table` was created WITH, as `table_properties` gave it."""
    workers = table_workers(table)
    return {"workers": workers} if workers > 1 else {}


@_publishes
def create_table(name: str, columns: Sequence[str],
                 types: Sequence[T.Type],
                 if_not_exists: bool = False,
                 properties: Optional[dict] = None) -> None:
    with _lock:
        _create_locked(name, columns, types, if_not_exists, properties)


def _create_locked(name: str, columns: Sequence[str],
                   types: Sequence[T.Type], if_not_exists: bool = False,
                   properties: Optional[dict] = None) -> None:
    if name in _tables:
        if if_not_exists:
            return
        raise ValueError(f"memory table {name!r} already exists")
    _tables[name] = _Table(
        list(columns), list(types),
        table_properties(properties or {}).get("workers", 1))
    _bump_version(name)


@_publishes
def drop_table(name: str, if_exists: bool = False) -> None:
    with _lock:
        if name not in _tables and not if_exists:
            raise KeyError(f"no memory table {name!r}")
        _tables.pop(name, None)
        _bump_version(name)


def column_type(table: str, column: str) -> T.Type:
    with _lock:
        t = _tables[table]
        return t.types[t.columns.index(column)]


def table_row_count(table: str, sf: float = 0.0) -> int:
    with _lock:
        return _tables[table].row_count


def scan_snapshot(table: str, columns: Sequence[str]):
    """A whole-table scan's read, as one publish left the table: its
    version, its row count and each column's values and null mask (in
    `columns` order), read together under the store's lock, so that no
    scan pairs one version with another's rows."""
    with _lock:
        t = _tables[table]
        idx = [t.columns.index(c) for c in columns]
        return (_versions.get(table, 0), t.row_count,
                [_view(t.values[i][:]) for i in idx],
                [_view(t.nulls[i][:]) for i in idx])


def generate_columns(table: str, sf: float, columns: Sequence[str],
                     start: int = 0, count: Optional[int] = None
                     ) -> Dict[str, np.ndarray]:
    """Scan surface (sf is ignored -- stored tables have one size)."""
    with _lock:
        t = _tables[table]
        n = t.row_count
        count = n - start if count is None else count
        out = {}
        for c in columns:
            i = t.columns.index(c)
            out[c] = _view(t.values[i][start:start + count])
        return out


def _view(col):
    """A scan's slice of a published column. Published arrays are
    replaced (finish_insert, replace_table) and never written, so a
    scan reads them in place: the view is read-only, and a caller that
    would write gets numpy's error instead of another table's rows."""
    if isinstance(col, np.ndarray):
        col.flags.writeable = False
    return col


def column_range(table: str, column: str, sf: float = 0.0):
    """Exact (lo, hi) over the stored NON-NULL values (narrow-width
    execution stats). None for empty/all-null/non-integer columns --
    width inference then refuses to narrow. Exact at plan time; the
    staging-time guard (plan/widths.checked_physical_dtypes) covers
    any write racing plan and execution. Read once per published
    column: every publish (finish_insert, replace_table) starts the
    table's ranges anew."""
    with _lock:
        t = _tables.get(table)
        if t is None:
            raise KeyError(f"no memory table {table!r}")
        i = t.columns.index(column)
        if i in t.ranges:
            return t.ranges[i]
        vals = t.values[i]
        nulls = t.nulls[i]
        ranges = t.ranges
    found = None
    if vals.dtype != object and vals.dtype.kind in "iu":
        live = vals[~nulls] if nulls.any() else vals
        if len(live):
            found = (int(live.min()), int(live.max()))
    with _lock:
        if t.ranges is ranges:  # no publish since the arrays were read
            ranges[i] = found
    return found


def generate_nulls(table: str, columns: Sequence[str], start: int = 0,
                   count: Optional[int] = None) -> Dict[str, np.ndarray]:
    with _lock:
        t = _tables[table]
        n = t.row_count
        count = n - start if count is None else count
        return {c: _view(t.nulls[t.columns.index(c)][start:start + count])
                for c in columns}


def generate_batch(table: str, sf: float, columns: Sequence[str],
                   start: int = 0, count: Optional[int] = None,
                   capacity: Optional[int] = None):
    with _lock:
        t = _tables[table]
        n = t.row_count
        count = n - start if count is None else count
        vals = []
        nulls = []
        types = []
        for c in columns:
            i = t.columns.index(c)
            vals.append(t.values[i][start:start + count])
            nulls.append(t.nulls[i][start:start + count])
            types.append(t.types[i])
    cap = capacity or max(count, 1)
    return batch_from_numpy(types, vals, capacity=cap, nulls=nulls)


# -- write protocol ---------------------------------------------------------


@_publishes
def begin_insert(table: str,
                 create_columns: Optional[Sequence[str]] = None,
                 create_types: Optional[Sequence[T.Type]] = None,
                 properties: Optional[dict] = None) -> str:
    """Start a staged insert; with create_columns/types this is CTAS:
    the (empty) table is created NOW, with the statement's
    `properties`, so concurrent CTAS to one name conflict early, and
    dropped again on abort."""
    with _lock:
        created = False
        if create_columns is not None:
            _create_locked(table, create_columns, create_types,
                           properties=properties)
            created = True
        if table not in _tables:
            raise KeyError(f"no memory table {table!r}")
        h = f"ins_{uuid.uuid4().hex[:12]}"
        t = _tables[table]
        _pending[h] = {"table": table, "created": created,
                       "values": [[] for _ in t.columns],
                       "nulls": [[] for _ in t.columns]}
        return h


def append(handle: str, columns: Sequence[np.ndarray],
           nulls: Optional[Sequence[np.ndarray]] = None) -> int:
    """Stage one result chunk (a task's output). Returns rows staged."""
    with _lock:
        st = _pending[handle]
        t = _tables[st["table"]]
        if len(columns) != len(t.columns):
            raise ValueError(
                f"insert arity {len(columns)} != table arity "
                f"{len(t.columns)}")
        n = len(columns[0]) if len(columns) else 0
        for i, col in enumerate(columns):
            st["values"][i].append(
                HostStrings.from_objects(col) if t.types[i].is_string
                else np.asarray(col))
            st["nulls"][i].append(
                np.asarray(nulls[i], dtype=bool) if nulls is not None
                else np.zeros(n, dtype=bool))
        return n


@_publishes
def finish_insert(handle: str) -> int:
    """Atomic publish of every staged chunk; returns rows written.
    Column by column, each column's chunks let go as soon as they are
    one array: the peak is the table and one column more."""
    with _lock:
        table = _pending[handle]["table"]
    with write_lock(table), _lock:
        st = _pending.pop(handle)
        t = _tables[st["table"]]
        rows = sum(len(c) for c in st["values"][0]) if t.columns else 0
        values, nulls = [], []
        for i, ty in enumerate(t.types):
            chunks, st["values"][i] = st["values"][i], None
            values.append(_stored(ty, [t.values[i]] + chunks)
                          if chunks else t.values[i])
            nulls.append(np.concatenate([t.nulls[i]] + st["nulls"][i])
                         if chunks else t.nulls[i])
        t.values, t.nulls, t.ranges = values, nulls, {}
        _bump_version(st["table"])
        return rows


def _to_object(arr) -> np.ndarray:
    out = np.empty(len(arr), dtype=object)
    for i, v in enumerate(arr):
        out[i] = v
    return out


@_publishes
def abort_insert(handle: str) -> None:
    with _lock:
        st = _pending.pop(handle, None)
        if st is not None and st["created"]:
            _tables.pop(st["table"], None)
            _bump_version(st["table"])


def data_version(table: str) -> int:
    """Fragment-result-cache seam (alias of table_version)."""
    return table_version(table)


@_publishes
def replace_table(name: str, columns: Sequence[np.ndarray],
                  nulls: Sequence[np.ndarray]) -> int:
    """Atomically swap a table's contents (DELETE/UPDATE rewrite sink).
    Returns the OLD row count."""
    with _lock:
        t = _tables[name]
        if len(columns) != len(t.columns):
            raise ValueError(
                f"rewrite arity {len(columns)} != table arity "
                f"{len(t.columns)}")
        old = t.row_count
        t.values = [_stored(ty, [c]) for ty, c in zip(t.types, columns)]
        t.nulls = [np.asarray(n, dtype=bool) for n in nulls]
        t.ranges = {}
        _bump_version(name)
        return old


_write_locks: Dict[str, threading.Lock] = {}


def write_lock(name: str) -> threading.Lock:
    """Per-table writer mutex: DML rewrites hold it across their whole
    read-compute-swap so committed concurrent inserts can't vanish
    under the replace; inserts take it around their publish."""
    with _lock:
        lk = _write_locks.get(name)
        if lk is None:
            lk = _write_locks[name] = threading.Lock()
        return lk
