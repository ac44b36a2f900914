"""Parquet connector: real files through the standard connector seam.

Reference surface: presto-parquet (reader/writer, column indexes) +
presto-hive's split/page-source path (ConnectorPageSource.getNextPage).
This slice decodes through pyarrow (the reference links parquet-mr /
its own decoder; the decode library is not the architecture) and stages
straight into the SAME columnar batches every other connector produces,
so the whole engine -- stats, dynamic filtering, adaptive capacities,
mesh sharding -- runs unchanged over files.

A scan is one producer, `scan_pieces`: the row groups its row range
touches are its pieces, those whose footer statistics exclude the
pushed-down range are skipped, and the rest are read (file bytes ->
arrow, hop ``connector_read``) and decoded (arrow -> the engine's lanes
and null masks, hop ``decode``) once, a row group a task on a small
thread pool, and handed over in file order while later groups are
still being read. Who consumes them decides where the lanes are
assembled: `read_columns` on the host, exec/runner's stager on the
device, a group's lanes decoded straight into the narrowed dtypes the
plan proved (plan/widths.py) and put while the next groups decompress.
Nothing on the way makes a Python object per value: a short decimal is
its int64 lane, a date its int32, a string column a `block.HostStrings`
built from arrow's offsets and bytes. `column_range` answers from the
footers' min/max, so plan/widths.py narrows a file scan's lanes as it
narrows a memory table's.

The writer (`engine_to_arrow`, `open_writer`; the staged commit is the
shared `lake_sink.LakeSink`) is the same conversions the other way, a
row group at a time. Short decimals are stored as Parquet INT64 (INT32
at precision 9 or less) with the DECIMAL logical type; the engine's
types ride the file's key-value metadata, so a table read back has the
varchar widths it was written with.

Tables register explicitly (`register_table(name, path)`) or by a
write; engine types of a foreign file derive from its schema (decimals
-> scaled int64/int128 lanes, date32 -> day numbers, strings ->
varchar)."""

from __future__ import annotations

import collections
import itertools
import json
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

from .. import types as T
from ..block import HostStrings, batch_from_numpy

__all__ = ["SCHEMA", "register_table", "unregister_table", "reset",
           "table_row_count", "scan_pieces", "read_columns",
           "generate_columns",
           "generate_nulls", "generate_batch", "column_type",
           "column_range", "write_table", "row_groups_matching"]

# the writer's defaults (benchmarks/configs/tpch_sf10_parquet.json
# states them under `assumed`)
ROW_GROUP_ROWS = 1 << 20
CODEC = "snappy"
_TYPES_KEY = b"presto_tpu.types"

_lock = threading.RLock()
_tables: Dict[str, dict] = {}  # name -> {path, pf, schema{col: Type}, ...}


def _engine_type(field) -> T.Type:
    import pyarrow as pa
    t = field.type
    if pa.types.is_boolean(t):
        return T.BOOLEAN
    if pa.types.is_int8(t):
        return T.TINYINT
    if pa.types.is_int16(t):
        return T.SMALLINT
    if pa.types.is_int32(t):
        return T.INTEGER
    if pa.types.is_integer(t):
        return T.BIGINT
    if pa.types.is_float32(t):
        return T.REAL
    if pa.types.is_floating(t):
        return T.DOUBLE
    if pa.types.is_decimal(t):
        return T.decimal(t.precision, t.scale)
    if pa.types.is_date(t):
        return T.DATE
    if pa.types.is_timestamp(t):
        return T.TIMESTAMP
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        return T.varchar(1 << 19)  # width discovered per batch at stage
    raise NotImplementedError(f"parquet type {t} for {field.name}")


def engine_schema(arrow_schema) -> Dict[str, T.Type]:
    """Engine types of a file's columns: those its writer left in the
    key-value metadata where it was this engine's, else what the arrow
    types say."""
    kept = {}
    try:
        kept = {c: T.parse_type(s) for c, s in json.loads(
            (arrow_schema.metadata or {}).get(_TYPES_KEY, b"{}")).items()}
    except ValueError:
        pass  # another writer's metadata under the key: the arrow types
    return {f.name: kept.get(f.name) or _engine_type(f)
            for f in arrow_schema}


class SCHEMA(dict):  # noqa: N801 - registry surface
    def __getitem__(self, table):
        with _lock:
            return dict(_tables[table]["schema"])

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


def register_table(name: str, path: str) -> Dict[str, T.Type]:
    import pyarrow.parquet as pq
    pf = pq.ParquetFile(path)
    schema = engine_schema(pf.schema_arrow)
    with _lock:
        # mtime snapshot taken WITH the handle: result caching keys on
        # the data this handle actually reads (an overwritten file
        # serves stale rows until re-registration, and re-registration
        # refreshes handle, version and footer ranges together)
        _tables[name] = {"path": path, "pf": pf, "schema": schema,
                         "mtime": os.path.getmtime(path), "ranges": {}}
    return schema


def unregister_table(name: str) -> None:
    with _lock:
        _tables.pop(name, None)


def reset() -> None:
    with _lock:
        _tables.clear()


def column_type(table: str, column: str) -> T.Type:
    with _lock:
        return _tables[table]["schema"][column]


def table_row_count(table: str, sf: float = 0.0) -> int:
    with _lock:
        return _tables[table]["pf"].metadata.num_rows


def data_version(table: str) -> float:
    """Fragment-result-cache seam: the registration-time mtime snapshot
    (what the pinned reader handle actually serves)."""
    with _lock:
        return _tables[table]["mtime"]


def stored_bytes(table: str) -> int:
    """The table's file size: what a write reports as `write_bytes`."""
    with _lock:
        return os.path.getsize(_tables[table]["path"])


# ---------------------------------------------------------------------------
# footer statistics: row-group pruning and column ranges
# ---------------------------------------------------------------------------


def _engine_repr(v):
    """Parquet stat value -> this engine's lane representation
    (dates = epoch days, timestamps = micros, decimals = scaled)."""
    import datetime
    import decimal
    if isinstance(v, datetime.datetime):
        return int(v.replace(tzinfo=datetime.timezone.utc)
                   .timestamp() * 1_000_000)
    if isinstance(v, datetime.date):
        return (v - datetime.date(1970, 1, 1)).days
    if isinstance(v, decimal.Decimal):
        return int(v.scaleb(-v.as_tuple().exponent))
    return v


def _group_stats(md, column_index: int):
    """(min, max) of each row group in engine representation; None for
    a group whose footer gives none."""
    out = []
    for g in range(md.num_row_groups):
        st = md.row_group(g).column(column_index).statistics
        if st is None or not st.has_min_max:
            out.append(None)
        else:
            out.append((_engine_repr(st.min), _engine_repr(st.max)))
    return out


def row_groups_matching(table: str,
                        predicate: Optional[Tuple[str, object, object]]
                        ) -> List[int]:
    """Row groups whose min/max statistics can satisfy
    `(column, lo, hi)` (None bound = unbounded) -- the row-group-level
    predicate pushdown hook."""
    with _lock:
        pf = _tables[table]["pf"]
    md = pf.metadata
    if predicate is None:
        return list(range(md.num_row_groups))
    col, lo, hi = predicate
    out = []
    for g, mm in enumerate(_group_stats(
            md, pf.schema_arrow.get_field_index(col))):
        if mm is not None and ((lo is not None and mm[1] < lo) or
                               (hi is not None and mm[0] > hi)):
            continue
        out.append(g)
    return out


def column_range(table: str, column: str, sf: float = 0.0):
    """(lo, hi) over the column's non-null values from the footers'
    min/max (narrow-width execution stats), read once per registered
    file. None for a column that is not an integer lane, an empty or
    all-null one, or a file with a row group whose statistics are
    absent: width inference then refuses to narrow. A file overwritten
    behind the handle is covered by the staging-time guard
    (plan/widths.checked_physical_dtypes)."""
    with _lock:
        ent = _tables.get(table)
        if ent is None:
            raise KeyError(f"no parquet table {table!r}")
        if column in ent["ranges"]:
            return ent["ranges"][column]
        pf, ty = ent["pf"], ent["schema"][column]
    found = None
    if ty.is_fixed_width and ty.to_dtype().kind in "iu" and \
            not (ty.is_decimal and not ty.is_short_decimal):
        md = pf.metadata
        ci = pf.schema_arrow.get_field_index(column)
        stats = _group_stats(md, ci)
        live = [mm for g, mm in enumerate(stats) if mm is not None]
        known = all(
            mm is not None or _all_null(md.row_group(g).column(ci))
            for g, mm in enumerate(stats))
        if live and known:
            found = (min(mm[0] for mm in live), max(mm[1] for mm in live))
    with _lock:
        ent["ranges"][column] = found
    return found


def _all_null(chunk) -> bool:
    st = chunk.statistics
    return st is not None and st.has_null_count and \
        st.null_count == chunk.num_values


# ---------------------------------------------------------------------------
# arrow <-> engine lanes (shared with the ORC module)
# ---------------------------------------------------------------------------


def _ragged(width: int, lengths: np.ndarray) -> np.ndarray:
    """(n, width) mask of the bytes each row of a `HostStrings` holds:
    row-major, it lists them in the order arrow's data buffer does."""
    return np.arange(width, dtype=np.int32)[None, :] < lengths[:, None]


def arrow_to_engine(arr, ty: T.Type
                    ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """One arrow array -> (engine values, null mask or None where no
    row is null). A null row's value is 0 (an empty string)."""
    import pyarrow as pa
    import pyarrow.compute as pc
    n, off = len(arr), arr.offset
    nulls = np.asarray(arr.is_null().to_numpy(zero_copy_only=False)) \
        if arr.null_count else None
    at = arr.type
    if ty.is_string:
        if nulls is not None:
            arr = pc.fill_null(arr, "")  # a null row holds no bytes
            off = arr.offset
        wide = pa.types.is_large_string(arr.type)
        offs = np.frombuffer(arr.buffers()[1],
                             dtype=np.int64 if wide else np.int32
                             )[off:off + n + 1]
        lengths = np.diff(offs).astype(np.int32)
        chars = np.zeros((n, max(int(lengths.max()) if n else 0, 1)),
                         dtype=np.uint8)
        if n and offs[-1] > offs[0]:
            chars[_ragged(chars.shape[1], lengths)] = np.frombuffer(
                arr.buffers()[2], dtype=np.uint8)[offs[0]:offs[-1]]
        return HostStrings(chars, lengths), nulls
    if ty.is_decimal and ty.is_short_decimal and at.scale == ty.scale:
        # a decimal's unscaled value is a little-endian two's-complement
        # integer of 4, 8, 16 or 32 bytes; for p <= 18 it fits int64, so
        # the LOW word is the value
        if at.byte_width == 4:
            vals = np.frombuffer(arr.buffers()[1], dtype=np.int32
                                 )[off:off + n].astype(np.int64)
        else:
            words = at.byte_width // 8
            vals = np.frombuffer(arr.buffers()[1], dtype=np.int64
                                 )[off * words:(off + n) * words:words]
    elif ty.is_decimal:
        # long decimals (int128 lanes) decode exactly through Python ints
        vals = np.array([0 if v is None else int(v.scaleb(ty.scale))
                         for v in arr.to_pylist()], dtype=object)
        return (vals.astype(np.int64) if ty.is_short_decimal else vals), \
            nulls
    elif ty.base == "boolean":
        vals = arr.to_numpy(zero_copy_only=False)
        if nulls is not None:
            vals = np.where(nulls, False, vals).astype(bool)
        return vals, nulls
    else:
        if ty.base == "date":
            arr = arr.cast(pa.date32()).cast(pa.int32())
        elif ty.base == "timestamp":
            arr = arr.cast(pa.timestamp("us")).cast(pa.int64())
        vals = np.frombuffer(arr.buffers()[1],
                             dtype=arr.type.to_pandas_dtype()
                             )[arr.offset:arr.offset + n]
    if vals.dtype != ty.to_dtype() or nulls is not None:
        vals = vals.astype(ty.to_dtype())  # the caller's own copy
        if nulls is not None:
            vals[nulls] = 0
    return vals, nulls


def arrow_type(ty: T.Type):
    import pyarrow as pa
    if ty.is_decimal:
        return pa.decimal64(ty.precision, ty.scale) if ty.is_short_decimal \
            else pa.decimal128(ty.precision, ty.scale)
    if ty.base == "date":
        return pa.date32()
    if ty.base == "timestamp":
        return pa.timestamp("us")
    if ty.is_string:
        return pa.string()
    return pa.from_numpy_dtype(ty.to_dtype())


def arrow_schema(types: Dict[str, T.Type]):
    import pyarrow as pa
    return pa.schema(
        [pa.field(c, arrow_type(ty)) for c, ty in types.items()],
        metadata={_TYPES_KEY: json.dumps(
            {c: str(ty) for c, ty in types.items()}).encode()})


def _column_to_arrow(vals, ty: T.Type, nulls: Optional[np.ndarray]):
    import pyarrow as pa
    n = len(vals)
    if nulls is not None:
        nulls = np.asarray(nulls, dtype=bool)
        if not nulls.any():
            nulls = None
    if ty.is_decimal and not ty.is_short_decimal:
        import decimal
        return pa.array(
            [None if nulls is not None and nulls[i]
             else decimal.Decimal(int(v)).scaleb(-ty.scale)
             for i, v in enumerate(vals)], type=arrow_type(ty))
    if ty.is_string or ty.is_decimal:
        valid, n_null = None, 0
        if nulls is not None:
            valid = pa.py_buffer(np.packbits(~nulls, bitorder="little"))
            n_null = int(nulls.sum())
        if ty.is_decimal:
            lanes = np.ascontiguousarray(vals, dtype=np.int64)
            return pa.Array.from_buffers(arrow_type(ty), n,
                                         [valid, pa.py_buffer(lanes)],
                                         n_null)
        hs = HostStrings.from_objects(vals)  # itself, where it is one
        lengths = hs.lengths if nulls is None else \
            np.where(nulls, 0, hs.lengths).astype(np.int32)
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(lengths, out=offsets[1:])
        if offsets[-1] >= 1 << 31:
            raise ValueError(f"{offsets[-1]} bytes of strings in one "
                             "arrow array: write fewer rows at a time")
        return pa.Array.from_buffers(
            pa.string(), n,
            [valid, pa.py_buffer(offsets.astype(np.int32)),
             pa.py_buffer(hs.chars[_ragged(hs.chars.shape[1], lengths)])],
            n_null)
    lanes = np.asarray(vals, dtype=ty.to_dtype())
    arr = pa.array(lanes, mask=nulls)
    return arr if arr.type == arrow_type(ty) else arr.cast(arrow_type(ty))


def engine_to_arrow(columns: Dict[str, np.ndarray],
                    types: Dict[str, T.Type],
                    nulls: Optional[Dict[str, np.ndarray]] = None):
    """Engine-representation columns -> a pyarrow Table, lanes and
    buffers handed over as they are (shared by the parquet and ORC
    sinks). Only a long decimal, which the engine itself holds as
    Python ints, is converted value by value."""
    import pyarrow as pa
    return pa.Table.from_arrays(
        [_column_to_arrow(vals, types[c], (nulls or {}).get(c))
         for c, vals in columns.items()],
        schema=arrow_schema({c: types[c] for c in columns}))


# ---------------------------------------------------------------------------
# the scan
# ---------------------------------------------------------------------------

_pool_lock = threading.Lock()
_pool = None
# what a scan may hold of pieces read and not yet taken by its consumer
_IN_FLIGHT_BYTES = 1 << 30


def _pool_width() -> int:
    # past eight threads a scan's groups cost more thread-seconds each
    # and the consumer's thread wants a core too (PERF.md, PR 33: on 13
    # cores a scan took 464 ms on 8 threads, 552 on 10, 525 on 12)
    return min(8, os.cpu_count() or 1)


def _decode_pool():
    """The threads every scan's pieces are read and decoded on (pyarrow
    and numpy's passes over a piece release the GIL)."""
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = ThreadPoolExecutor(max_workers=_pool_width(),
                                       thread_name_prefix="lake-decode")
        return _pool


class Piece:
    """One row group or stripe of a scan, decoded: `values` and `nulls`
    by column over its `rows` rows (a mask is None where no row is
    null); for a scan that names its lanes' dtypes, a lane and a mask
    each of `whole` rows, zero past `rows`. `refused`: a value did not
    fit the dtype named for its lane, and the piece holds nothing."""

    __slots__ = ("rows", "values", "nulls", "refused", "read_at",
                 "decode_at")

    def __init__(self, rows: int):
        self.rows = rows
        self.values, self.nulls = {}, {}
        self.refused = False
        self.read_at = self.decode_at = None


class PieceScan:
    """The one producer of a lake scan's decoded pieces. Iterating it
    reads and decodes each piece on the decode pool and yields them in
    file order, at most `depth` of them submitted and not yet taken;
    who iterates decides where the lanes are assembled: `host_columns`
    into whole host lanes, exec/runner's stager on the device.

    `sources` are `(source, first, rows, whole)`: `read(source)` gives
    the arrow table of a piece of `whole` rows, of which the scan takes
    `rows` from `first`; with no `read` a source is its table. With
    `dtypes` (one a column: the lane's dtype) a column decodes straight
    into a fresh lane of that dtype and of `whole` rows, proved against
    it while the piece is cache-resident (plan/widths.lane_holds), and
    into a mask of its own; without, into the logical lanes
    `arrow_to_engine` gives. `room` is the most rows any piece of the
    file holds, whatever this scan picked: a consumer that lands such
    lanes one after another leaves that much past its last row.

    Pool threads have no ambient collector: a piece carries the clock
    readings of its read and its decode, and `record`, called by the
    consumer on the statement's thread, records each hop once, from its
    first piece's entry to its last piece's exit, and beside the two
    envelopes what they hide: the pieces' own durations summed (the
    pool's thread-seconds: ``lake_read_thread_us``,
    ``lake_decode_thread_us``) and how long the statement's thread
    stood waiting for a piece (``lake_consumer_wait_us``)."""

    def __init__(self, sources, read, schema: Dict[str, T.Type],
                 columns: Sequence[str], touched: int, file_bytes: int,
                 piece_bytes: int = 0, dtypes=None, room: int = 0):
        self.sources = list(sources)
        self.schema, self.columns = schema, list(columns)
        self.touched, self.file_bytes = touched, file_bytes
        self.dtypes = None if dtypes is None else \
            [np.dtype(dt) for dt in dtypes]
        self.rows = sum(src[2] for src in self.sources)
        self.room = room
        self.depth = max(2, min(2 * _pool_width(),
                                _IN_FLIGHT_BYTES // max(piece_bytes, 1)))
        self._read = read
        self._read_at = self._decode_at = None
        self._read_s = self._decode_s = self._wait_s = 0.0

    def _piece(self, src) -> Piece:
        source, first, rows, whole = src
        piece = Piece(rows)
        if self._read is None:
            tbl = source
        else:
            t0 = time.time()
            with TraceAnnotation("presto:connector_read"):
                tbl = self._read(source)
            piece.read_at = (t0, time.time())
        if rows != tbl.num_rows:
            tbl = tbl.slice(first, rows)
        t0 = time.time()
        with TraceAnnotation("presto:decode"):
            for k, c in enumerate(self.columns):
                col = tbl.column(c)
                arr = col.chunk(0) if col.num_chunks == 1 \
                    else col.combine_chunks()
                vals, nl = arrow_to_engine(arr, self.schema[c])
                if self.dtypes is not None:
                    vals, nl = _into_lane(vals, nl, self.dtypes[k], whole)
                    if vals is None:
                        piece.refused = True
                        break
                piece.values[c], piece.nulls[c] = vals, nl
        piece.decode_at = (t0, time.time())
        return piece

    def __iter__(self):
        pool, todo = _decode_pool(), iter(self.sources)
        pending = collections.deque(
            pool.submit(self._piece, src)
            for src in itertools.islice(todo, self.depth))
        try:
            while pending:
                t0 = time.time()
                piece = pending.popleft().result()
                self._wait_s += time.time() - t0
                src = next(todo, None)
                if src is not None:
                    pending.append(pool.submit(self._piece, src))
                self._read_at = _span_of(self._read_at, piece.read_at)
                self._decode_at = _span_of(self._decode_at, piece.decode_at)
                if piece.read_at is not None:
                    self._read_s += piece.read_at[1] - piece.read_at[0]
                self._decode_s += piece.decode_at[1] - piece.decode_at[0]
                yield piece
        finally:
            # an error, or a consumer that stopped: nothing of this scan
            # stays on the pool
            for f in pending:
                f.cancel()
            wait(pending)

    def record(self, decoded_bytes: int, pipelined: bool,
               decode_end: float = 0.0) -> None:
        """The scan's hops and the statement's ``lake_*`` counters, once
        its pieces are all taken. `decoded_bytes`: the lanes and masks
        the consumer got; `pipelined`: they went to the device piece by
        piece; `decode_end`: where a consumer's own assembly of the
        decoded pieces ended, if after the last piece's decode."""
        from ..exec.datapath import hop_interval
        from ..exec.stats import note
        now = time.time()
        if self._read is not None:
            hop_interval("connector_read", self.file_bytes,
                         *(self._read_at or (now, now)))
            note("lake_read_thread_us", round(self._read_s * 1e6))
        t0, t1 = self._decode_at or (now, now)
        hop_interval("decode", decoded_bytes, t0, max(t1, decode_end))
        note("lake_row_groups_total", self.touched)
        note("lake_row_groups_read", len(self.sources))
        note("lake_row_groups_pipelined",
             len(self.sources) if pipelined else 0)
        note("lake_file_bytes", self.file_bytes)
        note("lake_decoded_bytes", decoded_bytes)
        note("lake_decode_thread_us", round(self._decode_s * 1e6))
        note("lake_consumer_wait_us", round(self._wait_s * 1e6))


def _span_of(so_far, at):
    """The interval from the earliest entry to the latest exit."""
    if so_far is None or at is None:
        return so_far or at
    return min(so_far[0], at[0]), max(so_far[1], at[1])


def _into_lane(vals: np.ndarray, nl: Optional[np.ndarray], dt: np.dtype,
               whole: int):
    """A piece's decoded column as a fresh lane of `dt` and a mask, each
    of `whole` rows; (None, None) where a value does not fit `dt`. A
    null row's value is 0 (`arrow_to_engine`), which every lane holds,
    so the proof over all rows is the proof over the rows that are not
    null."""
    from ..plan.widths import lane_holds
    if dt != vals.dtype and not lane_holds(dt, vals):
        return None, None
    n = len(vals)
    lane = np.empty(whole, dtype=dt)
    lane[:n] = vals
    lane[n:] = 0
    mask = np.zeros(whole, dtype=bool)
    if nl is not None:
        mask[:n] = nl
    return lane, mask


def _empty_lane(ty: T.Type, n: int):
    if ty.is_string:
        return None  # its pieces are concatenated: widths differ
    if ty.is_decimal and not ty.is_short_decimal:
        return np.zeros(n, dtype=object)
    return np.empty(n, dtype=ty.to_dtype())


def host_columns(scan: PieceScan
                 ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """The producer's host-side consumer: whole lanes and null masks,
    each piece copied into its slice as it arrives while later pieces
    are still being read (shared with the ORC module). For the callers
    that need a scan's columns on the host: a dynamic-filtered scan,
    string columns, `generate_columns`."""
    schema, columns = scan.schema, scan.columns
    values = {c: _empty_lane(schema[c], scan.rows) for c in columns}
    nulls = {c: np.zeros(scan.rows, dtype=bool) for c in columns}
    strings = {c: [] for c in columns if values[c] is None}
    at = 0
    for piece in scan:
        rows = slice(at, at + piece.rows)
        for c in columns:
            if piece.nulls[c] is not None:
                nulls[c][rows] = piece.nulls[c]
            if c in strings:
                strings[c].append(piece.values[c])
            else:
                values[c][rows] = piece.values[c]
        at += piece.rows
    for c, parts in strings.items():
        values[c] = HostStrings.concat(parts)
    scan.record(sum(v.nbytes for v in values.values())
                + sum(n.nbytes for n in nulls.values()),
                pipelined=False, decode_end=time.time())
    return values, nulls


def columns_batch(values, nulls, schema: Dict[str, T.Type],
                  columns: Sequence[str], capacity: Optional[int]):
    """What `read_columns` gave as a device batch."""
    vals = [values[c] for c in columns]
    n = len(vals[0]) if vals else 0
    return batch_from_numpy([schema[c] for c in columns], vals,
                            capacity=capacity or max(n, 1),
                            nulls=[nulls[c] for c in columns])


def scan_pieces(table: str, columns: Sequence[str], start: int = 0,
                count: Optional[int] = None, predicate=None,
                dtypes=None) -> PieceScan:
    """The producer over rows [start, start+count) of `columns`, a row
    group a piece. Row groups the range does not touch, and those
    `predicate` (column, lo, hi) excludes by their statistics, are not
    opened: with a predicate the pieces may hold fewer rows than
    `count`."""
    import pyarrow.parquet as pq
    with _lock:
        ent = _tables[table]
    path, md, schema = ent["path"], ent["pf"].metadata, ent["schema"]
    count = md.num_rows - start if count is None else count
    columns = list(columns)
    keep = set(row_groups_matching(table, predicate))
    group_rows = [md.row_group(g).num_rows
                  for g in range(md.num_row_groups)]
    picked, touched, at = [], 0, 0  # picked: (group, first, rows, whole)
    for g, g_rows in enumerate(group_rows):
        lo, hi = max(start - at, 0), min(start + count - at, g_rows)
        at += g_rows
        if lo < hi:
            touched += 1
            if g in keep:
                picked.append((g, lo, hi - lo, g_rows))
    index = [ent["pf"].schema_arrow.get_field_index(c) for c in columns]
    chunks = [[md.row_group(g).column(ci) for ci in index]
              for g, _lo, _n, _whole in picked]

    def read(g: int):
        # a reader of its own: a ParquetFile is one file position
        return pq.ParquetFile(path, metadata=md).read_row_group(
            g, columns=columns, use_threads=False)

    return PieceScan(
        picked, read, schema, columns, touched,
        file_bytes=sum(c.total_compressed_size for g in chunks for c in g),
        piece_bytes=max((sum(c.total_uncompressed_size for c in g)
                         for g in chunks), default=0),
        dtypes=dtypes, room=max(group_rows, default=0))


def read_columns(table: str, columns: Sequence[str], start: int = 0,
                 count: Optional[int] = None, predicate=None
                 ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """Rows [start, start+count) of `columns` as (values, null masks)
    on the host: the one decode of a scan whose columns are needed
    there. Records the hops ``connector_read`` and ``decode`` and the
    statement's ``lake_*`` counters."""
    return host_columns(scan_pieces(table, columns, start, count,
                                    predicate))


def generate_columns(table: str, sf: float, columns: Sequence[str],
                     start: int = 0, count: Optional[int] = None
                     ) -> Dict[str, np.ndarray]:
    return read_columns(table, columns, start, count)[0]


def generate_nulls(table: str, columns: Sequence[str], start: int = 0,
                   count: Optional[int] = None) -> Dict[str, np.ndarray]:
    return read_columns(table, columns, start, count)[1]


def generate_batch(table: str, sf: float, columns: Sequence[str],
                   start: int = 0, count: Optional[int] = None,
                   capacity: Optional[int] = None, predicate=None):
    values, nulls = read_columns(table, columns, start, count, predicate)
    return columns_batch(values, nulls, SCHEMA[table], columns, capacity)


# ---------------------------------------------------------------------------
# the writer: this format's primitives under the SHARED LakeSink
# (lake_sink.py, ConnectorPageSink analog)
# ---------------------------------------------------------------------------


def open_writer(path: str, schema):
    """A writer of row groups at `path` (`write_table(arrow table)`,
    `close()`), with this module's defaults."""
    import pyarrow.parquet as pq
    return pq.ParquetWriter(path, schema, compression=CODEC,
                            store_decimal_as_integer=True)


def read_tables(path: str):
    """The file's row groups as arrow tables, one at a time."""
    import pyarrow.parquet as pq
    pf = pq.ParquetFile(path)
    for g in range(pf.metadata.num_row_groups):
        yield pf.read_row_group(g)


def write_table(path: str, columns: Dict[str, np.ndarray],
                types: Dict[str, T.Type],
                nulls: Optional[Dict[str, np.ndarray]] = None,
                row_group_size: Optional[int] = None) -> None:
    """Write engine-representation columns to a parquet file (the
    fixture writer; a table's writes go through the sink)."""
    w = open_writer(path, arrow_schema({c: types[c] for c in columns}))
    try:
        w.write_table(engine_to_arrow(columns, types, nulls),
                      row_group_size=row_group_size or ROW_GROUP_ROWS)
    finally:
        w.close()


from .lake_sink import LakeSink  # noqa: E402

_sink = LakeSink("parquet", ".parquet", _tables, _lock, open_writer,
                 read_tables, register_table)
set_warehouse = _sink.set_warehouse
write_lock = _sink.write_lock
drop_table = _sink.drop_table
begin_insert = _sink.begin_insert
append = _sink.append
finish_insert = _sink.finish_insert
abort_insert = _sink.abort_insert
replace_table = _sink.replace_table
