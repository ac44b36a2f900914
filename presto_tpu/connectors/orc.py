"""ORC connector: the lake's other first-class columnar format.

Reference surface: presto-orc (OrcBatchRecordReader /
OrcSelectiveRecordReader, writer + DictionaryCompressionOptimizer --
81k LoC incl. tests) behind the same ConnectorPageSource seam as
parquet. This slice decodes through pyarrow's ORC reader (the decode
library is not the architecture) and serves the SAME connector surface
as the parquet module: explicit registration, schema inference into
engine types, range-split stripe reads, and the writer sink contract
(begin_insert/append/finish_insert + create/drop/replace) with
staged-file atomic replace.

Engine difference, documented: pyarrow exposes no per-stripe column
statistics, so ORC scans do not prune stripes by predicate the way the
parquet connector (and the reference's selective reader) does, and
`column_range` proves nothing (a scan stages its logical widths); range
splits and column pruning still apply. The conversion layer
(engine_to_arrow / arrow_to_engine) and the producer of decoded pieces
(PieceScan, with stripes as pieces, and its host-side consumer) are
shared with parquet: one decode a scan, no Python object per value.
Without stripe metadata the stripes a range needs are known only as
they are read, one after another on the scan's thread, so this module
offers no `scan_pieces` and its scans assemble on the host."""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from .. import types as T
from .parquet import (PieceScan, _engine_type, arrow_schema, columns_batch,
                      engine_to_arrow, host_columns)

__all__ = ["SCHEMA", "register_table", "unregister_table", "reset",
           "table_row_count", "read_columns", "generate_columns",
           "generate_nulls", "generate_batch", "column_type",
           "column_range", "write_table",
           "set_warehouse", "data_version"]

_lock = threading.RLock()
_tables: Dict[str, dict] = {}


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
    import pyarrow.orc as orc
    f = orc.ORCFile(path)
    schema = {fld.name: _engine_type(fld) for fld in f.schema}
    with _lock:
        _tables[name] = {"path": path, "f": f, "schema": schema,
                         "mtime": os.path.getmtime(path)}
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
        return _tables[table]["f"].nrows


def data_version(table: str) -> float:
    with _lock:
        return _tables[table]["mtime"]


def stored_bytes(table: str) -> int:
    with _lock:
        return os.path.getsize(_tables[table]["path"])


def column_range(table: str, column: str, sf: float = 0.0):
    """None: no statistics, so width inference refuses to narrow."""
    return None


def read_columns(table: str, columns: Sequence[str], start: int = 0,
                 count: Optional[int] = None, predicate=None
                 ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    """Rows [start, start+count) of `columns` as (values, null masks),
    decoding only the stripes the range touches (stripe = the ORC
    row-group analog), each once; `predicate` prunes nothing here.
    Hops and counters as the parquet module's."""
    from ..exec.datapath import timed_hop
    with _lock:
        f = _tables[table]["f"]
        schema = _tables[table]["schema"]
    import pyarrow as pa
    count = f.nrows - start if count is None else count
    columns = list(columns)
    pieces = []
    seen = 0
    with timed_hop("connector_read") as t_read:
        for s in range(f.nstripes):
            if seen >= start + count:
                break  # range satisfied: do not read trailing stripes
            # pyarrow exposes no stripe metadata, so rows are counted
            # as the stripes are read
            t = pa.table(f.read_stripe(s, columns=columns))
            g_lo, g_hi = seen, seen + t.num_rows
            seen += t.num_rows
            if g_hi <= start:
                continue
            lo = max(start - g_lo, 0)
            pieces.append(t.slice(lo, min(start + count - g_lo,
                                          t.num_rows) - lo))
        t_read.bytes = sum(t.nbytes for t in pieces)
    return host_columns(PieceScan(
        [(t, 0, t.num_rows, t.num_rows) for t in pieces], None, schema,
        columns, touched=len(pieces), file_bytes=t_read.bytes))


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
    values, nulls = read_columns(table, columns, start, count)
    return columns_batch(values, nulls, SCHEMA[table], columns, capacity)


# ---------------------------------------------------------------------------
# the writer: this format's primitives under the SHARED LakeSink
# (lake_sink.py, ConnectorPageSink analog)
# ---------------------------------------------------------------------------


def _orc_schema(schema):
    """The schema as ORC can hold it: pyarrow's ORC writer knows no
    decimal64, so a short decimal goes as decimal128."""
    import pyarrow as pa
    return pa.schema([
        pa.field(f.name, pa.decimal128(f.type.precision, f.type.scale))
        if pa.types.is_decimal(f.type) else f for f in schema])


class _StripeWriter:
    """pyarrow's ORCWriter behind the sink's writer surface
    (`schema`, `write_table`, `close`)."""

    def __init__(self, path: str, schema, stripe_size: Optional[int] = None):
        import pyarrow.orc as orc
        self.schema = _orc_schema(schema)
        kw = {"stripe_size": stripe_size} if stripe_size else {}
        self._w = orc.ORCWriter(path, **kw)
        self._wrote = False

    def write_table(self, tbl) -> None:
        self._w.write(tbl.cast(self.schema))
        self._wrote = True

    def close(self) -> None:
        if self._w is None:
            return
        if not self._wrote:  # an ORC file needs its schema written
            self._w.write(self.schema.empty_table())
        self._w.close()
        self._w = None


open_writer = _StripeWriter


def read_tables(path: str):
    """The file's stripes as arrow tables, one at a time."""
    import pyarrow as pa
    import pyarrow.orc as orc
    f = orc.ORCFile(path)
    for s in range(f.nstripes):
        yield pa.table(f.read_stripe(s))


def write_table(path: str, columns: Dict[str, np.ndarray],
                types: Dict[str, T.Type],
                nulls: Optional[Dict[str, np.ndarray]] = None,
                stripe_size: Optional[int] = None) -> None:
    w = _StripeWriter(path, arrow_schema({c: types[c] for c in columns}),
                      stripe_size)
    try:
        w.write_table(engine_to_arrow(columns, types, nulls))
    finally:
        w.close()


from .lake_sink import LakeSink  # noqa: E402

_sink = LakeSink("orc", ".orc", _tables, _lock, open_writer, read_tables,
                 register_table)
set_warehouse = _sink.set_warehouse
write_lock = _sink.write_lock
drop_table = _sink.drop_table
begin_insert = _sink.begin_insert
append = _sink.append
finish_insert = _sink.finish_insert
abort_insert = _sink.abort_insert
replace_table = _sink.replace_table
