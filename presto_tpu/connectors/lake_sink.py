"""Shared lake-connector writer sink (ConnectorPageSink analog).

One implementation of the staged-insert state machine — drop,
begin_insert/append/finish_insert/abort_insert, replace_table,
warehouse management — parameterized by the format module's primitives
(`open_writer` / `read_tables` / `register_table`). The parquet and ORC
connectors bind a `LakeSink` instance to module-level functions, so the
commit semantics (staged file + atomic os.replace + re-registration
advancing data_version) cannot drift between formats.

A CREATE TABLE AS writes as it goes: each page `append` is handed goes
to the staged file as row groups (`engine_to_arrow` a group at a time,
no Python object per value) and is let go, so the host holds a page,
not the table. The table's name is only reserved until `finish_insert`
renames the staged file into place and registers it: a reader sees all
of the table or none of it, and whatever an earlier process left at
the path is replaced, never appended to. An INSERT into a table that
exists keeps its pages (as arrow tables) until `finish_insert`, which
under the table's writer lock copies the file's row groups and then
the pages into a new file and renames that: two inserts that commit
one after the other both land.
Reference: presto-spi/.../spi/ConnectorPageSink.java plus the
hive-style staged-commit pattern (finishInsert/finishCreateTable)."""

from __future__ import annotations

import itertools
import os
import tempfile
import threading
import uuid
from typing import Callable, Dict, Optional, Sequence

__all__ = ["LakeSink", "WAREHOUSE_ENV"]

# where the files of written tables live unless `set_warehouse` says
WAREHOUSE_ENV = "PRESTO_TPU_WAREHOUSE"


class LakeSink:
    def __init__(self, kind: str, extension: str,
                 tables: Dict[str, dict], lock,
                 open_writer: Callable, read_tables: Callable,
                 register_table: Callable):
        """`open_writer(path, arrow schema)` gives a writer of row
        groups (`write_table`, `close`), `read_tables(path)` a file's
        row groups as arrow tables, `register_table(name, path)` makes
        a file the table."""
        self.kind = kind
        self.extension = extension
        self._tables = tables
        self._lock = lock
        self._open_writer = open_writer
        self._read_tables = read_tables
        self._register_table = register_table
        self._config: Dict[str, Optional[str]] = {"warehouse": None}
        self._write_locks: Dict[str, threading.Lock] = {}
        self._pending: Dict[str, dict] = {}
        self._creating: set = set()  # names reserved by an open CTAS

    # -- warehouse ---------------------------------------------------------

    def warehouse_dir(self) -> str:
        d = self._config.get("warehouse") or os.environ.get(WAREHOUSE_ENV) \
            or os.path.join(tempfile.gettempdir(), "presto_tpu_warehouse")
        os.makedirs(d, exist_ok=True)
        return d

    def set_warehouse(self, path: Optional[str]) -> None:
        self._config["warehouse"] = path

    def write_lock(self, table: str):
        with self._lock:
            return self._write_locks.setdefault(table, threading.Lock())

    # -- DDL ---------------------------------------------------------------

    def drop_table(self, name: str, if_exists: bool = False) -> None:
        with self._lock:
            ent = self._tables.pop(name, None)
        if ent is None:
            if if_exists:
                return
            raise KeyError(f"no {self.kind} table {name!r}")
        # only reclaim files this connector owns (warehouse output);
        # externally registered files are the user's
        if ent["path"].startswith(self.warehouse_dir()):
            try:
                os.remove(ent["path"])
            except OSError:
                pass

    # -- staged insert -----------------------------------------------------

    def begin_insert(self, table: str,
                     create_columns: Optional[Sequence[str]] = None,
                     create_types=None) -> str:
        from .parquet import arrow_schema
        h = f"{self.kind}_ins_{uuid.uuid4().hex[:12]}"
        with self._lock:
            exists = table in self._tables
            if create_columns is None:
                if not exists:
                    raise KeyError(f"no {self.kind} table {table!r}")
                self._pending[h] = {
                    "table": table, "created": False, "pages": [],
                    "rows": 0,
                    "types": dict(self._tables[table]["schema"])}
                return h
            if exists or table in self._creating:
                raise KeyError(f"{self.kind} table {table!r} already exists")
            self._creating.add(table)
        types = dict(zip(create_columns, create_types))
        path = os.path.join(self.warehouse_dir(), table + self.extension)
        staged = f"{path}.staged-{h[-12:]}"
        try:
            writer = self._open_writer(staged, arrow_schema(types))
        except BaseException:
            with self._lock:
                self._creating.discard(table)
            raise
        self._pending[h] = {
            "table": table, "created": True, "types": types, "path": path,
            "staged": staged, "writer": writer, "rows": 0}
        return h

    def _arrow_pages(self, types, columns, nulls, rows_at_a_time: int):
        """One page as arrow tables of at most a row group's rows."""
        from .parquet import engine_to_arrow
        names = list(types)
        if len(columns) != len(names):
            raise ValueError(f"insert arity {len(columns)} != table arity "
                             f"{len(names)}")
        n = len(columns[0]) if len(columns) else 0
        for at in range(0, n, rows_at_a_time):
            cut = slice(at, at + rows_at_a_time)
            yield engine_to_arrow(
                {c: col[cut] for c, col in zip(names, columns)}, types,
                None if nulls is None else
                {c: nl[cut] for c, nl in zip(names, nulls)})

    def append(self, handle: str, columns, nulls=None) -> int:
        from .parquet import ROW_GROUP_ROWS
        st = self._pending[handle]
        rows = 0
        for tbl in self._arrow_pages(st["types"], columns, nulls,
                                     ROW_GROUP_ROWS):
            if st["created"]:
                st["writer"].write_table(tbl)
            else:
                st["pages"].append(tbl)
            rows += tbl.num_rows
        st["rows"] += rows
        return rows

    def finish_insert(self, handle: str) -> int:
        """Commit: the staged file becomes the table by one os.replace;
        re-registration advances data_version (the fragment-cache
        invalidation seam). Returns the rows this insert wrote."""
        st = self._pending.pop(handle)
        table = st["table"]
        if st["created"]:
            self._publish(table, st["path"], st["staged"], st["writer"], ())
            return st["rows"]
        from .parquet import arrow_schema
        with self.write_lock(table):
            with self._lock:
                path = self._tables[table]["path"]
            staged = f"{path}.staged-{handle[-12:]}"
            self._publish(
                table, path, staged,
                self._open_writer(staged, arrow_schema(st["types"])),
                itertools.chain(self._read_tables(path), st["pages"]))
        return st["rows"]

    def _publish(self, table, path, staged, writer, tables) -> None:
        """`tables` into `writer`, then the staged file in the table's
        place and registered; nothing of it is left where that fails."""
        try:
            for tbl in tables:
                writer.write_table(tbl.cast(writer.schema))
            writer.close()
            os.replace(staged, path)
            self._register_table(table, path)
        except BaseException:
            writer.close()
            if os.path.exists(staged):
                os.remove(staged)
            raise
        finally:
            with self._lock:
                self._creating.discard(table)

    def abort_insert(self, handle: str) -> None:
        st = self._pending.pop(handle, None)
        if st and st["created"]:
            st["writer"].close()
            if os.path.exists(st["staged"]):
                os.remove(st["staged"])
            with self._lock:
                self._creating.discard(st["table"])

    def replace_table(self, table: str, columns, nulls) -> None:
        """DELETE/UPDATE commit: rewritten contents become the file."""
        from .parquet import ROW_GROUP_ROWS, arrow_schema
        with self._lock:
            path = self._tables[table]["path"]
            types = dict(self._tables[table]["schema"])
        staged = f"{path}.staged-{uuid.uuid4().hex[:12]}"
        self._publish(table, path, staged,
                      self._open_writer(staged, arrow_schema(types)),
                      self._arrow_pages(types, columns, nulls,
                                        ROW_GROUP_ROWS))
