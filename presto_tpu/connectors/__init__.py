"""Connector registry: catalog name -> generator module.

Reference surface: the Plugin/ConnectorFactory registration path
(presto-spi Plugin.java; MetadataManager catalog map). Each connector
module exposes the same surface: TPCH_SCHEMA/TPCDS_SCHEMA-style schema
dict (as `SCHEMA`), table_row_count, generate_columns, generate_batch,
column_type.

`hive` is no third lake connector: it is a name over the Parquet and
ORC modules, as upstream's Hive connector is one catalog over files of
several formats. A table made through it (`CREATE TABLE hive.t WITH
(format = 'PARQUET') AS ...`) is a table of the module its `format`
names, and everything asked of `hive.t` goes to the module that holds
`t`; `parquet.t` / `orc.t` name the same tables by their format.
"""

import os

# pyarrow 25's default allocator (mimalloc) segfaults now and then when
# arrow is first used from a server's engine thread on a busy host: 2 to
# 4 of every 10 rehearsals of a lake cell under load, in ParquetWriter's
# constructor or in read_row_group; none of 20 with jemalloc or the
# system allocator. Arrow reads the choice once, where its default pool
# is first asked for, so it is made here, before this package imports
# pyarrow; a process that set the variable itself keeps its choice.
os.environ.setdefault("ARROW_DEFAULT_MEMORY_POOL", "jemalloc")


class _Hive:
    """The `hive` catalog: the lake modules' tables under one name."""

    FORMATS = ("PARQUET", "ORC")
    DEFAULT_FORMAT = "PARQUET"
    # what a connector is asked about one table, the table first
    _PER_TABLE = frozenset((
        "table_row_count", "column_type", "column_range", "data_version",
        "stored_bytes", "read_columns", "generate_columns", "generate_nulls",
        "generate_batch", "row_groups_matching", "write_lock",
        "replace_table"))

    def __init__(self, modules: dict):
        self._modules = modules  # format -> module
        self.SCHEMA = _HiveSchema(self)

    def _holding(self, table: str):
        for mod in self._modules.values():
            if table in mod.SCHEMA:
                return mod
        raise KeyError(f"no hive table {table!r}")

    def __getattr__(self, name):
        if name not in self._PER_TABLE:
            raise AttributeError(name)

        def per_table(table, *args, **kwargs):
            return getattr(self._holding(table), name)(table, *args,
                                                       **kwargs)
        return per_table

    def scan_pieces(self, table, *args, **kwargs):
        """The holding module's producer of decoded pieces; None for a
        format that offers none (ORC: its scans assemble on the host)."""
        offered = getattr(self._holding(table), "scan_pieces", None)
        return offered and offered(table, *args, **kwargs)

    def table_properties(self, given: dict) -> dict:
        """The properties of a CREATE TABLE as the sink takes them: an
        unknown property or format is the statement's error."""
        unknown = sorted(set(given) - {"format"})
        if unknown:
            raise ValueError(f"catalog 'hive' has no table property "
                             f"{unknown[0]!r} (it has: format)")
        fmt = str(given.get("format", self.DEFAULT_FORMAT)).upper()
        if fmt not in self.FORMATS:
            raise ValueError(f"unknown hive table format {fmt!r} "
                             f"(one of {', '.join(self.FORMATS)})")
        return {"format": fmt}

    def begin_insert(self, table, create_columns=None, create_types=None,
                     properties=None) -> str:
        if create_columns is None:
            return self._holding(table).begin_insert(table)
        if table in self.SCHEMA:
            raise KeyError(f"hive table {table!r} already exists")
        fmt = self.table_properties(properties or {})["format"]
        return self._modules[fmt].begin_insert(table, create_columns,
                                               create_types)

    def _of_handle(self, handle: str):
        """A handle names its sink's kind: `parquet_ins_...`."""
        return self._modules[handle.split("_", 1)[0].upper()]

    def append(self, handle, columns, nulls=None) -> int:
        return self._of_handle(handle).append(handle, columns, nulls)

    def finish_insert(self, handle) -> int:
        return self._of_handle(handle).finish_insert(handle)

    def abort_insert(self, handle) -> None:
        self._of_handle(handle).abort_insert(handle)

    def drop_table(self, table, if_exists=False) -> None:
        try:
            mod = self._holding(table)
        except KeyError:
            if if_exists:
                return
            raise
        mod.drop_table(table, if_exists)


class _HiveSchema:
    """table -> {column: Type} over every format's tables."""

    def __init__(self, hive: _Hive):
        self._hive = hive

    def __getitem__(self, table):
        return self._hive._holding(table).SCHEMA[table]

    def __contains__(self, table):
        return any(table in m.SCHEMA for m in self._hive._modules.values())

    def keys(self):
        return [t for m in self._hive._modules.values()
                for t in m.SCHEMA.keys()]

    def __iter__(self):
        return iter(self.keys())

    def __len__(self):
        return len(self.keys())

    def items(self):
        return [(t, self[t]) for t in self.keys()]

    def values(self):
        return [self[t] for t in self.keys()]


def _load():
    from . import information_schema, localfile, memory, system, tpch, tpcds
    cats = {"tpch": tpch, "tpcds": tpcds, "memory": memory,
            "system": system, "information_schema": information_schema,
            "localfile": localfile}
    try:
        import pyarrow  # noqa: F401  (parquet.py imports it lazily)
        from . import orc, parquet
        cats["parquet"] = parquet
        cats["orc"] = orc
        cats["hive"] = _Hive({"PARQUET": parquet, "ORC": orc})
    except ImportError:
        pass  # pyarrow absent: the lake catalogs are gated off
    return cats


CATALOGS = None


def catalogs() -> dict:
    global CATALOGS
    if CATALOGS is None:
        CATALOGS = _load()
    return CATALOGS


def catalog(name: str):
    try:
        return catalogs()[name]
    except KeyError:
        raise KeyError(f"unknown connector/catalog {name!r}") from None


def schema_of(name: str):
    mod = catalog(name)
    return mod.SCHEMA
