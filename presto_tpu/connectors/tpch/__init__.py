from .generator import (TPCH_SCHEMA, table_row_count, generate_columns,
                        generate_batch, column_type)
from .stats import column_distinct_count, column_range

__all__ = ["TPCH_SCHEMA", "table_row_count", "generate_columns",
           "generate_batch", "column_type", "column_distinct_count",
           "column_range"]

SCHEMA = TPCH_SCHEMA  # uniform connector-registry surface
__all__ = __all__ + ["SCHEMA"]


def data_version(table: str) -> int:
    """Fragment-result-cache seam: generated data is a pure function
    of (table, sf), so the version never changes."""
    return 0


__all__ = __all__ + ["data_version"]


def schema_scale(schema: str) -> float:
    """The scale factor an upstream schema name stands for: `tiny` is
    0.01 and `sf<n>` is n (`tpch.sf10.lineitem`), as the reference's
    TPCH connector names them."""
    if schema == "tiny":
        return 0.01
    try:
        if not schema.startswith("sf"):
            raise ValueError(schema)
        return float(schema[2:])
    except ValueError:
        raise KeyError(f"schema {schema!r} not in catalog 'tpch' (its "
                       f"schemas are tiny, sf1, sf10, ...)") from None


def check_schema(schema: str, sf: float) -> None:
    """One server generates one scale: a statement that names another
    schema is refused before anything is read."""
    if schema_scale(schema) != sf:
        raise KeyError(
            f"schema {schema!r} of catalog 'tpch' is scale factor "
            f"{schema_scale(schema):g}; this server serves scale factor "
            f"{sf:g} (start it with sf={schema_scale(schema):g}, or name "
            f"the table without a schema)")


__all__ = __all__ + ["schema_scale", "check_schema"]
