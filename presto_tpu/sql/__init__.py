import sys as _sys
import types as _types

from .parser import parse_sql
from .planner import plan_sql, sql

__all__ = ["parse_sql", "plan_sql", "sql"]


class _CallableModule(_types.ModuleType):
    """Importing this subpackage rebinds the attribute `presto_tpu.sql`
    from the package's convenience function to this module, so the
    documented `presto_tpu.sql(text, ...)` worked exactly once per
    process. The module answers the call itself."""

    def __call__(self, query_text, **kwargs):
        return sql(query_text, **kwargs)


_sys.modules[__name__].__class__ = _CallableModule
