"""The TPC-H population the references answer over: the benchmark's own copy.

Every value is a pure function of (table, column, row index, scale
factor) through a splitmix64 hash salted with crc32 of "table.column".
This is a copy of the arithmetic of `presto_tpu/connectors/tpch/
generator.py` for the columns the templates read, kept here so that the
reference imports nothing of the program: if the program's generator
ever emits other values, the answers stop agreeing and `correct` says
so. `--seed` seeds nothing here: the population is fixed by the
configuration (spec ranges, 4 lineitems per order).
"""

from __future__ import annotations

import zlib

import numpy as np

BASE_ROWS = {"lineitem": 6_000_000, "orders": 1_500_000,
             "customer": 150_000, "part": 200_000}
LINES_PER_ORDER = 4
_EPOCH = np.datetime64("1970-01-01")
_EPOCH_1992 = int((np.datetime64("1992-01-01") - _EPOCH).astype(int))
_ORDERDATE_RANGE = 2405
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
P_TYPES = [f"{a} {b} {c}"
           for a in ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY",
                     "PROMO"]
           for b in ["ANODIZED", "BURNISHED", "PLATED", "POLISHED",
                     "BRUSHED"]
           for c in ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]]

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def day(text: str) -> int:
    """'1994-01-01' -> days since 1970-01-01, as DATE columns hold them."""
    return int((np.datetime64(text) - _EPOCH).astype(int))


def day_text(days: int) -> str:
    return str(_EPOCH + int(days))


def rows(table: str, sf: float) -> int:
    return int(BASE_ROWS[table] * sf)


def _splitmix64(x):
    with np.errstate(over="ignore"):
        z = (x + _GOLDEN).astype(np.uint64)
        z = np.bitwise_xor(z, z >> np.uint64(30)) * _M1
        z = np.bitwise_xor(z, z >> np.uint64(27)) * _M2
        return np.bitwise_xor(z, z >> np.uint64(31))


def _h(table, column, idx):
    salt = _splitmix64(np.uint64(zlib.crc32(f"{table}.{column}".encode())))
    with np.errstate(over="ignore"):
        return _splitmix64(idx.astype(np.uint64) * _GOLDEN + salt)


def _uniform(table, column, idx, lo, hi):
    return (_h(table, column, idx) % np.uint64(hi - lo + 1)
            ).astype(np.int64) + lo


def _orderdate(order_idx):
    return _EPOCH_1992 + _uniform("orders", "orderdate", order_idx, 0,
                                  _ORDERDATE_RANGE)


def _lineitem(column, idx, sf):
    order_idx = idx // LINES_PER_ORDER
    if column == "orderkey":
        return order_idx + 1
    if column == "partkey":
        return _uniform("lineitem", "partkey", idx, 1, rows("part", sf))
    if column == "quantity":  # decimal(12,2), scaled by 100
        return _uniform("lineitem", "quantity", idx, 1, 50) * 100
    if column == "extendedprice":  # decimal(12,2): quantity * retail price
        pkey = _uniform("lineitem", "partkey", idx, 1, rows("part", sf))
        retail = 90000 + (pkey % 200001) + 100 * (pkey % 1000)
        return _uniform("lineitem", "quantity", idx, 1, 50) * retail
    if column == "discount":  # decimal(12,2): 0.00 .. 0.10
        return _uniform("lineitem", "discount", idx, 0, 10)
    if column == "shipdate":
        return _orderdate(order_idx) + _uniform("lineitem", "shipdate",
                                                idx, 1, 121)
    raise KeyError(f"lineitem.{column}")


def _orders(column, idx, sf):
    if column == "orderkey":
        return idx + 1
    if column == "custkey":  # only two thirds of the customers have orders
        c = _uniform("orders", "custkey", idx, 0,
                     (rows("customer", sf) // 3) * 2 - 1)
        return c // 2 * 3 + c % 2 + 1
    if column == "orderdate":
        return _orderdate(idx)
    if column == "shippriority":
        return np.zeros(len(idx), dtype=np.int64)
    raise KeyError(f"orders.{column}")


def _pick(table, column, idx, choices):
    codes = (_h(table, column, idx) % np.uint64(len(choices))
             ).astype(np.int64)
    return np.array(choices)[codes]


def _customer(column, idx, sf):
    if column == "custkey":
        return idx + 1
    if column == "mktsegment":
        return _pick("customer", "mktsegment", idx, SEGMENTS)
    raise KeyError(f"customer.{column}")


def _part(column, idx, sf):
    if column == "partkey":
        return idx + 1
    if column == "type":
        return _pick("part", "type", idx, P_TYPES)
    raise KeyError(f"part.{column}")


_TABLES = {"lineitem": _lineitem, "orders": _orders,
           "customer": _customer, "part": _part}


class Population:
    """Whole columns of one scale factor, made on first use and kept."""

    def __init__(self, sf: float):
        self.sf = sf
        self._kept = {}

    def rows(self, table: str) -> int:
        return rows(table, self.sf)

    def column(self, table: str, name: str) -> np.ndarray:
        key = (table, name)
        if key not in self._kept:
            idx = np.arange(self.rows(table), dtype=np.int64)
            self._kept[key] = _TABLES[table](name, idx, self.sf)
        return self._kept[key]
