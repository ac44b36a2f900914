"""Deterministic columnar TPC-H data generator.

Reference surface: presto-tpch/src/main/java/com/facebook/presto/tpch/
(TpchRecordSetProvider generates rows on the fly from the airlift tpch
dbgen port; splits address disjoint row ranges so scans parallelize).

This generator is columnar and *stateless per row*: every value is a pure
function of (table, column, global row index, scale factor) via a
splitmix64 hash, so any split [start, end) of any table can be generated
independently and identically on any host -- the property the reference
gets from chunked dbgen streams, redesigned for vectorized columnar
production straight into numpy (then HBM).

Cardinalities follow the TPC-H spec (lineitem ~= 6M * SF via exactly 4
lines per order -- the spec's 1..7 average 4; fixed fan-out keeps row
ranges addressable in O(1)). Value distributions (dates, quantities,
discounts, return flags) follow the spec's ranges so the standard
queries' selectivities are realistic; string columns (comments, names)
are deterministic phrases, not dbgen grammar text. A string column is
made as it is held everywhere below the client: a `HostStrings` (bytes
and lengths), by row gathers of the encoded choices and vectorised
digit formatting; no Python string per row.

Decimals are generated as scaled int64 (cents) matching
presto_tpu.types decimal mapping.
"""

from __future__ import annotations

import functools
import os
import zlib
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ... import types as T
from ...block import Batch, HostStrings, batch_from_numpy

# ---------------------------------------------------------------------------
# Schema (TPC-H spec 1.4; types as Presto's tpch connector exposes them)
# ---------------------------------------------------------------------------

_D122 = T.decimal(12, 2)
_D152 = T.decimal(15, 2)

TPCH_SCHEMA: Dict[str, List[Tuple[str, T.Type]]] = {
    "lineitem": [
        ("orderkey", T.BIGINT), ("partkey", T.BIGINT), ("suppkey", T.BIGINT),
        ("linenumber", T.INTEGER), ("quantity", _D122),
        ("extendedprice", _D122), ("discount", _D122), ("tax", _D122),
        ("returnflag", T.char(1)), ("linestatus", T.char(1)),
        ("shipdate", T.DATE), ("commitdate", T.DATE), ("receiptdate", T.DATE),
        ("shipinstruct", T.varchar(25)), ("shipmode", T.varchar(10)),
        ("comment", T.varchar(44)),
    ],
    "orders": [
        ("orderkey", T.BIGINT), ("custkey", T.BIGINT),
        ("orderstatus", T.char(1)), ("totalprice", _D152),
        ("orderdate", T.DATE), ("orderpriority", T.varchar(15)),
        ("clerk", T.varchar(15)), ("shippriority", T.INTEGER),
        ("comment", T.varchar(79)),
    ],
    "customer": [
        ("custkey", T.BIGINT), ("name", T.varchar(25)),
        ("address", T.varchar(40)), ("nationkey", T.BIGINT),
        ("phone", T.varchar(15)), ("acctbal", _D122),
        ("mktsegment", T.varchar(10)), ("comment", T.varchar(117)),
    ],
    "part": [
        ("partkey", T.BIGINT), ("name", T.varchar(55)),
        ("mfgr", T.varchar(25)), ("brand", T.varchar(10)),
        ("type", T.varchar(25)), ("size", T.INTEGER),
        ("container", T.varchar(10)), ("retailprice", _D122),
        ("comment", T.varchar(23)),
    ],
    "supplier": [
        ("suppkey", T.BIGINT), ("name", T.varchar(25)),
        ("address", T.varchar(40)), ("nationkey", T.BIGINT),
        ("phone", T.varchar(15)), ("acctbal", _D122),
        ("comment", T.varchar(101)),
    ],
    "partsupp": [
        ("partkey", T.BIGINT), ("suppkey", T.BIGINT),
        ("availqty", T.INTEGER), ("supplycost", _D122),
        ("comment", T.varchar(199)),
    ],
    "nation": [
        ("nationkey", T.BIGINT), ("name", T.varchar(25)),
        ("regionkey", T.BIGINT), ("comment", T.varchar(152)),
    ],
    "region": [
        ("regionkey", T.BIGINT), ("name", T.varchar(25)),
        ("comment", T.varchar(152)),
    ],
}

_BASE_ROWS = {
    "lineitem": 6_000_000, "orders": 1_500_000, "customer": 150_000,
    "part": 200_000, "supplier": 10_000, "partsupp": 800_000,
    "nation": 25, "region": 5,
}

LINES_PER_ORDER = 4  # fixed fan-out: lineitem row i belongs to order i//4 + 1

# date epochs (days since 1970-01-01)
_D = np.datetime64("1970-01-01")
_EPOCH_1992 = int((np.datetime64("1992-01-01") - _D).astype(int))
_ORDERDATE_RANGE = 2405  # spec: orders span 1992-01-01 .. 1998-08-02 (ENDDATE - 151 days)
_CUTOFF_1995_06_17 = int((np.datetime64("1995-06-17") - _D).astype(int))

_NATIONS = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
            "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ",
            "JAPAN", "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU",
            "CHINA", "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA",
            "UNITED KINGDOM", "UNITED STATES"]
_NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3,
                  4, 2, 3, 3, 1]
_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
_INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
_MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
_TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
_TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
_TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
_CONTAINERS = ["SM CASE", "SM BOX", "SM PACK", "SM PKG", "MED BAG", "MED BOX",
               "MED PKG", "MED PACK", "LG CASE", "LG BOX", "LG PACK", "LG PKG",
               "JUMBO BAG", "JUMBO BOX", "WRAP CASE", "WRAP BOX"]
_COMMENT_WORDS = ["carefully", "quickly", "furiously", "slyly", "blithely",
                  "final", "special", "pending", "regular", "express",
                  "deposits", "requests", "packages", "accounts", "ideas",
                  "theodolites", "dependencies", "instructions", "foxes",
                  "platelets", "sleep", "nag", "haggle", "wake", "cajole",
                  "above the", "among the", "across the", "beneath"]

P_TYPES = [f"{a} {b} {c}" for a in _TYPE_S1 for b in _TYPE_S2 for c in _TYPE_S3]


def table_row_count(table: str, sf: float) -> int:
    if table in ("nation", "region"):
        return _BASE_ROWS[table]
    return int(_BASE_ROWS[table] * sf)


def column_type(table: str, column: str) -> T.Type:
    for name, ty in TPCH_SCHEMA[table]:
        if name == column:
            return ty
    raise KeyError(f"{table}.{column}")


# ---------------------------------------------------------------------------
# splitmix64: the stateless per-row hash
# ---------------------------------------------------------------------------

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        z = (x + _GOLDEN).astype(np.uint64)
        z = np.bitwise_xor(z, z >> np.uint64(30)) * _M1
        z = np.bitwise_xor(z, z >> np.uint64(27)) * _M2
        return np.bitwise_xor(z, z >> np.uint64(31))


def _h(table: str, column: str, idx: np.ndarray) -> np.ndarray:
    """64-bit hash of global row index, salted by table.column. The salt
    uses crc32 (not Python's randomized str hash) so values are identical
    across processes and hosts."""
    seed = _splitmix64(np.uint64(zlib.crc32(f"{table}.{column}".encode())))
    with np.errstate(over="ignore"):
        return _splitmix64(idx.astype(np.uint64) * _GOLDEN + seed)


def _uniform(table, column, idx, lo, hi):
    """Integers uniform in [lo, hi] (inclusive). Offset added in int64 so
    negative bounds (acctbal) don't overflow uint64 arithmetic."""
    return (_h(table, column, idx) % np.uint64(hi - lo + 1)).astype(np.int64) + lo


@functools.lru_cache(maxsize=None)
def _encoded(choices: Tuple[str, ...]) -> HostStrings:
    return HostStrings.from_objects(choices)


def _choose(choices: Sequence[str], codes: np.ndarray) -> HostStrings:
    """Row i is choices[codes[i]]."""
    return _encoded(tuple(choices))[codes]


def _codes(table, column, idx, n: int) -> np.ndarray:
    return (_h(table, column, idx) % np.uint64(n)).astype(np.int64)


def _pick(table, column, idx, choices: Sequence[str]) -> HostStrings:
    return _choose(choices, _codes(table, column, idx, len(choices)))


@functools.lru_cache(maxsize=None)
def _phrases(nwords: int) -> Tuple[str, ...]:
    """Every phrase of `nwords` comment words, the first word slowest."""
    out = ("",)
    for _ in range(nwords):
        out = tuple(f"{p} {w}" if p else w
                    for p in out for w in _COMMENT_WORDS)
    return out


def _joined(left: HostStrings, right: HostStrings) -> HostStrings:
    """left + ' ' + right, row by row. Rows of one left length move as
    one block, and a phrase has few lengths."""
    n, wl = left.chars.shape
    wr = right.chars.shape[1]
    chars = np.zeros((n, wl + 1 + wr), dtype=np.uint8)
    chars[:, :wl] = left.chars
    for at in np.unique(left.lengths):
        rows = np.flatnonzero(left.lengths == at)
        chars[rows, at] = ord(" ")
        chars[rows, at + 1:at + 1 + wr] = right.chars[rows]
    return HostStrings(chars, left.lengths + 1 + right.lengths)


def _comment(table, idx, nwords=4, max_chars: Optional[int] = None
             ) -> HostStrings:
    """`nwords` hashed words joined by spaces, cut to `max_chars` as
    dbgen cuts to the declared width. Up to three words at a time are
    one gather from the table of their phrases (29**3 rows)."""
    nw = len(_COMMENT_WORDS)
    out = None
    for k0 in range(0, nwords, 3):
        code = np.zeros(len(idx), dtype=np.int64)
        take = min(3, nwords - k0)
        for k in range(k0, k0 + take):
            code = code * nw + _codes(table, f"comment{k}", idx, nw)
        part = _choose(_phrases(take), code)
        out = part if out is None else _joined(out, part)
    if max_chars is not None and out.chars.shape[1] > max_chars:
        out = HostStrings(np.ascontiguousarray(out.chars[:, :max_chars]),
                          np.minimum(out.lengths, max_chars))
    return out


def _digits(num: np.ndarray, width: int) -> np.ndarray:
    """(n, width) ASCII digits of `num`, zero-filled on the left."""
    num = num.astype(np.int64)
    if len(num) and int(num.max()) >= 10 ** width:
        raise ValueError(f"{int(num.max())} needs more than {width} digits")
    powers = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    return ((num[:, None] // powers) % 10 + ord("0")).astype(np.uint8)


def _fixed(n: int, *pieces) -> HostStrings:
    """Rows of one width from pieces laid side by side: a str is the
    same bytes in every row, an (n, w) uint8 matrix a column of its
    own."""
    cols = [np.broadcast_to(np.frombuffer(p.encode(), dtype=np.uint8),
                            (n, len(p.encode())))
            if isinstance(p, str) else p for p in pieces]
    chars = np.concatenate(cols, axis=1)
    return HostStrings(chars, np.full(n, chars.shape[1], dtype=np.int32))


# ---------------------------------------------------------------------------
# Per-table column generators.  idx is the global row index vector.
# ---------------------------------------------------------------------------

def _orders_orderdate(idx: np.ndarray) -> np.ndarray:
    return (_EPOCH_1992
            + _uniform("orders", "orderdate", idx, 0, _ORDERDATE_RANGE)).astype(np.int32)


def _retail_price(pkey: np.ndarray) -> np.ndarray:
    """part.retailprice in cents; lineitem.extendedprice = quantity * this."""
    return (90000 + (pkey % 200001) + 100 * (pkey % 1000)).astype(np.int64)


def _numbered(prefix: str, num: np.ndarray, width: int = 9) -> HostStrings:
    """Vectorized 'Prefix#000000042' formatting."""
    return _fixed(len(num), f"{prefix}#", _digits(num, width))


def _phone(table: str, idx: np.ndarray) -> HostStrings:
    """Spec: country code = nationkey + 10 (uses the SAME nationkey hash as
    the table's nationkey column so phone and nationkey stay consistent)."""
    nk = _uniform(table, "nationkey", idx, 0, 24)
    h = _h(table, "phone", idx).astype(np.int64)
    return _fixed(len(idx), _digits(10 + nk, 2), "-",
                  _digits(h % 900 + 100, 3), "-",
                  _digits((h >> 10) % 900 + 100, 3), "-",
                  _digits((h >> 20) % 9000 + 1000, 4))


def _gen_lineitem(column: str, idx: np.ndarray, sf: float) -> np.ndarray:
    n_part = table_row_count("part", sf)
    n_supp = table_row_count("supplier", sf)
    okey = idx // LINES_PER_ORDER  # 0-based order row index
    if column == "orderkey":
        return (okey + 1).astype(np.int64)
    if column == "linenumber":
        return (idx % LINES_PER_ORDER + 1).astype(np.int32)
    if column == "partkey":
        return _uniform("lineitem", "partkey", idx, 1, n_part)
    if column == "suppkey":
        # spec ties suppkey to partkey's eligible suppliers; uniform is fine here
        return _uniform("lineitem", "suppkey", idx, 1, n_supp)
    if column == "quantity":
        return _uniform("lineitem", "quantity", idx, 1, 50) * 100
    if column == "extendedprice":
        qty = _uniform("lineitem", "quantity", idx, 1, 50)
        pkey = _uniform("lineitem", "partkey", idx, 1, n_part)
        return (qty * _retail_price(pkey)).astype(np.int64)
    if column == "discount":
        return _uniform("lineitem", "discount", idx, 0, 10)  # 0.00..0.10
    if column == "tax":
        return _uniform("lineitem", "tax", idx, 0, 8)
    if column in ("shipdate", "commitdate", "receiptdate", "returnflag",
                  "linestatus"):
        odate = _orders_orderdate(okey)
        ship = odate + _uniform("lineitem", "shipdate", idx, 1, 121).astype(np.int32)
        if column == "shipdate":
            return ship.astype(np.int32)
        if column == "commitdate":
            return (odate + _uniform("lineitem", "commitdate", idx, 30, 90)).astype(np.int32)
        receipt = ship + _uniform("lineitem", "receiptdate", idx, 1, 30).astype(np.int32)
        if column == "receiptdate":
            return receipt.astype(np.int32)
        if column == "returnflag":
            ra = _codes("lineitem", "returnflag", idx, 2)
            return _choose(["R", "A", "N"],
                           np.where(receipt <= _CUTOFF_1995_06_17, ra, 2))
        if column == "linestatus":
            return _choose(["O", "F"],
                           np.where(ship > _CUTOFF_1995_06_17, 0, 1))
    if column == "shipinstruct":
        return _pick("lineitem", "shipinstruct", idx, _INSTRUCTS)
    if column == "shipmode":
        return _pick("lineitem", "shipmode", idx, _MODES)
    if column == "comment":
        return _comment("lineitem", idx, 3)
    raise KeyError(f"lineitem.{column}")


def _gen_orders(column: str, idx: np.ndarray, sf: float) -> np.ndarray:
    n_cust = table_row_count("customer", sf)
    if column == "orderkey":
        return (idx + 1).astype(np.int64)
    if column == "custkey":
        # spec: only 2/3 of customers have orders (sparse custkeys)
        c = _uniform("orders", "custkey", idx, 0, (n_cust // 3) * 2 - 1)
        return (c // 2 * 3 + c % 2 + 1).astype(np.int64)
    if column == "orderstatus":
        # derived from line statuses; approximate with the spec's marginals
        return _pick("orders", "orderstatus", idx, ["F", "O", "P"])
    if column == "totalprice":
        return _uniform("orders", "totalprice", idx, 85000, 55550000)
    if column == "orderdate":
        return _orders_orderdate(idx)
    if column == "orderpriority":
        return _pick("orders", "orderpriority", idx, _PRIORITIES)
    if column == "clerk":
        c = _uniform("orders", "clerk", idx, 1, max(int(1000 * sf), 1))
        return _numbered("Clerk", c)
    if column == "shippriority":
        return np.zeros(len(idx), dtype=np.int32)
    if column == "comment":
        return _comment("orders", idx, 5)
    raise KeyError(f"orders.{column}")


def _gen_customer(column: str, idx: np.ndarray, sf: float) -> np.ndarray:
    if column == "custkey":
        return (idx + 1).astype(np.int64)
    if column == "name":
        return _numbered("Customer", idx + 1)
    if column == "address":
        return _comment("customer", idx, 2)
    if column == "nationkey":
        return _uniform("customer", "nationkey", idx, 0, 24)
    if column == "phone":
        return _phone("customer", idx)
    if column == "acctbal":
        return _uniform("customer", "acctbal", idx, -99999, 999999)
    if column == "mktsegment":
        return _pick("customer", "mktsegment", idx, _SEGMENTS)
    if column == "comment":
        return _comment("customer", idx, 6)
    raise KeyError(f"customer.{column}")


def _gen_part(column: str, idx: np.ndarray, sf: float) -> np.ndarray:
    if column == "partkey":
        return (idx + 1).astype(np.int64)
    if column == "name":
        return _comment("part", idx, 3)
    if column == "mfgr":
        m = _uniform("part", "mfgr", idx, 1, 5)
        return _choose([f"Manufacturer#{v}" for v in range(1, 6)], m - 1)
    if column == "brand":
        m = _uniform("part", "mfgr", idx, 1, 5)
        b = _uniform("part", "brand", idx, 1, 5)
        return _choose([f"Brand#{mm}{bb}" for mm in range(1, 6)
                        for bb in range(1, 6)], (m - 1) * 5 + (b - 1))
    if column == "type":
        return _pick("part", "type", idx, P_TYPES)
    if column == "size":
        return _uniform("part", "size", idx, 1, 50).astype(np.int32)
    if column == "container":
        return _pick("part", "container", idx, _CONTAINERS)
    if column == "retailprice":
        return _retail_price(idx + 1)
    if column == "comment":
        return _comment("part", idx, 2, max_chars=23)
    raise KeyError(f"part.{column}")


def _gen_supplier(column: str, idx: np.ndarray, sf: float) -> np.ndarray:
    if column == "suppkey":
        return (idx + 1).astype(np.int64)
    if column == "name":
        return _numbered("Supplier", idx + 1)
    if column == "address":
        return _comment("supplier", idx, 2)
    if column == "nationkey":
        return _uniform("supplier", "nationkey", idx, 0, 24)
    if column == "phone":
        return _phone("supplier", idx)
    if column == "acctbal":
        return _uniform("supplier", "acctbal", idx, -99999, 999999)
    if column == "comment":
        return _comment("supplier", idx, 5)
    raise KeyError(f"supplier.{column}")


def _gen_partsupp(column: str, idx: np.ndarray, sf: float) -> np.ndarray:
    n_supp = table_row_count("supplier", sf)
    if column == "partkey":
        return (idx // 4 + 1).astype(np.int64)
    if column == "suppkey":
        pk = idx // 4
        s = idx % 4
        return ((pk + s * (n_supp // 4 + pk % max(n_supp // 4, 1))) % n_supp + 1).astype(np.int64)
    if column == "availqty":
        return _uniform("partsupp", "availqty", idx, 1, 9999).astype(np.int32)
    if column == "supplycost":
        return _uniform("partsupp", "supplycost", idx, 100, 100000)
    if column == "comment":
        return _comment("partsupp", idx, 8)
    raise KeyError(f"partsupp.{column}")


def _gen_nation(column: str, idx: np.ndarray, sf: float) -> np.ndarray:
    if column == "nationkey":
        return idx.astype(np.int64)
    if column == "name":
        return _choose(_NATIONS, idx)
    if column == "regionkey":
        return np.array(_NATION_REGION, dtype=np.int64)[idx]
    if column == "comment":
        return _comment("nation", idx, 4)
    raise KeyError(f"nation.{column}")


def _gen_region(column: str, idx: np.ndarray, sf: float) -> np.ndarray:
    if column == "regionkey":
        return idx.astype(np.int64)
    if column == "name":
        return _choose(_REGIONS, idx)
    if column == "comment":
        return _comment("region", idx, 4)
    raise KeyError(f"region.{column}")


_GENERATORS = {
    "lineitem": _gen_lineitem, "orders": _gen_orders, "customer": _gen_customer,
    "part": _gen_part, "supplier": _gen_supplier, "partsupp": _gen_partsupp,
    "nation": _gen_nation, "region": _gen_region,
}


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

_CHUNK_ROWS = 1 << 19   # rows a task makes at a time: its temporaries are
_THREADS = min(8, os.cpu_count() or 1)  # a few MB, which the allocator reuses


def generate_columns(table: str, sf: float, columns: Sequence[str],
                     start: int = 0, count: Optional[int] = None
                     ) -> Dict[str, np.ndarray]:
    """Generate host columns for rows [start, start+count) of `table`.
    A large split is made in chunks of rows, side by side on a few
    threads (a column is numpy passes over the row indices, which
    release the interpreter lock), each chunk written into its place
    in the split's columns: the passes' temporaries stay small enough
    to be reused, where a whole split's would each be fresh memory."""
    total = table_row_count(table, sf)
    if count is None:
        count = total - start
    assert 0 <= start and start + count <= total, (start, count, total)
    gen = _GENERATORS[table]

    def make(lo: int, n: int) -> List[np.ndarray]:
        idx = np.arange(lo, lo + n, dtype=np.int64)
        return [gen(c, idx, sf) for c in columns]

    if count <= _CHUNK_ROWS:
        return dict(zip(columns, make(start, count)))
    out = [_like(one, count) for one in make(start, 1)]

    def fill(at: int) -> None:
        n = min(_CHUNK_ROWS, count - at)
        for whole, part in zip(out, make(start + at, n)):
            if isinstance(whole, HostStrings):
                whole.chars[at:at + n] = part.chars
                whole.lengths[at:at + n] = part.lengths
            else:
                whole[at:at + n] = part
    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, range(0, count, _CHUNK_ROWS)))
    return dict(zip(columns, out))


def _like(one, count: int):
    """An uninitialised column of `count` rows of the kind of `one`. A
    string column's width is its generator's, whatever the rows."""
    if isinstance(one, HostStrings):
        return HostStrings(
            np.empty((count, one.chars.shape[1]), dtype=np.uint8),
            np.empty(count, dtype=np.int32))
    return np.empty(count, dtype=one.dtype)


def generate_batch(table: str, sf: float, columns: Sequence[str],
                   start: int = 0, count: Optional[int] = None,
                   capacity: Optional[int] = None) -> Batch:
    """Generate a device Batch for a split of `table` (scan-operator feed)."""
    data = generate_columns(table, sf, columns, start, count)
    tys = [column_type(table, c) for c in columns]
    return batch_from_numpy(tys, [data[c] for c in columns], capacity=capacity)
