"""Device-resident columnar data model: the Page/Block analog.

Reference surface: presto-common/.../common/Page.java:107,163 and
presto-common/.../common/block/ (73 files: LongArrayBlock, IntArrayBlock,
VariableWidthBlock, DictionaryBlock, RunLengthEncodedBlock, LazyBlock...).

TPU-first redesign (NOT a translation of the JVM layout):

* A `Column` is a flat value array plus a boolean null mask, resident in
  HBM. Fixed-width SQL types map 1:1 to a dtype'd vector (the
  LongArrayBlock/IntArrayBlock/... family collapses into one class
  parameterized by dtype).
* Strings (`StringColumn`) are a fixed-width padded `(N, L) uint8` matrix
  plus a length vector -- vectorizable on the 8x128 VPU, unlike the
  reference's offsets+bytes heap (VariableWidthBlock). Wide or
  low-cardinality string columns should be wrapped in `DictionaryColumn`.
* `DictionaryColumn` (DictionaryBlock analog) is (indices:int32,
  dictionary:Block). RunLengthEncodedBlock is a DictionaryColumn with a
  1-row dictionary.
* A `Batch` is the Page analog: a tuple of equal-length columns plus an
  `active` row mask. XLA requires static shapes, so every Batch has a
  fixed `capacity`; rows beyond the real row count -- and rows dropped by
  filters -- are simply inactive in the mask. This replaces the
  reference's SelectedPositions selection vectors
  (operator/project/PageProcessor.java:112, SelectedPositions.java:21)
  with a form the VPU can consume without gathers.

All classes are JAX pytrees: they flow through jit/shard_map/scan, and
sharding annotations apply leaf-wise.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import types as T

__all__ = ["Column", "StringColumn", "DictionaryColumn", "Int128Column",
           "Batch", "Block", "HostStrings", "from_numpy", "to_numpy",
           "BatchBuilder", "concat_batches"]


def _register(cls, data_fields, meta_fields):
    jax.tree_util.register_dataclass(cls, data_fields=list(data_fields),
                                     meta_fields=list(meta_fields))
    return cls


@dataclasses.dataclass
class Column:
    """Fixed-width column: `values` (N,) dtype'd array, `nulls` (N,) bool
    (True = SQL NULL). Value slots under a null are unspecified but must be
    finite/in-domain so padded lanes never poison reductions."""

    values: jax.Array
    nulls: jax.Array
    type: T.Type = dataclasses.field(metadata=dict(static=True))

    def __len__(self):
        return self.values.shape[0]

    @property
    def capacity(self) -> int:
        return self.values.shape[0]


_register(Column, ["values", "nulls"], ["type"])


@dataclasses.dataclass
class StringColumn:
    """Padded string column: `chars` (N, L) uint8, `lengths` (N,) int32,
    `nulls` (N,) bool. chars[i, k] for k >= lengths[i] must be 0 so
    equality can compare full rows without masking."""

    chars: jax.Array
    lengths: jax.Array
    nulls: jax.Array
    type: T.Type = dataclasses.field(metadata=dict(static=True))

    def __len__(self):
        return self.chars.shape[0]

    @property
    def capacity(self) -> int:
        return self.chars.shape[0]

    @property
    def max_len(self) -> int:
        return self.chars.shape[1]


_register(StringColumn, ["chars", "lengths", "nulls"], ["type"])


@dataclasses.dataclass
class DictionaryColumn:
    """Dictionary-encoded column (DictionaryBlock analog): row i's value is
    dictionary[indices[i]]. `nulls` is the top-level null mask (a null row
    may point at any dictionary slot)."""

    indices: jax.Array
    dictionary: Union[Column, StringColumn]
    nulls: jax.Array
    type: T.Type = dataclasses.field(metadata=dict(static=True))

    def __len__(self):
        return self.indices.shape[0]

    @property
    def capacity(self) -> int:
        return self.indices.shape[0]

    def decode(self) -> Union[Column, StringColumn]:
        """Materialize the flat column (gather through the dictionary)."""
        d = self.dictionary
        if isinstance(d, StringColumn):
            return StringColumn(d.chars[self.indices], d.lengths[self.indices],
                                self.nulls, self.type)
        return Column(d.values[self.indices], self.nulls, self.type)


_register(DictionaryColumn, ["indices", "dictionary", "nulls"], ["type"])


@dataclasses.dataclass
class ArrayColumn:
    """Fixed-fanout array column (ArrayBlock analog, TPU layout): row i's
    array is elements[i, :lengths[i]]. The reference stores arrays as
    offsets into a flat child block (pointer-shaped); a (N, K) matrix
    keeps element access vectorizable -- K is the per-batch max
    cardinality (shape bucketing, like string widths). Fixed-width
    element types in round 1."""

    elements: jax.Array    # (N, K) element values
    elem_nulls: jax.Array  # (N, K)
    lengths: jax.Array     # (N,)
    nulls: jax.Array       # (N,) top-level null array
    type: T.Type = dataclasses.field(metadata=dict(static=True))

    def __len__(self):
        return self.elements.shape[0]

    @property
    def capacity(self) -> int:
        return self.elements.shape[0]

    @property
    def max_cardinality(self) -> int:
        return self.elements.shape[1]


_register(ArrayColumn, ["elements", "elem_nulls", "lengths", "nulls"], ["type"])


@dataclasses.dataclass
class Int128Column:
    """Long-decimal lanes (Int128ArrayBlock / Decimals.java analog):
    value = hi * 2^64 + lo in two's complement, stored SoA (two flat
    64-bit lanes) so every op stays a plain VPU elementwise op -- see
    int128.py for the arithmetic."""

    hi: jax.Array   # int64
    lo: jax.Array   # uint64
    nulls: jax.Array
    type: T.Type = dataclasses.field(metadata=dict(static=True))

    def __len__(self):
        return self.hi.shape[0]

    @property
    def capacity(self) -> int:
        return self.hi.shape[0]


_register(Int128Column, ["hi", "lo", "nulls"], ["type"])


@dataclasses.dataclass
class MapColumn:
    """Fixed-fanout map column (MapBlock analog, TPU layout): row i's
    entries are (keys[i, j], values[i, j]) for j < lengths[i]. Keys are
    non-null by SQL contract; fixed-width key/value types in this
    revision (string keys ride dictionary-encoded ints upstream)."""

    keys: jax.Array        # (N, K) key lanes
    values: jax.Array      # (N, K) value lanes
    value_nulls: jax.Array  # (N, K)
    lengths: jax.Array     # (N,)
    nulls: jax.Array       # (N,) top-level null map
    type: T.Type = dataclasses.field(metadata=dict(static=True))

    def __len__(self):
        return self.keys.shape[0]

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]

    @property
    def max_cardinality(self) -> int:
        return self.keys.shape[1]


_register(MapColumn, ["keys", "values", "value_nulls", "lengths", "nulls"],
          ["type"])


@dataclasses.dataclass
class RowColumn:
    """Struct column (RowBlock analog): one child Block per field plus a
    top-level null mask -- already SoA, the natural TPU layout (the
    reference's RowBlock is the same design)."""

    fields: Tuple["Block", ...]
    nulls: jax.Array
    type: T.Type = dataclasses.field(metadata=dict(static=True))

    def __len__(self):
        return self.nulls.shape[0]

    @property
    def capacity(self) -> int:
        return self.nulls.shape[0]

    def field(self, i: int) -> "Block":
        return self.fields[i]


_register(RowColumn, ["fields", "nulls"], ["type"])

Block = Union[Column, StringColumn, DictionaryColumn, ArrayColumn,
              Int128Column, MapColumn, RowColumn]


@dataclasses.dataclass
class Batch:
    """The Page analog: equal-capacity columns + an active-row mask.

    `active[i]` False means row i is padding or was filtered out. All
    kernels must honor the mask; `count()` is the live row count.
    """

    columns: Tuple[Block, ...]
    active: jax.Array

    def __len__(self):
        return self.capacity

    @property
    def capacity(self) -> int:
        return self.active.shape[0]

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def count(self) -> jax.Array:
        return jnp.sum(self.active.astype(jnp.int32))

    def column(self, i: int) -> Block:
        return self.columns[i]

    def with_columns(self, columns: Sequence[Block]) -> "Batch":
        return Batch(tuple(columns), self.active)

    def with_active(self, active: jax.Array) -> "Batch":
        return Batch(self.columns, active)


_register(Batch, ["columns", "active"], [])


# --------------------------------------------------------------------------
# Host <-> device staging
# --------------------------------------------------------------------------

class HostStrings:
    """A string column on the host in the device's own encoding:
    `chars` (n, L) uint8, zero beyond each row's length, and `lengths`
    (n,) int32 -- what a `StringColumn` holds, as numpy. The generator
    makes it, the memory connector stores it, staging hands it to the
    device and a result comes back as it: no Python string per row
    anywhere between. Python strings are made where someone asks for
    one (`col[i]`, iteration, `np.asarray(col)`, a comparison with a
    str): where a client's rows are rendered, and in tests. A NULL
    row is an empty row; the null mask travels beside the column."""

    __slots__ = ("chars", "lengths")
    dtype = np.dtype(object)   # what np.asarray(col) gives

    def __init__(self, chars: np.ndarray, lengths: np.ndarray):
        self.chars = chars
        self.lengths = lengths

    @classmethod
    def from_objects(cls, values) -> "HostStrings":
        """Encode Python strings (None: an empty row), one at a time:
        for what arrives as Python objects (VALUES rows, API callers)."""
        if isinstance(values, HostStrings):
            return values
        encoded = [b"" if v is None else
                   v if isinstance(v, bytes) else str(v).encode("utf-8")
                   for v in values]
        lengths = np.fromiter((len(b) for b in encoded), dtype=np.int32,
                              count=len(encoded))
        width = max(int(lengths.max()) if len(encoded) else 0, 1)
        chars = np.zeros((len(encoded), width), dtype=np.uint8)
        flat = np.frombuffer(b"".join(encoded), dtype=np.uint8)
        if len(flat):
            rows = np.repeat(np.arange(len(encoded)), lengths)
            starts = np.cumsum(lengths) - lengths
            chars[rows, np.arange(len(flat)) - np.repeat(starts, lengths)] = flat
        return cls(chars, lengths)

    @classmethod
    def concat(cls, parts: Sequence["HostStrings"]) -> "HostStrings":
        width = max((p.chars.shape[1] for p in parts), default=1)
        n = sum(len(p) for p in parts)
        chars = np.zeros((n, width), dtype=np.uint8)
        at = 0
        for p in parts:
            chars[at:at + len(p), :p.chars.shape[1]] = p.chars
            at += len(p)
        return cls(chars, np.concatenate([p.lengths for p in parts])
                   if parts else np.zeros(0, dtype=np.int32))

    def __len__(self) -> int:
        return self.chars.shape[0]

    @property
    def shape(self):
        return (self.chars.shape[0],)

    @property
    def nbytes(self) -> int:
        return self.chars.nbytes + self.lengths.nbytes

    def __getitem__(self, key):
        if isinstance(key, (int, np.integer)):
            return self.chars[key, :self.lengths[key]].tobytes().decode(
                "utf-8", "replace")
        return HostStrings(self.chars[key], self.lengths[key])

    def __iter__(self):
        return iter(self.to_objects())

    def to_objects(self) -> np.ndarray:
        """The rows as an object array of Python str: the rendering
        boundary. numpy's bytes view drops the zero padding in one
        pass; a row it cuts short (a NUL inside the string) is read
        again by its length."""
        n, width = self.chars.shape
        raw = np.ascontiguousarray(self.chars).view(f"S{width}").reshape(n)
        out = np.char.decode(raw, "utf-8", "replace").astype(object) \
            if n else np.empty(0, dtype=object)
        for i in np.flatnonzero(np.char.str_len(raw) != self.lengths):
            out[i] = self[int(i)]
        return out

    def __array__(self, dtype=None, copy=None):
        out = self.to_objects()
        return out if dtype is None else out.astype(dtype)

    def astype(self, dtype):
        return self.to_objects().astype(dtype)

    def tolist(self) -> list:
        return self.to_objects().tolist()

    def _equals(self, other) -> np.ndarray:
        if isinstance(other, str):
            lit = np.frombuffer(other.encode("utf-8"), dtype=np.uint8)
            if len(lit) > self.chars.shape[1]:
                return np.zeros(len(self), dtype=bool)
            return (self.lengths == len(lit)) & \
                (self.chars[:, :len(lit)] == lit).all(axis=1)
        return self.to_objects() == np.asarray(other)

    def __eq__(self, other):
        return self._equals(other)

    def __ne__(self, other):
        return ~self._equals(other)

    __hash__ = None

    def __repr__(self):
        return f"HostStrings(n={len(self)}, width={self.chars.shape[1]})"


def _pad(arr: np.ndarray, capacity: int, fill=0) -> np.ndarray:
    n = arr.shape[0]
    if n == capacity:
        return arr
    pad_width = [(0, capacity - n)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad_width, constant_values=fill)


def _pad_cast(arr: np.ndarray, capacity: int, dt, fill=0) -> np.ndarray:
    """Fused cast+pad: allocate the (capacity, ...) staging buffer at
    the target dtype once and slice-assign into it, instead of the
    cast-then-pad chain that materializes two host copies of the same
    column (M003 copy amplification)."""
    dt = np.dtype(dt)
    n = arr.shape[0]
    if n == capacity and arr.dtype == dt:
        return arr
    out = np.full((capacity,) + arr.shape[1:], fill, dtype=dt)
    out[:n] = arr
    return out


def from_numpy(ty: T.Type, values: np.ndarray, nulls: Optional[np.ndarray] = None,
               capacity: Optional[int] = None,
               physical_dtype=None) -> Block:
    """Stage a host column to a device Block. For string types `values`
    is a `HostStrings` (staged as it is), an object/str numpy array
    (encoded here, row by row) or a (N, L) uint8 matrix; for
    array types, an object array of Python lists (None elements = null,
    None rows = null array).

    `physical_dtype` (narrow-width execution, plan/widths.py) overrides
    the staged lane dtype for fixed-width columns whose value range the
    planner proved fits a narrower lane -- host->device transfer and
    HBM residency shrink accordingly; the logical `ty` is unchanged and
    compute sites widen before arithmetic."""
    if ty.base == "array":
        ety = ty.element_type
        rows = list(values)
        n = len(rows)
        capacity = capacity or n
        k = max((len(r) for r in rows if r is not None), default=1) or 1
        elems = np.zeros((n, k), dtype=ety.to_dtype())
        enulls = np.ones((n, k), dtype=bool)
        lengths = np.zeros(n, dtype=np.int32)
        topn = np.zeros(n, dtype=bool) if nulls is None else \
            np.asarray(nulls, dtype=bool).copy()
        for i, r in enumerate(rows):
            if r is None or topn[i]:
                topn[i] = True
                continue
            lengths[i] = len(r)
            for j, v in enumerate(r):
                if v is None:
                    continue
                elems[i, j] = v
                enulls[i, j] = False
        return ArrayColumn(jnp.asarray(_pad(elems, capacity)),
                           jnp.asarray(_pad(enulls, capacity, fill=True)),
                           jnp.asarray(_pad(lengths, capacity)),
                           jnp.asarray(_pad(topn, capacity, fill=True)), ty)
    if ty.base == "map":
        # object array of python dicts (None = null map)
        kty, vty = ty.key_type, ty.value_type
        rows = list(values)
        n = len(rows)
        capacity = capacity or n
        k = max((len(r) for r in rows if r is not None), default=1) or 1
        keys = np.zeros((n, k), dtype=kty.to_dtype())
        vals = np.zeros((n, k), dtype=vty.to_dtype())
        vnulls = np.ones((n, k), dtype=bool)
        lengths = np.zeros(n, dtype=np.int32)
        topn = np.zeros(n, dtype=bool) if nulls is None else \
            np.asarray(nulls, dtype=bool).copy()
        for i, r in enumerate(rows):
            if r is None or topn[i]:
                topn[i] = True
                continue
            lengths[i] = len(r)
            for j, (kk, vv) in enumerate(r.items()):
                keys[i, j] = kk
                if vv is not None:
                    vals[i, j] = vv
                    vnulls[i, j] = False
        return MapColumn(jnp.asarray(_pad(keys, capacity)),
                         jnp.asarray(_pad(vals, capacity)),
                         jnp.asarray(_pad(vnulls, capacity, fill=True)),
                         jnp.asarray(_pad(lengths, capacity)),
                         jnp.asarray(_pad(topn, capacity, fill=True)), ty)
    if ty.base == "row":
        # object array of python tuples/lists (None = null row)
        ftys = ty.field_types
        rows = list(values)
        n = len(rows)
        capacity = capacity or n
        topn = np.zeros(n, dtype=bool) if nulls is None else \
            np.asarray(nulls, dtype=bool).copy()
        fields = []
        for fi, fty in enumerate(ftys):
            col = np.empty(n, dtype=object)
            for i, r in enumerate(rows):
                col[i] = None if (r is None or topn[i]) else r[fi]
            if not (fty.is_string or fty.base in ("array", "map", "row")
                    or (fty.is_decimal and not fty.is_short_decimal)):
                fn = np.array([v is None for v in col], dtype=bool)
                col = np.array([0 if v is None else v for v in col],
                               dtype=fty.to_dtype())
                fields.append(from_numpy(fty, col, fn, capacity))
            else:
                fields.append(from_numpy(fty, col, None, capacity))
        for i, r in enumerate(rows):
            if r is None:
                topn[i] = True
        return RowColumn(tuple(fields),
                         jnp.asarray(_pad(topn, capacity, fill=True)), ty)
    n = values.shape[0]
    capacity = capacity or n
    if nulls is None:
        if values.dtype == object and not isinstance(values, HostStrings):
            nulls = np.array([v is None for v in values], dtype=bool)
        else:
            nulls = np.zeros(n, dtype=bool)
    nulls = _pad_cast(nulls, capacity, bool, fill=True)
    if ty.is_string and values.dtype != np.uint8:
        enc = HostStrings.from_objects(values)  # itself, where it is one
        return StringColumn(jnp.asarray(_pad(enc.chars, capacity)),
                            jnp.asarray(_pad(enc.lengths, capacity)),
                            jnp.asarray(nulls), ty)
    if ty.is_string:
        # length = position after the last nonzero byte (strings may
        # contain interior NULs; trailing zeros are padding by invariant)
        nonzero = values != 0
        any_nz = nonzero.any(axis=1)
        lengths = np.where(any_nz,
                           values.shape[1] - np.argmax(nonzero[:, ::-1], axis=1),
                           0).astype(np.int32)
        return StringColumn(jnp.asarray(_pad(values, capacity)),
                            jnp.asarray(_pad(lengths, capacity)),
                            jnp.asarray(nulls), ty)
    if ty.is_decimal and not ty.is_short_decimal:
        # long decimals stage as 128-bit lane pairs (Int128Column); host
        # values arrive as Python ints (exact) or any int64-safe array
        from .int128 import python_to_int128
        if values.dtype == object:
            hi, lo = python_to_int128(list(values))
        else:
            v = np.asarray(values, dtype=np.int64)
            hi, lo = (v >> 63).astype(np.int64), v.astype(np.uint64)
        return Int128Column(jnp.asarray(_pad(hi, capacity)),
                            jnp.asarray(_pad(lo, capacity)),
                            jnp.asarray(nulls), ty)
    dt = np.dtype(physical_dtype) if physical_dtype is not None \
        else ty.to_dtype()
    values = _pad_cast(values, capacity, dt)
    return Column(jnp.asarray(values), jnp.asarray(nulls), ty)


def batch_from_numpy(types: Sequence[T.Type], arrays: Sequence[np.ndarray],
                     nulls: Optional[Sequence[Optional[np.ndarray]]] = None,
                     capacity: Optional[int] = None,
                     physical_dtypes=None, sharding=None) -> Batch:
    """Host columns to a device Batch. With `sharding` (rows over a
    mesh's devices: `NamedSharding(mesh, P(axis))`, `capacity` a
    multiple of their number) the batch is staged shard by shard
    (`_sharded_batch`)."""
    n = arrays[0].shape[0]
    capacity = capacity or n
    nulls = nulls or [None] * len(arrays)
    physical_dtypes = physical_dtypes or [None] * len(arrays)
    if sharding is not None:
        return _sharded_batch(types, arrays, nulls, capacity,
                              physical_dtypes, sharding)
    cols = tuple(from_numpy(t, a, m, capacity, physical_dtype=p)
                 for t, a, m, p in zip(types, arrays, nulls,
                                       physical_dtypes))
    active = np.zeros(capacity, dtype=bool)
    active[:n] = True
    return Batch(cols, jnp.asarray(active))


def _shards_alike(ty: T.Type, values) -> bool:
    """A column whose staged leaves have one shape whatever rows a
    shard holds: not the nested types (an array's width is its longest
    row's) nor strings still to be encoded (a matrix as wide as the
    longest string)."""
    if ty.base in ("array", "map", "row"):
        return False
    return not ty.is_string or isinstance(values, HostStrings) \
        or values.dtype == np.uint8


def _sharded_batch(types, arrays, nulls, capacity: int, physical_dtypes,
                   sharding) -> Batch:
    """The batch of `batch_from_numpy`, its rows in contiguous ranges
    over the sharding's devices: each device's range is cut from the
    host columns, narrowed and padded to the shard's capacity and put
    on that device, the devices side by side on a thread each (numpy's
    casts and the transfers run outside the interpreter's lock). Nothing
    passes through the first device and a program sharded the same way
    takes the batch as it lies. Every shard but the last is full; the
    padding is at the end of the table's order, as on one device."""
    devices = list(sharding.mesh.devices.flat)
    n = arrays[0].shape[0]
    per = capacity // len(devices)
    assert per * len(devices) == capacity, (capacity, len(devices))
    if not all(_shards_alike(t, a) for t, a in zip(types, arrays)):
        whole = batch_from_numpy(types, arrays, nulls, capacity,
                                 physical_dtypes)
        return jax.device_put(whole, sharding)

    def stage(k: int) -> Batch:
        lo, hi = min(k * per, n), min((k + 1) * per, n)
        with jax.default_device(devices[k]):
            return batch_from_numpy(
                types, [a[lo:hi] for a in arrays],
                [None if m is None else m[lo:hi] for m in nulls],
                per, physical_dtypes)

    with concurrent.futures.ThreadPoolExecutor(len(devices)) as pool:
        shards = list(pool.map(stage, range(len(devices))))
    return jax.tree_util.tree_map(
        lambda *leaves: jax.make_array_from_single_device_arrays(
            (capacity,) + leaves[0].shape[1:], sharding, list(leaves)),
        *shards)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _blank_lanes(rows: int, dtypes):
    return tuple(jnp.zeros(rows, dtype=dt) for dt in dtypes)


@functools.partial(jax.jit, donate_argnums=0)
def _land_piece(lanes, piece, at):
    return tuple(jax.lax.dynamic_update_slice(lane, part, (at,))
                 for lane, part in zip(lanes, piece))


@functools.partial(jax.jit, static_argnums=2)
def _close_lanes(lanes, rows, capacity: int):
    """The first `capacity` rows of every lane, the rows from `rows` on
    as padding (values 0, masks True), and the `active` mask."""
    live = jnp.arange(capacity) < rows
    k = len(lanes) // 2
    values = tuple(jnp.where(live, v[:capacity], jnp.zeros((), v.dtype))
                   for v in lanes[:k])
    nulls = tuple(jnp.where(live, m[:capacity], True) for m in lanes[k:])
    return values, nulls, live


class BatchBuilder:
    """A Batch of plain `Column`s assembled on the device from pieces of
    its rows put one at a time: what `batch_from_numpy` gives for the
    pieces' concatenation (same capacity, dtypes, row order and masks),
    with the put of one piece running while its caller prepares the
    next. A piece lands in lanes allocated once, by a donated
    `dynamic_update_slice`. A piece's arrays may be longer than the rows
    it holds (the next piece overwrites the rest), so that a caller can
    keep to a few array lengths, each of which compiles once, whatever
    rows its pieces hold; `room` is the longest such array, and what the
    lanes keep past `capacity` for the last piece to land in."""

    def __init__(self, types: Sequence[T.Type], dtypes, capacity: int,
                 room: int):
        self.types, self.capacity = tuple(types), capacity
        self.rows = 0
        self._lanes = _blank_lanes(
            capacity + room,
            tuple(np.dtype(dt) for dt in dtypes)
            + (np.dtype(bool),) * len(self.types))

    def put(self, values: Sequence[np.ndarray], nulls: Sequence[np.ndarray],
            rows: int) -> int:
        """Put one piece (a lane and a mask a column, equally long, the
        piece's `rows` rows first); returns the bytes handed over."""
        piece = jax.device_put((*values, *nulls))
        self._lanes = _land_piece(self._lanes, piece, self.rows)
        self.rows += rows
        return sum(a.nbytes for a in piece)

    def finish(self) -> Batch:
        if self.rows > self.capacity:
            raise ValueError(f"{self.rows} rows put into a batch of "
                             f"capacity {self.capacity}")
        values, nulls, active = _close_lanes(self._lanes, self.rows,
                                             self.capacity)
        self._lanes = None
        return Batch(tuple(Column(v, n, ty) for v, n, ty
                           in zip(values, nulls, self.types)), active)


def to_numpy(block: Block) -> Tuple[np.ndarray, np.ndarray]:
    """Fetch (values, nulls) to host. Strings come back as they are
    held, a `HostStrings`; arrays as an object array of Python lists."""
    if isinstance(block, DictionaryColumn):
        return to_numpy(block.decode())
    if isinstance(block, ArrayColumn):
        elems = np.asarray(block.elements)
        enulls = np.asarray(block.elem_nulls)
        lengths = np.asarray(block.lengths)
        nulls = np.asarray(block.nulls)
        out = np.empty(len(lengths), dtype=object)
        for i in range(len(lengths)):
            out[i] = None if nulls[i] else [
                None if enulls[i, j] else elems[i, j].item()
                for j in range(lengths[i])]
        return out, nulls
    if isinstance(block, StringColumn):
        return (HostStrings(np.asarray(block.chars),
                            np.asarray(block.lengths)),
                np.asarray(block.nulls))
    if isinstance(block, Int128Column):
        from .int128 import int128_to_python
        vals = int128_to_python(np.asarray(block.hi), np.asarray(block.lo))
        return vals, np.asarray(block.nulls)
    if isinstance(block, MapColumn):
        keys = np.asarray(block.keys)
        vals = np.asarray(block.values)
        vnulls = np.asarray(block.value_nulls)
        lengths = np.asarray(block.lengths)
        nulls = np.asarray(block.nulls)
        out = np.empty(len(lengths), dtype=object)
        for i in range(len(lengths)):
            out[i] = None if nulls[i] else {
                keys[i, j].item(): (None if vnulls[i, j]
                                    else vals[i, j].item())
                for j in range(lengths[i])}
        return out, nulls
    if isinstance(block, RowColumn):
        nulls = np.asarray(block.nulls)
        fvals = [to_numpy(f) for f in block.fields]
        out = np.empty(len(nulls), dtype=object)
        for i in range(len(nulls)):
            out[i] = None if nulls[i] else tuple(
                None if fn[i] else (fv[i].item()
                                    if isinstance(fv[i], np.generic)
                                    else fv[i])
                for fv, fn in fvals)
        return out, nulls
    return np.asarray(block.values), np.asarray(block.nulls)


def gather_block(b: Block, idx: jax.Array, valid: Optional[jax.Array] = None
                 ) -> Block:
    """Row gather for every Block kind (the one shared implementation
    behind join/aggregation/unnest/sort row movement). `valid=None`
    means a pure permutation (nulls ride along); with a mask, invalid
    output rows become NULL/empty."""
    if isinstance(b, DictionaryColumn):
        if valid is None:
            return DictionaryColumn(b.indices[idx], b.dictionary,
                                    b.nulls[idx], b.type)
        b = b.decode()
    if isinstance(b, StringColumn):
        lengths = b.lengths[idx]
        nulls = b.nulls[idx]
        if valid is not None:
            lengths = jnp.where(valid, lengths, 0)
            nulls = jnp.where(valid, nulls, True)
        return StringColumn(b.chars[idx], lengths, nulls, b.type)
    if isinstance(b, ArrayColumn):
        lengths = b.lengths[idx]
        nulls = b.nulls[idx]
        if valid is not None:
            lengths = jnp.where(valid, lengths, 0)
            nulls = jnp.where(valid, nulls, True)
        return ArrayColumn(b.elements[idx], b.elem_nulls[idx], lengths,
                           nulls, b.type)
    if isinstance(b, MapColumn):
        lengths = b.lengths[idx]
        nulls = b.nulls[idx]
        if valid is not None:
            lengths = jnp.where(valid, lengths, 0)
            nulls = jnp.where(valid, nulls, True)
        return MapColumn(b.keys[idx], b.values[idx], b.value_nulls[idx],
                         lengths, nulls, b.type)
    if isinstance(b, RowColumn):
        nulls = b.nulls[idx]
        if valid is not None:
            nulls = jnp.where(valid, nulls, True)
        return RowColumn(tuple(gather_block(f, idx, valid)
                               for f in b.fields), nulls, b.type)
    if isinstance(b, Int128Column):
        nulls = b.nulls[idx]
        if valid is not None:
            nulls = jnp.where(valid, nulls, True)
        return Int128Column(b.hi[idx], b.lo[idx], nulls, b.type)
    nulls = b.nulls[idx]
    if valid is not None:
        nulls = jnp.where(valid, nulls, True)
    return Column(b.values[idx], nulls, b.type)


def null_like(b: Block) -> Block:
    """An all-NULL block with the same capacity/type/layout as `b`
    (GroupIdNode's dropped-key columns; the reference materializes the
    same via null Blocks in GroupIdOperator)."""
    n = len(b)
    ones = jnp.ones(n, dtype=bool)
    if isinstance(b, DictionaryColumn):
        b = b.decode()
    if isinstance(b, StringColumn):
        return StringColumn(b.chars, jnp.zeros(n, dtype=jnp.int32), ones,
                            b.type)
    if isinstance(b, ArrayColumn):
        return ArrayColumn(b.elements, b.elem_nulls,
                           jnp.zeros(n, dtype=jnp.int32), ones, b.type)
    if isinstance(b, Int128Column):
        return Int128Column(b.hi, b.lo, ones, b.type)
    return Column(b.values, ones, b.type)


def concat_batches(batches: Sequence[Batch]) -> Batch:
    """Concatenate batches (device-side). Capacities add."""
    cols = []
    for ci in range(batches[0].num_columns):
        blocks = [b.columns[ci] for b in batches]
        blocks = [b.decode() if isinstance(b, DictionaryColumn) else b for b in blocks]
        b0 = blocks[0]
        if isinstance(b0, StringColumn):
            max_l = max(b.max_len for b in blocks)
            chars = jnp.concatenate([
                jnp.pad(b.chars, ((0, 0), (0, max_l - b.max_len))) for b in blocks])
            cols.append(StringColumn(chars,
                                     jnp.concatenate([b.lengths for b in blocks]),
                                     jnp.concatenate([b.nulls for b in blocks]),
                                     b0.type))
        elif isinstance(b0, Int128Column):
            cols.append(Int128Column(
                jnp.concatenate([b.hi for b in blocks]),
                jnp.concatenate([b.lo for b in blocks]),
                jnp.concatenate([b.nulls for b in blocks]), b0.type))
        elif isinstance(b0, ArrayColumn):
            max_k = max(b.elements.shape[1] for b in blocks)
            cols.append(ArrayColumn(
                jnp.concatenate([
                    jnp.pad(b.elements,
                            ((0, 0), (0, max_k - b.elements.shape[1])))
                    for b in blocks]),
                jnp.concatenate([
                    jnp.pad(b.elem_nulls,
                            ((0, 0), (0, max_k - b.elements.shape[1])))
                    for b in blocks]),
                jnp.concatenate([b.lengths for b in blocks]),
                jnp.concatenate([b.nulls for b in blocks]), b0.type))
        elif isinstance(b0, MapColumn):
            max_k = max(b.keys.shape[1] for b in blocks)

            def cat2(field):
                return jnp.concatenate([
                    jnp.pad(getattr(b, field),
                            ((0, 0), (0, max_k - b.keys.shape[1])))
                    for b in blocks])
            cols.append(MapColumn(
                cat2("keys"), cat2("values"), cat2("value_nulls"),
                jnp.concatenate([b.lengths for b in blocks]),
                jnp.concatenate([b.nulls for b in blocks]), b0.type))
        elif isinstance(b0, RowColumn):
            fields = tuple(
                concat_batches([Batch((b.fields[fi],),
                                      jnp.ones(len(b), dtype=bool))
                                for b in blocks]).columns[0]
                for fi in range(len(b0.fields)))
            cols.append(RowColumn(
                fields, jnp.concatenate([b.nulls for b in blocks]),
                b0.type))
        else:
            cols.append(Column(jnp.concatenate([b.values for b in blocks]),
                               jnp.concatenate([b.nulls for b in blocks]), b0.type))
    active = jnp.concatenate([b.active for b in batches])
    return Batch(tuple(cols), active)
