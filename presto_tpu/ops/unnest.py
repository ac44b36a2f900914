"""Unnest: the UnnestOperator analog.

Reference surface: operator/unnest/ (UnnestOperator expanding ARRAY/MAP
columns into rows, replicating the other channels; UnnestNode in the
plan vocabulary, WITH ORDINALITY variant).

TPU-first: the same static-capacity prefix-sum expansion the join
uses (ops/join._slot_rows): output slot k maps back to its source row
through the exclusive offsets of per-row cardinalities, and to the
element by k - offset[row]. One gather per output column -- no
per-row loops, overflow flagged when out_capacity is short.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

from .. import types as T
from ..block import ArrayColumn, Batch, Block, Column, MapColumn, \
    gather_block as _gather
from .join import _slot_rows

__all__ = ["unnest"]


def unnest(batch: Batch, array_channel: int, out_capacity: int,
           with_ordinality: bool = False) -> Tuple[Batch, jnp.ndarray]:
    """Expand batch rows by the array (or map) at `array_channel`.
    Output columns: all input columns except the unnested one, then the
    element column -- for maps, a key column THEN a value column -- and
    an ordinality BIGINT column when requested. NULL/empty collections
    emit no rows (Presto UNNEST semantics). Returns (batch, overflow)."""
    arr = batch.column(array_channel)
    assert isinstance(arr, (ArrayColumn, MapColumn)), \
        "unnest requires an array or map column"
    n = batch.capacity

    cnt = jnp.where(batch.active & ~arr.nulls, arr.lengths, 0)
    off = jnp.cumsum(cnt, dtype=jnp.int64) - cnt
    total = off[-1] + cnt[-1]
    overflow = total > out_capacity

    k = jnp.arange(out_capacity, dtype=jnp.int32)
    row, j, _ = _slot_rows(off, out_capacity)
    row = jnp.clip(row, 0, n - 1)
    valid = (k < total) & (j < cnt[row])
    jc = jnp.clip(j, 0, arr.max_cardinality - 1)

    out_cols: List[Block] = []
    for ci, c in enumerate(batch.columns):
        if ci == array_channel:
            continue
        out_cols.append(_gather(c, row, valid))
    if isinstance(arr, MapColumn):
        key_vals = arr.keys[row, jc]
        out_cols.append(Column(key_vals, ~valid, arr.type.key_type))
        val_vals = arr.values[row, jc]
        val_nulls = jnp.where(valid, arr.value_nulls[row, jc], True)
        out_cols.append(Column(val_vals, val_nulls, arr.type.value_type))
    else:
        elem_vals = arr.elements[row, jc]
        elem_nulls = jnp.where(valid, arr.elem_nulls[row, jc], True)
        out_cols.append(Column(elem_vals, elem_nulls,
                               arr.type.element_type))
    if with_ordinality:
        out_cols.append(Column((j + 1).astype(jnp.int64), ~valid, T.BIGINT))
    return Batch(tuple(out_cols), valid), overflow
