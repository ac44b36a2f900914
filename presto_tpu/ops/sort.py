"""Sort and TopN operators.

Reference surface: operator/OrderByOperator.java, operator/TopNOperator.java,
operator/TopNRowNumberOperator.java and the OrderingCompiler's generated
comparators. On TPU both collapse into `jax.lax.sort` over order-preserving
key words (ops/keys.py): a full sort is one bitonic/radix sort on device;
TopN is sort + static slice (the PriorityQueue strategy of the reference
serves incremental streaming, which the batch model doesn't need).

DESC is word complement; NULLS FIRST/LAST flips the per-column null word.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..block import Batch, Block
from .keys import key_words, lex_sort

__all__ = ["SortKey", "sort_batch", "top_n", "sort_permutation"]


def _column_words(col: Block, descending: bool, nulls_last: bool):
    words, _ = key_words([col], nulls_last=[nulls_last != descending])
    # note: key_words emits (null_word, value_words...); for DESC we flip
    # value words AND the null word; pre-flipping nulls_last above makes
    # the double flip come out right.
    if descending:
        words = [~w for w in words]
    return words


class SortKey(Tuple):
    """(channel, descending, nulls_last) triple."""
    def __new__(cls, channel: int, descending: bool = False,
                nulls_last: Optional[bool] = None):
        # Presto default: ASC_NULLS_LAST / DESC_NULLS_LAST
        if nulls_last is None:
            nulls_last = True
        return super().__new__(cls, (channel, descending, nulls_last))

    channel = property(lambda s: s[0])
    descending = property(lambda s: s[1])
    nulls_last = property(lambda s: s[2])


def sort_permutation(batch: Batch, keys: Sequence[SortKey]) -> jnp.ndarray:
    """Stable permutation ordering active rows by keys; inactive rows sink
    to the end."""
    n = batch.capacity
    operands: List[jnp.ndarray] = [
        jnp.where(batch.active, np.uint64(0), np.uint64(1))]
    for sk in keys:
        operands.extend(_column_words(batch.column(sk.channel),
                                      sk.descending, sk.nulls_last))
    operands.append(jnp.arange(n, dtype=jnp.int32))
    out = lex_sort(operands, num_keys=len(operands) - 1, is_stable=True)
    return out[-1]


from ..block import gather_block as _permute_block  # perm = gather, no mask


def sort_batch(batch: Batch, keys: Sequence[SortKey]) -> Batch:
    perm = sort_permutation(batch, keys)
    return Batch(tuple(_permute_block(c, perm) for c in batch.columns),
                 batch.active[perm])


@jax.named_scope("top_n")
def top_n(batch: Batch, keys: Sequence[SortKey], n: int) -> Batch:
    """TopN: sorted prefix of n rows (static output capacity n)."""
    s = sort_batch(batch, keys)
    take = min(n, s.capacity)
    head = jnp.arange(take, dtype=jnp.int32)
    cols = tuple(_permute_block(c, head) for c in s.columns)
    return Batch(cols, s.active[:take])
