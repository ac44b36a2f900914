"""Key normalization: columns -> order-preserving uint64 "key words".

Group-by, join, sort, topN and distinct all reduce to operations over
row keys. The reference implements each with a different hand-tuned
structure (MultiChannelGroupByHash.java:55, PagesIndex row store,
OrderingCompiler's comparators). On TPU the uniform primitive is
`jax.lax.sort` over a tuple of uint64 words per row, constructed so that

  lexicographic order of words == SQL order of the key tuple
  word equality                == SQL key-tuple equality (exact)

* int64/int32/date/decimal/boolean: one word, sign-flipped
  (x XOR 1<<63) so unsigned order matches signed order.
* float32/float64: IEEE trick -- non-negative: bits XOR 1<<63;
  negative: ~bits. NaN sorts above +inf (Presto's NaN-largest rule);
  -0.0 is normalized to 0.0 first.
* varchar/char: big-endian packed 8-byte chunks, zero-padded --
  ceil(max_len/8) words, lexicographic per chunk. Exact for any width.
* NULL: a dedicated leading null word per column orders nulls first or
  last; for equality uses, NULL == NULL (SQL GROUP BY/DISTINCT treat
  nulls as one group, and joins drop null keys separately).

Sort direction is applied by bit-flipping words at the use site.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..block import Block, Column, DictionaryColumn, Int128Column, StringColumn

_SIGN = np.uint64(1 << 63)

__all__ = ["key_words", "num_key_words", "lex_sort"]

@jax.named_scope("lex_sort")
def lex_sort(operands: Sequence[jnp.ndarray], num_keys: int,
             is_stable: bool = False) -> List[jnp.ndarray]:
    """`jax.lax.sort(operands, num_keys=...)` for key WORDS (uint64),
    same contract, same result up to the order of fully equal keys.

    More than one key word runs as least-significant-word-first passes
    of ONE single-key stable sort inside a `lax.scan` (the textbook LSD
    construction: a stable sort by the last word, then the one before,
    ... is the lexicographic stable sort), so the compiler sees one
    small comparator whatever the key width. XLA:TPU's cost for ONE
    multi-key sort grows roughly with the square of the 32-bit words
    its comparator reads: on the v5e host a 1-key uint64 stable sort of
    64Ki rows compiles in 33 s, 2 keys in 66 s, 4 keys in ~300 s, and
    TPC-H q3's 7-word group-by sort did not finish in 10 minutes, where
    this form took 49 s. The price is one pass per word at run time."""
    operands = list(operands)
    if num_keys == 1:
        return list(jax.lax.sort(operands, num_keys=1, is_stable=is_stable))
    assert all(k.dtype == jnp.uint64 for k in operands[:num_keys]), \
        "lex_sort keys are uint64 key words"

    def one_pass(perm, word):
        _, perm = jax.lax.sort((word[perm], perm), num_keys=1,
                               is_stable=True)
        return perm, None

    # the identity permutation, seeded from an operand so that inside a
    # shard_map the carry varies over the same mesh axes going in as
    # coming out
    n = operands[0].shape[0]
    identity = (jnp.zeros_like(operands[0], dtype=jnp.int32)
                + jnp.arange(n, dtype=jnp.int32))
    perm, _ = jax.lax.scan(one_pass, identity,
                           jnp.stack(operands[:num_keys])[::-1])
    return [o[perm] for o in operands]


def _fixed_words(col: Column) -> List[jnp.ndarray]:
    v = col.values
    if col.type.base == "timestamp with time zone":
        # order/equality on the INSTANT: same micros in different zones
        # are the same SQL value (TimestampWithTimeZoneType semantics)
        v = v >> 12
    if v.dtype == jnp.bool_:
        return [v.astype(jnp.uint64)]
    if v.dtype in (jnp.float32, jnp.float64):
        f = v.astype(jnp.float64)
        f = jnp.where(f == 0.0, 0.0, f)
        bits = jax.lax.bitcast_convert_type(f, jnp.uint64)
        neg = bits >> np.uint64(63) != 0
        w = jnp.where(neg, ~bits, bits ^ _SIGN)
        # NaN: canonical largest
        w = jnp.where(jnp.isnan(f), jnp.uint64(0xFFFFFFFFFFFFFFFF), w)
        return [w]
    return [(v.astype(jnp.int64).astype(jnp.uint64)) ^ _SIGN]


def _string_words(col: StringColumn) -> List[jnp.ndarray]:
    n, w = col.chars.shape
    padded = jnp.pad(col.chars, ((0, 0), (0, (-w) % 8)))
    nwords = padded.shape[1] // 8
    chunks = padded.reshape(n, nwords, 8).astype(jnp.uint64)
    shifts = (np.uint64(8) * (7 - np.arange(8, dtype=np.uint64)))[None, None, :]
    words = jnp.sum(chunks << shifts, axis=2)  # big-endian per chunk
    return [words[:, i] for i in range(nwords)]


def key_words(cols: Sequence[Block], nulls_last: Union[bool, Sequence[bool]] = False
              ) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """Build the per-row word list for a key tuple.

    Returns (words, any_null): `words` begins, for each column, with its
    null-order word followed by its value words (value words are zeroed
    under null so NULL keys compare equal); `any_null` flags rows where
    any key column is null (what joins use to drop null keys).
    """
    if isinstance(nulls_last, bool):
        nulls_last = [nulls_last] * len(cols)
    words: List[jnp.ndarray] = []
    any_null = None
    for col, nl in zip(cols, nulls_last):
        if isinstance(col, DictionaryColumn):
            col = col.decode()
        isnull = col.nulls
        any_null = isnull if any_null is None else (any_null | isnull)
        null_word = jnp.where(isnull, np.uint64(0 if not nl else 1),
                              np.uint64(1 if not nl else 0))
        words.append(null_word)
        if isinstance(col, StringColumn):
            vws = _string_words(col)
        elif isinstance(col, Int128Column):
            # 128-bit two's complement: sign-flipped hi word then lo
            vws = [col.hi.astype(jnp.uint64) ^ _SIGN, col.lo]
        else:
            vws = _fixed_words(col)
        for vw in vws:
            words.append(jnp.where(isnull, np.uint64(0), vw))
    if any_null is None:
        any_null = jnp.zeros(0, dtype=bool)
    return words, any_null


def num_key_words(cols: Sequence[Block]) -> int:
    total = 0
    for col in cols:
        if isinstance(col, DictionaryColumn):
            col = col.dictionary
        if isinstance(col, StringColumn):
            total += 1 + (col.max_len + 7) // 8
        elif isinstance(col, Int128Column):
            total += 3
        else:
            total += 2
    return total
