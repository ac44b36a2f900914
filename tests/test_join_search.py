"""`ops/join._match_ranges`, the lookup that turns a probe key into its
match range, against the pair of `searchsorted` calls it replaced."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from presto_tpu.ops.join import _match_ranges
from presto_tpu.parallel import WORKERS_AXIS

MAXW = np.uint64(0xFFFFFFFFFFFFFFFF)
NQ = 240  # queries a case: three sets of 80, eight shards of 30


def _case(keys, tail, queries):
    """(sorted build words with `tail` MAX-masked unusable rows behind
    them, n_usable, NQ queries: the given ones, then build keys)."""
    keys = np.sort(np.asarray(keys, dtype=np.uint64))
    sb = np.concatenate([keys, np.full(tail, MAXW, np.uint64)])
    q = np.asarray(queries, dtype=np.uint64)[:NQ]
    fill = sb if len(sb) else np.zeros(1, np.uint64)
    q = np.concatenate([q, np.resize(fill, NQ - len(q))])
    return sb, len(keys), q


def _cases():
    rng = np.random.default_rng(27)
    dense = np.arange(1, 4097)
    sparse = [(i // 8) * 32 + i % 8 + 1 for i in range(4000)]  # TPC-H's
    return {
        "dense_unique": _case(dense, 0, rng.integers(0, 4200, 200)),
        "dense_masked_tail": _case(dense[:3000], 1096,
                                   rng.integers(0, 4200, 200)),
        "sparse_orderkeys": _case(sparse, 96, rng.integers(0, 17000, 200)),
        "heavy_duplicates": _case(rng.integers(0, 60, 4000), 96,
                                  rng.integers(0, 64, 200)),
        "one_outlier": _case(np.concatenate([dense[:-1], [2 ** 62]]), 0,
                             np.concatenate([rng.integers(0, 4200, 190),
                                             [2 ** 62, 2 ** 62 + 1,
                                              2 ** 63]])),
        "random_64_bit": _case(rng.integers(0, 2 ** 64, 4000,
                                            dtype=np.uint64), 96,
                               rng.integers(0, 2 ** 64, 200,
                                            dtype=np.uint64)),
        "max_word_is_a_key": _case([5, MAXW, MAXW], 5, [5, 6, MAXW]),
        "one_key": _case([42], 0, [41, 42, 43, 0, MAXW]),
        "empty_build": _case([], 0, [1, 2, MAXW]),
        "all_unusable": _case([], 64, [0, 1, MAXW]),
        "probes_below_and_above": _case(
            np.arange(1000, 2000), 24,
            np.concatenate([np.arange(0, 1000, 10),
                            np.arange(2000, 3000, 10)])),
    }


CASES = _cases()
# trips the bracketed search must take: 1 where every bucket holds one
# row, today's depth where one far key squeezes the rest into a bucket
STEPS = {"dense_unique": 1, "dense_masked_tail": 1, "all_unusable": 0,
         "empty_build": 0, "one_outlier": math.ceil(math.log2(4096))}


def _pair(sb, n, q):
    """What hash_join computed before: both sides, clamped."""
    return (np.minimum(np.searchsorted(sb, q, side="left"), n),
            np.minimum(np.searchsorted(sb, q, side="right"), n))


def _run(how, sb, n, q, mesh):
    sb, n = jnp.asarray(sb), jnp.asarray(n, dtype=jnp.int32)
    if how == "jit":
        start, end, steps = jax.jit(_match_ranges)(sb, n, jnp.asarray(q))
    elif how == "vmap":  # batching.py: one program over literal sets
        start, end, steps = jax.jit(jax.vmap(
            _match_ranges, in_axes=(None, None, 0)))(
                sb, n, jnp.asarray(q.reshape(3, -1)))
    else:  # each worker looks its shard of the probe up, as in stages.py

        def step(sb, n, q):
            start, end, steps = _match_ranges(sb, n, q)
            return start, end, steps[None]

        f = jax.shard_map(step, mesh=mesh,
                          in_specs=(P(), P(), P(WORKERS_AXIS)),
                          out_specs=P(WORKERS_AXIS))
        start, end, steps = jax.jit(f)(sb, n, jnp.asarray(q))
    assert start.dtype == end.dtype == jnp.int32
    return (np.asarray(start).reshape(-1), np.asarray(end).reshape(-1),
            np.unique(np.asarray(steps)))


@pytest.mark.parametrize("how", ["jit", "vmap", "shard_map"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_match_ranges_equals_the_clamped_searchsorted_pair(case, how, mesh8):
    sb, n, q = CASES[case]
    start, end, steps = _run(how, sb, n, q, mesh8)
    want_start, want_end = _pair(sb, n, q)
    np.testing.assert_array_equal(start, want_start)
    np.testing.assert_array_equal(end, want_end)
    assert len(steps) == 1  # one build side: every set and shard agrees
    assert 0 <= steps[0] <= math.ceil(math.log2(max(len(sb), 2)))
    if case in STEPS:
        assert steps[0] == STEPS[case]


def test_one_key_one_query_under_jit():
    """The shape on which XLA:CPU folds the directory's cumsum to a
    wrong constant unless the build side is given a second row."""
    sb = jnp.asarray(np.array([42], np.uint64))
    start, end, steps = jax.jit(_match_ranges)(
        sb, jnp.asarray(1, dtype=jnp.int32), sb)
    assert (int(start[0]), int(end[0]), int(steps)) == (0, 1, 1)
