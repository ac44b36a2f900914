"""`ops/join._match_ranges`, the lookup that turns a probe key into its
match range, against the pair of `searchsorted` calls it replaced: in
its direct form (the directory answers: the keys' span fits it) and in
its searched form (a bracketed search inside the directory's bucket)."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from presto_tpu.ops.join import _directory_bits, _lookup, _match_ranges
from presto_tpu.parallel import WORKERS_AXIS

MAXW = np.uint64(0xFFFFFFFFFFFFFFFF)
NQ = 240  # queries a case: three sets of 80, eight shards of 30
D256 = 1 << _directory_bits(256, 1)  # the directory of 129-256 rows


def _case(keys, tail, queries, spread=1):
    """(sorted build words with `tail` MAX-masked unusable rows behind
    them, n_usable, NQ queries: the given ones, then build keys, the
    directory's spread)."""
    keys = np.sort(np.asarray(keys, dtype=np.uint64))
    sb = np.concatenate([keys, np.full(tail, MAXW, np.uint64)])
    q = np.asarray(queries, dtype=np.uint64)[:NQ]
    fill = sb if len(sb) else np.zeros(1, np.uint64)
    q = np.concatenate([q, np.resize(fill, NQ - len(q))])
    return sb, len(keys), q, spread


def _span(values, rng):
    """200 keys (both ends among them, repeats too) whose span holds
    `values` key values from 10 on, 56 unusable rows behind: 256 rows,
    so the directory has D256 buckets; queries around and inside."""
    keys = np.concatenate([[10, 10 + values - 1],
                           rng.integers(10, 10 + values, 198)])
    return _case(keys, 56, rng.integers(0, 20 + values, 200))


def _mix(k):
    """splitmix64's finalizer: which of four chips a key hashes to."""
    k = (k ^ (k >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    k = (k ^ (k >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return k ^ (k >> np.uint64(31))


def _quarter():
    """A chip's orders after a hash exchange over four: the keys of
    1..16,384 that hash to it, half of them usable, in a 4,096-row
    build: the span is four times the 1x directory's."""
    keys = np.arange(1, 16_385, dtype=np.uint64)
    keys = keys[_mix(keys) % np.uint64(4) == 0]
    usable = np.random.default_rng(4).random(len(keys)) < 0.5
    return keys[usable], 4096 - int(usable.sum()), np.repeat(keys, 4)


def _cases():
    rng = np.random.default_rng(27)
    dense = np.arange(1, 4097)
    sparse = [(i // 8) * 32 + i % 8 + 1 for i in range(4000)]  # TPC-H's
    quarter = _quarter()
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
        "two_keys_gap": _case([3, 7], 0, np.arange(0, 12)),
        "two_keys_adjacent": _case([3, 4], 0, np.arange(0, 8)),
        "two_equal_keys": _case([9, 9], 0, [8, 9, 10, 0, MAXW]),
        "empty_build": _case([], 0, [1, 2, MAXW]),
        "all_unusable": _case([], 64, [0, 1, MAXW]),
        "probes_below_and_above": _case(
            np.arange(1000, 2000), 24,
            np.concatenate([np.arange(0, 1000, 10),
                            np.arange(2000, 3000, 10)])),
        "span_d_minus_1": _span(D256 - 1, rng),
        "span_d": _span(D256, rng),
        "span_d_plus_1": _span(D256 + 1, rng),
        "hash_quarter_p1": _case(quarter[0], quarter[1], quarter[2]),
        "hash_quarter_p4": _case(quarter[0], quarter[1], quarter[2], 4),
    }


CASES = _cases()
# which form answers: the directory alone (its buckets are one key
# value each: the span fits them, and start << k | run fits 31 bits),
# or the bracketed search, in the trips its fullest bucket needs
DIRECT = {"dense_unique", "dense_masked_tail", "heavy_duplicates",
          "one_key", "two_keys_adjacent", "two_equal_keys", "all_unusable",
          "probes_below_and_above", "span_d_minus_1", "span_d",
          "hash_quarter_p4"}
# trips the lookup takes: 0 where the directory answers, today's depth
# where one far key squeezes the rest into a bucket
STEPS = {"dense_unique": 0, "dense_masked_tail": 0, "all_unusable": 0,
         "empty_build": 0, "heavy_duplicates": 0, "span_d": 0,
         "hash_quarter_p4": 0, "two_keys_gap": 1, "hash_quarter_p1": 3,
         "one_outlier": math.ceil(math.log2(4096))}


def _pair(sb, n, q):
    """What hash_join computed before: both sides, clamped."""
    return (np.minimum(np.searchsorted(sb, q, side="left"), n),
            np.minimum(np.searchsorted(sb, q, side="right"), n))


def _run(how, fn, build, q, mesh, check_vma=True):
    """fn(*build, queries) -> (start, end, steps, direct) under `how`;
    returns the two ranges flat and the distinct steps and directs."""
    if how == "jit":
        start, end, steps, direct = jax.jit(fn)(*build, q)
    elif how == "vmap":  # batching.py: one program over literal sets
        start, end, steps, direct = jax.jit(jax.vmap(
            fn, in_axes=(None,) * len(build) + (0,)))(
                *build, jax.tree.map(lambda w: w.reshape(3, -1), q))
    else:  # each worker looks its shard of the probe up, as in stages.py

        def step(*args):
            start, end, steps, direct = fn(*args)
            return start, end, steps[None], direct[None]

        f = jax.shard_map(step, mesh=mesh,
                          in_specs=(P(),) * len(build) + (P(WORKERS_AXIS),),
                          out_specs=P(WORKERS_AXIS), check_vma=check_vma)
        start, end, steps, direct = jax.jit(f)(*build, q)
    assert start.dtype == end.dtype == jnp.int32
    return (np.asarray(start).reshape(-1), np.asarray(end).reshape(-1),
            np.unique(np.asarray(steps)), np.unique(np.asarray(direct)))


@pytest.mark.parametrize("how", ["jit", "vmap", "shard_map"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_match_ranges_equals_the_clamped_searchsorted_pair(case, how, mesh8):
    sb, n, q, spread = CASES[case]
    start, end, steps, direct = _run(
        how, functools.partial(_match_ranges, spread=spread),
        (jnp.asarray(sb), jnp.asarray(n, dtype=jnp.int32)), jnp.asarray(q),
        mesh8)
    want_start, want_end = _pair(sb, n, q)
    np.testing.assert_array_equal(start, want_start)
    np.testing.assert_array_equal(end, want_end)
    # one build side: every set and shard agrees on the form
    assert len(steps) == 1 and len(direct) == 1
    assert 0 <= steps[0] <= math.ceil(math.log2(max(len(sb), 2)))
    assert bool(direct[0]) == (case in DIRECT)
    if direct[0]:
        assert steps[0] == 0
    if case in STEPS:
        assert steps[0] == STEPS[case]


def _words(pairs):
    return [np.asarray([p[0] for p in pairs], np.uint64),
            np.asarray([p[1] for p in pairs], np.uint64)]


def _multi_word_cases():
    """Two-word keys (a, b), compared as tuples: dense ranks through
    `_pack_ranks`, then the one lookup."""
    rng = np.random.default_rng(31)

    def case(build, tail, queries):
        build = sorted(build)
        queries = list(queries)[:NQ]
        queries += [build[i % len(build)] for i in range(NQ - len(queries))]
        sb = _words(build + [(MAXW, MAXW)] * tail)
        return sb, len(build), _words(queries)

    pairs = [(int(a), int(b)) for a, b in rng.integers(0, 40, (300, 2))]
    probes = [(int(a), int(b)) for a, b in rng.integers(0, 44, (200, 2))]
    return {
        "repeating_pairs": case(pairs, 20, probes),
        "unique_pairs": case(sorted(set(pairs)), 0, probes),
        "one_pair": case([(3, 4)], 3, [(3, 4), (3, 5), (2, 9), (4, 0)]),
    }


MULTI = _multi_word_cases()


@pytest.mark.parametrize("how", ["jit", "vmap", "shard_map"])
@pytest.mark.parametrize("case", sorted(MULTI))
def test_multi_word_keys_equal_the_tuple_pair(case, how, mesh8):
    sb, n, q = MULTI[case]
    usable = np.arange(len(sb[0])) < n

    def fn(sb, usable, q):
        return _lookup(sb, usable, q)

    # `lex_sort`'s scan carry is not typed as varying: unchecked, as the
    # planner's meshed programs run (`compile_plan`: check_vma=False)
    start, end, steps, direct = _run(
        how, fn, ([jnp.asarray(w) for w in sb], jnp.asarray(usable)),
        [jnp.asarray(w) for w in q], mesh8, check_vma=False)
    build = list(zip(*(w[:n].tolist() for w in sb)))
    queries = list(zip(*(w.tolist() for w in q)))
    want_start = [sum(b < k for b in build) for k in queries]
    want_end = [sum(b <= k for b in build) for k in queries]
    np.testing.assert_array_equal(start, want_start)
    np.testing.assert_array_equal(end, want_end)
    assert len(steps) == 1 and (steps[0] == 0) == bool(direct[0])


def test_one_key_one_query_under_jit():
    """The shape on which XLA:CPU folds the directory's cumsum to a
    wrong constant unless the build side is given a second row."""
    sb = jnp.asarray(np.array([42], np.uint64))
    start, end, steps, direct = jax.jit(_match_ranges)(
        sb, jnp.asarray(1, dtype=jnp.int32), sb)
    assert (int(start[0]), int(end[0]), int(steps), bool(direct)) \
        == (0, 1, 0, True)


def test_runs_too_long_for_the_packed_lane_take_the_search():
    """start << k | run length must fit 31 bits: 2**20 usable rows of
    one key need k = 21 (21 + 21 > 31), so the search answers, exactly,
    though the span (0) fits the directory."""
    n = 1 << 20
    sb = jnp.full(n, 7, dtype=jnp.uint64)
    q = jnp.asarray(np.array([6, 7, 8], np.uint64))
    start, end, steps, direct = jax.jit(_match_ranges)(
        sb, jnp.asarray(n, dtype=jnp.int32), q)
    assert not bool(direct) and int(steps) == 21
    assert np.asarray(start).tolist() == [0, 0, n]
    assert np.asarray(end).tolist() == [0, n, n]
