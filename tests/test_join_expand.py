"""`ops/join._slot_rows`, the prefix-sum expansion's map from an output
slot to the row that emits it, against the `searchsorted` it replaced;
`hash_join` and `unnest` on top of it; and what `hash_join` lowers to."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from presto_tpu import types as T
from presto_tpu.block import batch_from_numpy
from presto_tpu.ops import join
from presto_tpu.ops.join import _slot_block, _slot_rows, hash_join
from presto_tpu.parallel import WORKERS_AXIS

TABLES = 8  # offset tables a case: one under jit, three sets, eight shards


def _offsets(emit, first=0):
    emit = np.asarray(emit, dtype=np.int64)
    return first + np.cumsum(emit) - emit


def _cases():
    """name -> (slots Q, TABLES offset tables of one length, int64)."""
    rng = np.random.default_rng(29)

    def tables(n, slots, draw):
        return slots, np.stack([draw(n) for _ in range(TABLES)])

    def sparse(share, fan=1):
        return lambda n: _offsets((rng.random(n) < share)
                                  * rng.integers(1, fan + 1, n))

    def exactly(total):
        def draw(n):
            emit = np.zeros(n, np.int64)
            np.add.at(emit, rng.integers(0, n, total), 1)
            return _offsets(emit)
        return draw

    def wide(n):  # offsets past 2**31 before the clip to the slots
        emit = rng.integers(0, 3, n)
        emit[rng.integers(n // 2, n, 4)] = 2 ** 31
        return _offsets(emit)

    return {
        # Q14's shape: 98.8% of the probe rows emit nothing (blocks of 8)
        "long_zero_emit_runs": tables(5001, 1024, sparse(0.012)),
        "fan_out_1_to_7": tables(3000, 16384, sparse(0.9, 7)),
        # a table no longer than the output is its own directory
        "as_many_rows_as_slots": tables(512, 512, sparse(0.5, 3)),
        "one_row": tables(1, 8, lambda n: _offsets([rng.integers(0, 5)])),
        # the table is one block: the short search alone
        "one_slot": tables(700, 1, sparse(0.001)),
        "two_slots_two_blocks": tables(300, 2, sparse(0.004)),
        "ragged_last_block": tables(1000, 300, sparse(0.5)),
        "blocks_of_sixteen": tables(6000, 400, sparse(0.05)),
        "slots_below_the_total": tables(2000, 512, sparse(0.8, 4)),
        "slots_equal_the_total": tables(2000, 777, exactly(777)),
        "slots_above_the_total": tables(2000, 4096, exactly(777)),
        "total_zero": tables(1500, 256, lambda n: _offsets(np.zeros(n))),
        "offsets_past_2_31": tables(2400, 1024, wide),
        # not hash_join's (its off[0] is 0): slots before the first row
        "first_offset_above_zero": tables(
            600, 512, lambda n: _offsets(rng.integers(0, 3, n), first=5)),
        "every_offset_above_the_slots": tables(
            600, 64, lambda n: _offsets(rng.integers(0, 3, n), first=100)),
    }


CASES = _cases()


def _want(off, slots):
    return np.searchsorted(off, np.arange(slots), side="right") - 1


def _run(how, offs, slots, mesh):
    def rows(off):
        row, j, _ = _slot_rows(off, slots)
        return row, j

    if how == "jit":
        offs = offs[:1]
        row, j = jax.jit(rows)(jnp.asarray(offs[0]))
    elif how == "vmap":  # batching.py: one program over literal sets
        offs = offs[:3]
        row, j = jax.jit(jax.vmap(rows))(jnp.asarray(offs))
    else:  # each worker expands its own shard's matches, as in stages.py
        f = jax.shard_map(rows, mesh=mesh, in_specs=P(WORKERS_AXIS),
                          out_specs=P(WORKERS_AXIS))
        row, j = jax.jit(f)(jnp.asarray(offs.reshape(-1)))
    assert row.dtype == j.dtype == jnp.int32
    return (offs, np.asarray(row).reshape(len(offs), slots),
            np.asarray(j).reshape(len(offs), slots))


@pytest.mark.parametrize("how", ["jit", "vmap", "shard_map"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_slot_rows_equals_the_searchsorted(case, how, mesh8):
    slots, offs = CASES[case]
    offs, rows, js = _run(how, offs, slots, mesh8)
    for off, row, j in zip(offs, rows, js):
        want = _want(off, slots)
        np.testing.assert_array_equal(row, want)
        at = want >= 0  # a slot before the first row has no place in one
        np.testing.assert_array_equal(
            j[at], (np.arange(slots) - off[want])[at])


@pytest.mark.parametrize("n,slots,block,trips", [
    # the benchmark's joins at SF10 and SF1: Q3's two, Q14's
    (60_000_000, 4_194_304, 16, 4), (4_194_304, 4_194_304, 1, 0),
    (60_000_000, 1_048_576, 64, 6), (6_000_000, 262_144, 32, 5),
    (3000, 1000, 4, 2), (1001, 1000, 2, 1), (1000, 1000, 1, 0),
    (5, 4096, 1, 0),
    # the table is one block (n + 1 answers): one row, or under 3 slots
    (1, 64, 1, 1), (700, 1, 1024, 10), (255, 2, 128, 7)])
def test_trips_follow_rows_a_slot_not_the_table(n, slots, block, trips):
    """Blocks of about n / slots rows: the histogram never takes more
    updates than there are slots, a slot log2(n / slots) trips."""
    assert _slot_block(n, slots) == block
    assert -(-n // block) <= slots
    if n <= 1 << 20:
        _, _, got = _slot_rows(jnp.arange(n, dtype=jnp.int64), slots)
        assert got == trips


def test_no_table_no_slots():
    row, j, trips = _slot_rows(jnp.zeros(0, dtype=jnp.int64), 4)
    assert (row.tolist(), trips) == ([-1] * 4, 0)
    row, j, trips = _slot_rows(jnp.arange(5, dtype=jnp.int64), 0)
    assert (row.shape, j.shape, trips) == ((0,), (0,), 0)


# -- hash_join on top of it -------------------------------------------------

def _searched(off, slots):
    """The map as hash_join held it until PR 29."""
    k = jnp.arange(slots, dtype=jnp.int64)
    row = jnp.searchsorted(off, k, side="right") - 1
    j = k - off[jnp.maximum(row, 0)]
    return row.astype(jnp.int32), j.astype(jnp.int32), 0


def _sides(keys):
    """(probe, build): 1,500 probe rows against 700 build rows, both
    with NULL keys and padding behind the live rows."""
    rng = np.random.default_rng(3)
    if keys == "n_to_1":    # a fact table's rows find their dimension row
        pk, bk = rng.integers(0, 900, 1500), rng.permutation(1000)[:700]
    elif keys == "1_to_n":  # a dimension row finds its facts: fan-out
        pk, bk = rng.permutation(2000)[:1500], rng.integers(0, 260, 700)
    else:                   # n to m
        pk, bk = rng.integers(0, 120, 1500), rng.integers(0, 160, 700)
    probe = batch_from_numpy(
        [T.INTEGER, T.INTEGER], [pk.astype(np.int32),
                                 np.arange(1500, dtype=np.int32)],
        nulls=[rng.random(1500) < 0.05, None], capacity=1536)
    build = batch_from_numpy(
        [T.INTEGER, T.INTEGER], [bk.astype(np.int32),
                                 np.arange(700, dtype=np.int32)],
        nulls=[rng.random(700) < 0.05, None], capacity=768)
    return probe, build


@pytest.mark.parametrize("capacity", [16384, 1024], ids=["fits", "overflows"])
@pytest.mark.parametrize("join_type", ["inner", "left", "right", "full"])
@pytest.mark.parametrize("keys", ["n_to_1", "1_to_n", "n_to_m"])
def test_hash_join_is_row_for_row_what_the_search_gave(keys, join_type,
                                                       capacity, monkeypatch):
    probe, build = _sides(keys)

    def run():
        return jax.jit(lambda p, b: hash_join(
            p, b, [0], [0], capacity, join_type))(probe, build)

    got = run()
    monkeypatch.setattr(join, "_slot_rows", _searched)
    want = run()
    assert int(got.num_rows) == int(want.num_rows) > 0
    assert bool(got.overflow) == bool(want.overflow) \
        == (int(want.num_rows) > capacity)
    # region 1 over the probe's 1,536 rows, region 2 over the build's 768
    assert got.expand_steps == (capacity == 1024)
    # every slot, live or not: the same rows were gathered
    for g, w in zip(jax.tree_util.tree_leaves(got.batch),
                    jax.tree_util.tree_leaves(want.batch)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("join_type", ["inner", "full"])
def test_hash_join_lowers_to_narrow_gathers_and_short_loops(join_type):
    """With 32-bit columns on both sides, no gather indexed by an
    out_capacity-long array reads a 64-bit table, and no loop runs
    ceil(log2 probe rows) trips: a slot finds its row in log2(block)."""
    rng = np.random.default_rng(5)
    npr, nb, capacity = 50_000, 300, 3000
    probe = batch_from_numpy([T.INTEGER], [rng.integers(0, 400, npr)
                                           .astype(np.int32)], capacity=npr)
    build = batch_from_numpy([T.INTEGER], [rng.integers(0, 400, nb)
                                           .astype(np.int32)], capacity=nb)
    jaxpr = jax.make_jaxpr(lambda p, b: hash_join(
        p, b, [0], [0], capacity, join_type).batch)(probe, build)
    wide, loops = [], []
    for eqn in _eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "gather":
            table, indices = (v.aval for v in eqn.invars[:2])
            if indices.shape[0] == capacity and table.dtype.itemsize == 8:
                wide.append(str(eqn))
        if eqn.primitive.name == "scan":
            loops.append(eqn.params["length"])
    assert wide == []
    searches = {npr.bit_length(), (npr - 1).bit_length(),
                nb.bit_length(), (nb - 1).bit_length()}
    assert not searches & set(loops)
    # blocks of 32 probe rows, the build's of 1: once in each form of the
    # probe side (the expansion's, or the compaction's before it)
    assert loops.count(5) == 2


def test_unnest_takes_the_same_map():
    """700 rows (a directory of three blocks) of 0-4 elements, empty
    and NULL arrays between them: every element in row order, under jit."""
    from presto_tpu.block import Batch, from_numpy, to_numpy
    from presto_tpu.ops.unnest import unnest
    rng = np.random.default_rng(7)
    arrays = [None if rng.random() < 0.1 else
              [int(v) for v in rng.integers(0, 99, rng.integers(0, 5))]
              for _ in range(700)]
    b = Batch((from_numpy(T.BIGINT, np.arange(700, dtype=np.int64)),
               from_numpy(T.array_of(T.BIGINT),
                          np.array(arrays + [[0]], dtype=object)[:-1])),
              jnp.ones(700, dtype=bool))
    want = [(i, v, o + 1) for i, a in enumerate(arrays)
            for o, v in enumerate(a or [])]
    out, overflow = jax.jit(lambda b: unnest(b, 1, 2048, True))(b)
    assert not bool(overflow)
    live = np.nonzero(np.asarray(out.active))[0]
    cols = [to_numpy(out.column(c))[0] for c in range(3)]
    assert out.column(2).values.dtype == jnp.int64  # ordinality: BIGINT
    assert [tuple(int(c[i]) for c in cols) for i in live] == want
    assert bool(jax.jit(lambda b: unnest(b, 1, len(want) - 1))(b)[1])
