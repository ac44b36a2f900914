"""`hash_join` looks up only the probe rows that can emit a slot, where
they fit the output capacity (`ops/join._probe_side`): the compacted
form against the same call with the form ruled out by shape, row for
row and slot for slot; the rule as a table of shapes; and what the
compacted branch lowers to."""

import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from presto_tpu import types as T
from presto_tpu.block import batch_from_numpy
from presto_tpu.ops import join
from presto_tpu.ops.join import _compact_capacity, _compact_probe, \
    _running_sum, _slot_trips, hash_join
from presto_tpu.parallel import WORKERS_AXIS
from presto_tpu.parallel.mesh import make_mesh

NPR, NB, CAPACITY = 2048, 96, 256  # a probe eight times its output
PROBES = 8  # probes a case, one a shard of `mesh8`

JOIN_TYPES = ["inner", "left", "right", "full"]
KEYS = ["dense", "duplicate", "two_word"]
# what the probe holds: (rows that can emit a slot under an inner join,
# active rows with a NULL key beside them, duplicates of a build key)
FILLS = {
    "emitting_rows_fit": 200,
    "emitting_rows_fill_the_capacity": CAPACITY,
    "emitting_rows_exceed_by_one": CAPACITY + 1,
    "none_emit": 0,
    "all_emit": NPR,
    "null_keys_beside_them": 120,
    "output_overflows": 150,
}


def _build(keys, rng):
    """96 build rows, NULL keys and padding among them: distinct keys,
    keys that repeat (a probe row then fans out), or (bigint, varchar)
    pairs, which go through `_pack_ranks`."""
    if keys == "duplicate":
        bk = rng.integers(0, 30, NB)
    else:
        bk = rng.permutation(200)[:NB]
    cols = [(T.BIGINT, bk.astype(np.int64))]
    if keys == "two_word":
        cols.append((T.varchar(3), np.array([f"k{v % 7}" for v in bk],
                                            dtype=object)))
    cols.append((T.INTEGER, np.arange(NB, dtype=np.int32)))
    nulls = [rng.random(NB) < 0.05] + [None] * (len(cols) - 1)
    return batch_from_numpy([t for t, _ in cols], [v for _, v in cols],
                            nulls=nulls, capacity=NB + 32), bk


def _probe(keys, fill, bk, rng):
    """2,048 probe rows of which FILLS[fill] are active with a key (most
    of them a build key): the others inactive (a filter dropped them),
    or, in one case, active with a NULL key."""
    emitting = FILLS[fill]
    if fill == "output_overflows" and keys != "duplicate":
        # a row of a unique build side emits one slot a probe row: what
        # overflows the output does not fit the compacted probe either
        emitting = CAPACITY + 200
    pk = np.where(rng.random(NPR) < 0.9, rng.choice(bk, NPR),
                  rng.integers(200, 300, NPR))
    if fill == "output_overflows" and keys == "duplicate":
        pk[:] = np.bincount(bk).argmax()  # every row fans out
    rows = rng.permutation(NPR)
    active = np.zeros(NPR, dtype=bool)
    active[rows[:emitting]] = True
    null_key = np.zeros(NPR, dtype=bool)
    if fill in ("null_keys_beside_them", "output_overflows"):
        more = rows[emitting:emitting + 60]
        active[more] = True
        null_key[more] = True
    cols = [(T.BIGINT, pk.astype(np.int64))]
    if keys == "two_word":
        cols.append((T.varchar(3), np.array([f"k{v % 7}" for v in pk],
                                            dtype=object)))
    cols.append((T.INTEGER, np.arange(NPR, dtype=np.int32)))
    b = batch_from_numpy([t for t, _ in cols], [v for _, v in cols],
                         nulls=[null_key] + [None] * (len(cols) - 1),
                         capacity=NPR)
    return b.with_active(jnp.asarray(active))


def _case(keys, fill):
    rng = np.random.default_rng(31)
    build, bk = _build(keys, rng)
    probes = [_probe(keys, fill, bk, rng) for _ in range(PROBES)]
    return probes, build


@functools.lru_cache(maxsize=None)
def _program(how, join_type, keys, compact):
    """The join of each probe of a case with its build side, as one
    jitted program a (transform, join type, key kind): the probe's fill
    is data, so the seven fills share it. `compact` False is the same
    call with the second form ruled out, as by shape."""
    channels = list(range(2 if keys == "two_word" else 1))
    capacity_rule = _compact_capacity if compact else lambda npr, cap: 0

    def fn(probe, build):
        with mock.patch.object(join, "_compact_capacity", capacity_rule):
            r = hash_join(probe, build, channels, channels, CAPACITY,
                          join_type)
        return r.batch, r.num_rows, r.overflow, r.search_steps, r.compacted

    if how == "jit":
        return jax.jit(fn)
    if how == "vmap":  # batching.py: one program over literal sets
        return jax.jit(jax.vmap(fn, in_axes=(0, None)))

    def shard(probe, build):
        return jax.tree_util.tree_map(lambda x: x[None], fn(probe, build))

    # each worker joins its own shard of the probe, as in stages.py
    return jax.jit(jax.shard_map(shard, mesh=make_mesh(8),
                                 in_specs=(P(WORKERS_AXIS), P()),
                                 out_specs=P(WORKERS_AXIS), check_vma=False))


def _run(how, join_type, keys, compact, probes, build):
    """A list of results, one a probe: one under jit, three under vmap,
    eight shards."""
    fn = _program(how, join_type, keys, compact)
    if how == "jit":
        return [fn(probes[0], build)]
    if how == "vmap":
        count, join_probes = 3, jnp.stack
    else:
        count, join_probes = PROBES, jnp.concatenate
    out = fn(jax.tree_util.tree_map(lambda *xs: join_probes(xs),
                                    *probes[:count]), build)
    out = jax.tree_util.tree_map(np.asarray, out)  # one read a leaf
    return [jax.tree_util.tree_map(lambda x, i=i: x[i], out)
            for i in range(count)]


def _same(got, want):
    """Every slot, live or not: the same probe rows were gathered, the
    same slots are live and NULL; a live slot holds the same build row
    (behind the live slots the build side's lanes are NULL, whatever
    row the gather read)."""
    (gb, gn, go, gs, _), (wb, wn, wo, ws, _) = got, want
    assert int(gn) == int(wn) and bool(go) == bool(wo)
    np.testing.assert_array_equal(np.asarray(gb.active),
                                  np.asarray(wb.active))
    probe_columns = gb.num_columns // 2
    for i, (g, w) in enumerate(zip(gb.columns, wb.columns)):
        np.testing.assert_array_equal(np.asarray(g.nulls),
                                      np.asarray(w.nulls))
        at = slice(None) if i < probe_columns else ~np.asarray(w.nulls)
        for gl, wl in zip(jax.tree_util.tree_leaves(g),
                          jax.tree_util.tree_leaves(w)):
            np.testing.assert_array_equal(np.asarray(gl)[at],
                                          np.asarray(wl)[at])


@pytest.mark.parametrize("how", ["jit", "vmap", "shard_map"])
@pytest.mark.parametrize("fill", sorted(FILLS))
@pytest.mark.parametrize("keys", KEYS)
@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_compacted_join_is_slot_for_slot_the_full_one(join_type, keys, fill,
                                                     how):
    probes, build = _case(keys, fill)
    got = _run(how, join_type, keys, True, probes, build)
    want = _run(how, join_type, keys, False, probes, build)
    outer_probe = join_type in ("left", "full")
    for probe, g, w in zip(probes, got, want):
        _same(g, w)
        active = np.asarray(probe.active)
        null_key = np.asarray(probe.column(0).nulls)
        emitting = int((active if outer_probe else active & ~null_key).sum())
        assert int(g[4]) == (emitting <= CAPACITY), (emitting, fill)
        assert int(w[4]) == 0
        if keys != "two_word":  # the trips follow the build side alone
            assert int(g[3]) == int(w[3])
    totals = [int(w[1]) for w in want]
    if fill == "output_overflows":
        assert min(totals) > CAPACITY
    if fill == "none_emit" and join_type == "inner":
        assert max(totals) == 0


@pytest.mark.parametrize("npr,capacity,compacts", [
    # the benchmark's joins at SF10 and SF1: Q14's, Q3's two
    (60_000_000, 1_048_576, True), (60_000_000, 4_194_304, True),
    (4_194_304, 4_194_304, False),
    (6_000_000, 262_144, True), (262_144, 262_144, False),
    # the ladder's first rungs over them
    (60_000_000, 65_536, True), (6_000_000, 65_536, True),
    # sf 0.01 at the default capacity: the probe is no longer
    (60_000, 65_536, False),
    # the edge: four times the capacity, and one row short of it
    (4096, 1024, True), (4095, 1024, False), (2048, 256, True),
    (1000, 300, False), (8, 2, True), (0, 16, False), (16, 0, False)])
def test_the_rule_is_a_table_of_shapes(npr, capacity, compacts):
    """The compacted capacity is the output's where the probe is at
    least four times as long, and there is no second form where it is
    not; the trips a slot takes (`join_expand_steps`) are the same in
    both forms, moved from the expansion to the compaction."""
    assert _compact_capacity(npr, capacity) == (capacity if compacts else 0)
    if compacts and capacity > 1:
        assert _slot_trips(npr, capacity) + _slot_trips(capacity, capacity) \
            == _slot_trips(npr, capacity)


@pytest.mark.parametrize("n", [1, 5, 1023, 1024, 1025, 5000, 70_001])
def test_running_sum_is_cumsum(n):
    x = np.random.default_rng(n).integers(0, 9, n).astype(np.int32)
    got = jax.jit(_running_sum)(jnp.asarray(x))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.cumsum(x))


@pytest.mark.parametrize("share", [0.0, 0.01, 0.3, 1.0])
def test_compact_probe_names_the_emitting_rows_in_order(share):
    emits = np.random.default_rng(2).random(5000) < share
    n_emit = int(emits.sum())
    capacity = max(1 << max(n_emit - 1, 0).bit_length(), 64)
    crow = jax.jit(_compact_probe, static_argnums=1)(jnp.asarray(emits),
                                                     capacity)
    assert crow.dtype == jnp.int32 and crow.shape == (capacity,)
    np.testing.assert_array_equal(np.asarray(crow)[:n_emit],
                                  np.nonzero(emits)[0])
    assert (np.asarray(crow)[n_emit:] == 4999).all()


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("join_type", JOIN_TYPES)
def test_compacted_branch_gathers_by_the_capacity(join_type):
    """Both forms are branches of one `cond` on the device's count. In
    the compacted one no gather is indexed by an array as long as the
    probe: what is npr long there is the mask and its prefix sum.
    The reverse probe of a RIGHT / FULL join stays outside the `cond`,
    as it was."""
    rng = np.random.default_rng(5)
    npr, nb, capacity = 50_000, 300, 3000
    probe = batch_from_numpy([T.INTEGER], [rng.integers(0, 400, npr)
                                           .astype(np.int32)], capacity=npr)
    build = batch_from_numpy([T.INTEGER], [rng.integers(0, 400, nb)
                                           .astype(np.int32)], capacity=nb)
    jaxpr = jax.make_jaxpr(lambda p, b: hash_join(
        p, b, [0], [0], capacity, join_type).batch)(probe, build)
    # the probe side's `cond` (its seven values; the lookups' own, a
    # range's end, lie inside its branches
    # and, for the reverse probe, beside it)
    conds = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "cond"
             and len(e.outvars) == 7]
    assert len(conds) == 1
    full, compacted = conds[0].params["branches"]  # index 0 is False

    def long_gathers(branch):
        return [str(e) for e in _eqns(branch.jaxpr)
                if e.primitive.name == "gather"
                and e.invars[1].aval.shape[0] == npr]

    assert long_gathers(full) and not long_gathers(compacted)

    # the host reads nothing in between: the predicate is a device value
    assert conds[0].invars[0].aval.shape == ()


def test_ineligible_shape_compiles_one_form():
    """A probe no longer than four outputs (Q3's `JoinNode.6`): no
    second form and no `cond` between forms (the lookup's own, between
    the directory's answer and the search, returns a range's end), and
    the counter's share is a constant 0."""
    rng = np.random.default_rng(6)
    probe = batch_from_numpy([T.INTEGER], [rng.integers(0, 400, 3000)
                                           .astype(np.int32)], capacity=3000)
    build = batch_from_numpy([T.INTEGER], [rng.integers(0, 400, 300)
                                           .astype(np.int32)], capacity=300)

    def fn(p, b):
        r = hash_join(p, b, [0], [0], 1024, "inner")
        return r.batch, r.compacted

    jaxpr = jax.make_jaxpr(fn)(probe, build)
    assert [len(e.outvars) for e in _eqns(jaxpr.jaxpr)
            if e.primitive.name == "cond"] == [1]
    assert int(jax.jit(fn)(probe, build)[1]) == 0
