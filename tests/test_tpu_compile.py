"""The chip's compiler, without the chip: what the smoke runs must compile.

Section 2 of the on-chip-measurement guide: the TPU compiler installed
here compiles for a DESCRIBED v5e:2x2. These tests hand it the Pallas
kernel and the whole statement programs chip_smoke.py sends, at the
shapes TPC-H SF1 produces, with x64 on as in production and the backend
question (`ops.device.on_tpu`) steered to its TPU answer. A compile
that passes is not a chip run -- it says Mosaic and XLA:TPU accept the
program, nothing about results or time.

Everything that loads the TPU's library happens inside the module
fixture, in this process, after a test of this file has started:
xdist gives the file to one worker and only that worker takes
libtpu's lock.
"""

import os

import jax
import jax.numpy as jnp
import pytest

import presto_tpu  # noqa: F401  (x64 on before any array exists)
from presto_tpu.ops import device
from presto_tpu.ops import pallas_kernels as pk

SF = 1.0
LINEITEM_ROWS = 6_000_000  # this generator's SF1 lineitem (4 per order)


@pytest.fixture(scope="module")
def one_chip():
    """SingleDeviceSharding on device 0 of a described v5e:2x2, with
    the persistent compile cache off for the module: a compile for a
    described chip is written to it but cannot be read back without
    one, and the next run would warn."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever the plugin raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


@pytest.fixture
def traced_for_tpu(monkeypatch):
    """Trace-time branches read ops.device.on_tpu(), which says False
    here; steer it in the test, never through an option of the program."""
    monkeypatch.setattr(device, "on_tpu", lambda: True)


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# -- the Pallas kernels alone ---------------------------------------------

# (limb lane dtype, L, MXU operand dtype): what the SQL-text q1 hands
# limb_partial_sums at SF1 (test_q1_program_... re-derives and pins it)
Q1_LIMB_FORMS = {"bf16": (jnp.int16, 34, jnp.bfloat16),
                 "f32": (jnp.float32, 19, jnp.float32)}


@pytest.mark.parametrize("form", sorted(Q1_LIMB_FORMS))
def test_limb_partial_sums_compiles_at_q1_sf1_shapes(one_chip, form):
    lane, L, compute = Q1_LIMB_FORMS[form]
    fn = jax.jit(lambda i, l: pk.limb_partial_sums(
        i, l, 16, interpret=False, compute_dtype=compute))
    compiled = fn.lower(_shape((LINEITEM_ROWS,), jnp.int32, one_chip),
                        _shape((LINEITEM_ROWS, L), lane, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lex_sort_compiles_as_one_single_key_sort(one_chip):
    """The pass-per-word form exists to bound the compile: 7 key words
    over 64Ki rows as ONE multi-key sort did not compile in ten minutes
    (test_q3_join_program_compiles is the real-size guard; this one
    pins the form at a size where it is cheap)."""
    from presto_tpu.ops.keys import lex_sort
    rows = 4096
    fn = jax.jit(lambda *ops: lex_sort(ops, num_keys=7))
    text = fn.lower(*[_shape((rows,), jnp.uint64, one_chip)] * 7,
                    _shape((rows,), jnp.int32, one_chip)).compile().as_text()
    assert "while" in text  # the scan over key words


@pytest.mark.parametrize("npr,slots,nb,spread", [
    (60_000_000, 4_194_304, 15_000_000, 1),  # Q3's lineitem x orders
    (60_000_000, 1_048_576, 2_000_000, 1),   # Q14's lineitem x part
    # Q3 at the capacities its nodes need (the ladder fits a node from
    # its own count): lineitem x orders, and x customer, whose probe is
    # now four times its output and so holds both forms too
    (60_000_000, 2_097_152, 15_000_000, 1),
    (2_097_152, 524_288, 1_500_000, 1),
    # a chip's share of the mesh's Q3 at SF30: 56M probe rows after the
    # exchange against a 14M-row hash quarter of orders, the lookup's
    # directory four times wider (2**26 buckets)
    (56_000_000, 2_097_152, 14_000_000, 4),
], ids=["sf10-q3-join7", "sf10-q14-join4", "sf10-q3-join7-fitted",
        "sf10-q3-join6-fitted", "mesh4-sf30-q3-join12"])
def test_probe_side_compiles_with_both_forms_at_sf10_shapes(one_chip, npr,
                                                            slots, nb,
                                                            spread):
    """`hash_join`'s probe side holds its two forms in a `cond`, and
    XLA:TPU fails to place some 64-bit scans inside a `cond`'s branch
    ("vmem while allocating on stack", by the scan's length: a flat
    int64 cumsum over 4,194,304 rows failed here, over 1,048,576 it did
    not), which no CPU run shows: the forms keep their running sums in
    int32, and this holds them to it at the benchmark's SF10 shapes.
    Inside each form the lookup holds its own `cond` (the directory's
    answer or the search's run ends), and the mesh's directory of 2**26
    buckets is summed along rows (`_running_sum`): both compile here."""
    from presto_tpu import types as T
    from presto_tpu.block import Column
    from presto_tpu.ops import join
    capacity = join._compact_capacity(npr, slots)
    assert capacity == slots

    def fn(sorted_keys, b_usable, p_keys, p_active):
        key = Column(p_keys, jnp.zeros(p_keys.shape, dtype=bool), T.INTEGER)
        return join._probe_side([sorted_keys], b_usable, [key], p_active,
                                False, slots, capacity, spread)

    text = jax.jit(fn).lower(
        _shape((nb,), jnp.uint64, one_chip), _shape((nb,), jnp.bool_, one_chip),
        _shape((npr,), jnp.int32, one_chip),
        _shape((npr,), jnp.bool_, one_chip)).compile().as_text()
    assert text.count("conditional(") == 3  # the forms', a lookup's each


def test_a_meshed_programs_needs_cross_the_chips_in_32_bits(one_chip):
    """Under a mesh a counted node's need is the largest shard's: a
    `pmax` over the chips. XLA:TPU lowers a 64-bit all-reduce for sums
    alone ("Supported lowering only of Sum all reduce"), which no CPU
    mesh shows, so the needs cross in int32; this compiles a join under
    a group-by over the described 2x2 and holds them to it."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from presto_tpu import types as T
    from presto_tpu.block import Batch, Column
    from presto_tpu.exec.planner import compile_plan
    from presto_tpu.parallel.mesh import WORKERS_AXIS
    from presto_tpu.plan import nodes as N
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:4]), (WORKERS_AXIS,))
    rows = 4096
    left, right = (N.ValuesNode([T.BIGINT, T.BIGINT], [(0, 0)])
                   for _ in range(2))
    join = N.JoinNode(
        N.ExchangeNode(left, kind="REPARTITION", scope="REMOTE",
                       partition_channels=[0]),
        N.ExchangeNode(right, kind="REPARTITION", scope="REMOTE",
                       partition_channels=[0]),
        [0], [0], distribution="partitioned", out_capacity=2048)
    plan = compile_plan(N.AggregationNode(
        join, [0], [], step="PARTIAL", max_groups=1024), mesh)
    assert len(plan.counted) == 2
    shard = NamedSharding(mesh, PartitionSpec(WORKERS_AXIS))
    lane = _shape((rows,), jnp.int64, shard)
    mask = _shape((rows,), jnp.bool_, shard)
    batch = Batch((Column(lane, mask, T.BIGINT),
                   Column(lane, mask, T.BIGINT)), mask)
    out, status = jax.eval_shape(plan.fn, (batch, batch))
    assert status.shape == (2 + 2,) and status.dtype == jnp.int64
    text = jax.jit(plan.fn).lower((batch, batch)).compile().as_text()
    assert "all-to-all" in text


def test_a_lake_scans_row_groups_land_in_place_at_sf10_shapes(one_chip):
    """`block.BatchBuilder`'s three programs at `lake_sf10.scan`'s
    shapes (60M rows of Q6's narrowed lanes and their masks, a row
    group of 1,048,576 landing): the donated `dynamic_update_slice`
    writes into the lanes it was given -- every lane aliased, no
    scratch the size of one -- so landing 60 groups moves 60 groups'
    bytes and holds one copy of the lanes, and the closing pass, the
    only one that holds two, stays far inside the chip."""
    from presto_tpu import block
    rows, group = 60_000_000, 1 << 20
    dtypes = (jnp.int16, jnp.int32, jnp.int8, jnp.int16) + (jnp.bool_,) * 4
    lanes = tuple(_shape((rows + group,), dt, one_chip) for dt in dtypes)
    piece = tuple(_shape((group,), dt, one_chip) for dt in dtypes)
    at = _shape((), jnp.int64, one_chip)
    nbytes = sum((rows + group) * jnp.dtype(dt).itemsize for dt in dtypes)
    land = block._land_piece.lower(lanes, piece, at).compile()
    mem = land.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes  # padded to tiles
    assert mem.temp_size_in_bytes < 64 << 20
    close = block._close_lanes.lower(lanes, at, rows).compile()
    mem = close.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < 3_206_279_680  # the load's peak
    block._blank_lanes.lower(rows + group, tuple(
        jnp.dtype(dt) for dt in dtypes)).compile()


# -- whole statement programs, as the SQL front door builds them ----------

def _program(text, sharding):
    """(fn, abstract scan batches): the CompiledPlan.fn the front door
    builds for `text` at SF1 with the server's defaults (no hand hints),
    and its scan batches as shapes -- dtypes and string widths from a
    64-row staged slice, rows from the table's SF1 capacity."""
    from presto_tpu.connectors import catalog
    from presto_tpu.exec.planner import compile_plan
    from presto_tpu.exec.regions import partition_regions
    from presto_tpu.exec.runner import _scan_batch, prepare_plan
    from presto_tpu.sql import plan_sql
    root = prepare_plan(plan_sql(text), sf=SF)
    assert len(partition_regions(root, session=None, sf=SF,
                                 mesh=None).regions) == 1
    plan = compile_plan(root, None, 1 << 16)
    batches = []
    for node in plan.scan_nodes:
        rows = catalog(node.connector).table_row_count(node.table, SF)
        cap = -(-rows // 8) * 8
        tiny = _scan_batch(node, SF, None, 8, scan_range=(0, 64))
        batches.append(jax.tree.map(
            lambda x: _shape((cap,) + x.shape[1:], x.dtype, sharding), tiny))
    return plan.fn, tuple(batches)


def _compile(text, sharding):
    fn, batches = _program(text, sharding)
    compiled = jax.jit(lambda b: fn(b)).lower(batches).compile()
    mem = compiled.memory_analysis()
    print(f"args {mem.argument_size_in_bytes / 1e6:.1f} MB, "
          f"temp {mem.temp_size_in_bytes / 1e6:.1f} MB")
    # one program's arguments and scratch must fit the 16 GB chip
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    return compiled.as_text()


def test_q1_program_compiles_with_the_pallas_kernel(one_chip, traced_for_tpu,
                                                    monkeypatch):
    from presto_tpu.ops.aggregation import last_smallg_form
    from presto_tpu.queries.tpch_sql import tpch_query
    seen = []
    real = pk.limb_partial_sums

    def spy(ids, limbs, groups, **kw):
        seen.append((limbs.shape, limbs.dtype, groups, kw["interpret"],
                     kw["compute_dtype"]))
        return real(ids, limbs, groups, **kw)

    monkeypatch.setattr(pk, "limb_partial_sums", spy)
    text = _compile(tpch_query(1).text, one_chip)
    assert "tpu_custom_call" in text
    assert last_smallg_form() == "pallas-bf16"
    lane, L, compute = Q1_LIMB_FORMS["bf16"]
    assert seen == [((LINEITEM_ROWS, L), lane, 16, False, compute)]


def test_q6_program_compiles(one_chip, traced_for_tpu):
    from presto_tpu.queries.tpch_sql import tpch_query
    _compile(tpch_query(6).text, one_chip)


def test_q3_join_program_compiles(one_chip, traced_for_tpu):
    from presto_tpu.queries.tpch_sql import tpch_query
    _compile(tpch_query(3).text, one_chip)


# the string columns TPC-H q9/q13/q16/q20 search, with the patterns
# those queries' texts use (widths 55, 25, 79, 101 at SF1 row counts)
LIKE_STATEMENTS = {
    "part.name": "SELECT count(*) FROM part WHERE name LIKE '%sleep%'",
    "part.type": "SELECT count(*) FROM part WHERE type LIKE 'PROMO%'",
    "orders.comment": "SELECT count(*) FROM orders "
                      "WHERE comment NOT LIKE '%special%requests%'",
    "supplier.comment": "SELECT count(*) FROM supplier "
                        "WHERE comment LIKE '%carefully%deposits%'",
}


@pytest.mark.parametrize("column", sorted(LIKE_STATEMENTS))
def test_like_count_compiles_at_tpch_widths(one_chip, traced_for_tpu,
                                            column):
    _compile(LIKE_STATEMENTS[column], one_chip)
