"""presto_tpu: a TPU-native distributed SQL query engine.

A from-scratch rebuild of the capabilities of prestodb/presto with a
JAX/XLA/Pallas execution core. The columnar operator pipeline
(reference: presto-main-base/.../operator/, presto-native-execution's
Velox path) executes as jit'd XLA programs over device-resident columnar
batches; inter-stage shuffles map onto `jax.lax.all_to_all` over an ICI
device mesh instead of HTTP page pull.

Package layout:
  types    -- SQL type system + signature parser
               (ref: presto-common/.../common/type/)
  block    -- device columnar Page/Block model
               (ref: presto-common/.../common/Page.java, common/block/)
  expr     -- RowExpression IR and its JAX lowering
               (ref: presto-spi/.../spi/relation/, sql/gen/ExpressionCompiler.java)
  ops      -- operator kernels: filter/project, aggregation, join, sort, ...
               (ref: presto-main-base/.../operator/)
  plan     -- plan node / fragment model
               (ref: presto-spi/.../spi/plan/)
  exec     -- local execution planner + task/driver execution
               (ref: sql/planner/LocalExecutionPlanner.java, operator/Driver.java)
  parallel -- device mesh, partitioned exchange via collectives
               (ref: operator/repartition/, operator/ExchangeClient.java)
  serde    -- SerializedPage wire format
               (ref: presto-spi/.../spi/page/PagesSerde.java)
  connectors.tpch -- deterministic columnar TPC-H generator
               (ref: presto-tpch/.../TpchRecordSetProvider.java)
"""

import jax as _jax

# SQL semantics are 64-bit: BIGINT arithmetic, DECIMAL-as-scaled-int64, and
# SUM accumulators must not truncate. JAX defaults to 32-bit; flip the
# switch before any array is created. (TPU executes s64 as emulated i32
# pairs -- hot kernels that can prove 32-bit ranges downcast explicitly.)
_jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"


def connect(**kwargs):
    """PEP-249 connection (presto_tpu.dbapi.connect)."""
    from . import dbapi
    return dbapi.connect(**kwargs)

