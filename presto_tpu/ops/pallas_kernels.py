"""Pallas TPU kernel for the hot op XLA alone stages through HBM: the
grouped-sum inner loop of the small-table aggregation.

The engine calls it only when `ops.device.on_tpu()` says the program
is being traced for the chip, and always compiled (`interpret=False`):
a kernel Mosaic refuses fails the query, it never falls back.
`interpret=True` exists for the CPU tests of the kernel's numerics and
nothing else. The kernel is independent of the x64 switch the package
turns on (32-bit index maps, 32-bit lanes inside), and
tests/test_tpu_compile.py compiles it for a described v5e at the
shapes TPC-H SF1 produces.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["limb_partial_sums"]

# BlockSpec index maps must return 32-bit values: under x64 a Python 0
# traces as i64 and Mosaic cannot legalize the map's func.return
_Z = np.int32(0)

_SUM_TILE = 1024


def _limb_sum_kernel(ids_ref, limbs_ref, out_ref, *, groups: int,
                     compute_dtype):
    """One row tile: build the one-hot(ids) in VMEM and ride the MXU
    for (G, L) partial sums -- the fused form of the XLA path's
    one_hot-materialize + einsum (which stages an (n, G) one-hot
    through HBM). ids arrive lane-major (1, TILE), so the one-hot is
    built already transposed, (G, TILE), and the dot needs no layout change.
    Each tile's f32 sums stay < 2^24 (exact); tiles combine in int64
    OUTSIDE the kernel, identical numerics to
    aggregation._fused_limb_sums' einsum form.

    compute_dtype=bfloat16 (narrow-width execution): one MXU pass --
    exact because one-hot entries are 0/1 and 8-bit limbs (|v| <= 255,
    every integer representable in bf16's 8-bit mantissa) accumulate in
    f32. compute_dtype=float32 keeps the wide form, where
    precision=HIGHEST is required: default-precision f32 dot lowers to
    bf16 passes on TPU, which cannot hold 13-bit limbs exactly."""
    ids = ids_ref[0]
    gidx = jax.lax.broadcasted_iota(jnp.int32, (groups, ids.shape[1]), 0)
    onehot_t = (ids == gidx).astype(compute_dtype)
    limbs = limbs_ref[:].astype(compute_dtype)
    precision = (None if compute_dtype == jnp.bfloat16
                 else jax.lax.Precision.HIGHEST)
    out_ref[0] = jnp.dot(onehot_t, limbs, precision=precision,
                         preferred_element_type=jnp.float32)


def limb_partial_sums(ids: jax.Array, limbs: jax.Array, groups: int,
                      *, interpret: bool,
                      compute_dtype=jnp.float32) -> jax.Array:
    """(tiles, G, L) f32 per-tile partial sums of `limbs` grouped by
    `ids` (int32; out-of-range ids contribute nothing). Rows pad to the
    tile size with ids == groups (dropped by the one-hot compare).
    `limbs` may arrive at any integer/float lane dtype whose values the
    MXU operand dtype holds exactly (int16 8-bit limbs for the bf16
    narrow form, f32 13-bit limbs for the wide form)."""
    n, L = limbs.shape
    pad = (-n) % _SUM_TILE
    if pad:
        ids = jnp.pad(ids, (0, pad), constant_values=groups)
        limbs = jnp.pad(limbs, ((0, pad), (0, 0)))
    tiles = ids.shape[0] // _SUM_TILE
    kernel = functools.partial(_limb_sum_kernel, groups=groups,
                               compute_dtype=compute_dtype)
    if limbs.dtype not in (jnp.int16, jnp.bfloat16):
        limbs = limbs.astype(jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=(tiles,),
        in_specs=[pl.BlockSpec((1, 1, _SUM_TILE), lambda i: (i, _Z, _Z)),
                  pl.BlockSpec((_SUM_TILE, L), lambda i: (i, _Z))],
        out_specs=pl.BlockSpec((1, groups, L), lambda i: (i, _Z, _Z)),
        out_shape=jax.ShapeDtypeStruct((tiles, groups, L), jnp.float32),
        interpret=interpret,
        name="limb_partial_sums",
    )(ids.astype(jnp.int32).reshape(tiles, 1, _SUM_TILE), limbs)
