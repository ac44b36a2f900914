def test_limb_partial_sums_matches_oracle_and_einsum_form(monkeypatch):
    """The fused Pallas group-sum partials (interpret mode off-TPU)
    must equal both a numpy oracle and the XLA einsum form's totals."""
    import numpy as np
    import jax.numpy as jnp
    from presto_tpu.ops.pallas_kernels import limb_partial_sums
    from presto_tpu.ops.aggregation import _limb_matmul_sum
    from presto_tpu.int128 import limbs13_of_i64

    rng = np.random.default_rng(3)
    n, G = 5000, 16
    ids = rng.integers(0, G, n).astype(np.int32)
    vals = rng.integers(-10**12, 10**12, n).astype(np.int64)

    # oracle through the kernel's own limb decomposition
    limbs = jnp.stack([l.astype(jnp.float32)
                       for l in limbs13_of_i64(jnp.asarray(vals), 5)],
                      axis=1)
    parts = limb_partial_sums(jnp.asarray(ids), limbs, G, interpret=True)
    tot = np.asarray(parts).astype(np.int64).sum(axis=0)
    scale = (1 << (13 * np.arange(5, dtype=np.int64)))
    got = (tot * scale[None, :]).sum(axis=1)

    want = np.zeros(G, np.int64)
    for i in range(n):
        want[ids[i]] += vals[i]
    assert (got == want).all()

    # and the einsum form agrees bit-for-bit (pin the XLA form even on
    # a TPU host, where the default would dispatch back to Pallas)
    monkeypatch.setenv("PRESTO_TPU_SMALLG_PALLAS", "0")
    einsum = np.asarray(_limb_matmul_sum(jnp.asarray(ids),
                                         jnp.asarray(vals), G))
    assert (einsum == want).all()


def test_limb_partial_sums_padding_and_oob_ids_drop():
    import numpy as np
    import jax.numpy as jnp
    from presto_tpu.ops.pallas_kernels import limb_partial_sums

    # rows with ids == groups (the padding sentinel / masked rows)
    # contribute nothing; non-tile-multiple n pads internally
    ids = jnp.asarray(np.array([0, 1, 2, 3, 16, 16, 2], np.int32))
    limbs = jnp.ones((7, 3), jnp.float32)
    parts = limb_partial_sums(ids, limbs, 16, interpret=True)
    tot = np.asarray(parts).sum(axis=0)
    assert tot[0, 0] == 1 and tot[2, 0] == 2
    assert tot.sum() == 5 * 3  # the two id-16 rows dropped
