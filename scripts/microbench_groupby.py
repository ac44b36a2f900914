"""Micro-benchmark group-by building blocks on the attached device.

Times each primitive with the two-window differencing harness bench.py
uses (real host fetch ends each window; differencing cancels the
fetch's fixed cost). Drives the choice of group-by kernel for the hot
path (HandTpchQuery1-style measurement discipline)."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

import presto_tpu  # noqa: F401  (x64 on, before any array is created)

N = int(os.environ.get("MB_ROWS", 6_000_000))
G = int(os.environ.get("MB_GROUPS", 16))
ITERS = int(os.environ.get("MB_ITERS", 5))


def timeit(name, fn, *args):
    fn_j = jax.jit(fn)
    jax.device_get(fn_j(*args))  # compile + round trip

    def window(k):
        t0 = time.time()
        out = None
        for _ in range(k):
            out = fn_j(*args)
        jax.device_get(out)
        return time.time() - t0

    t1 = window(ITERS)
    t2 = window(2 * ITERS)
    dt = (t2 - t1) / ITERS
    if dt <= 0:
        dt = t2 / (2 * ITERS)
    print(f"{name:42s} {dt*1e3:10.2f} ms")
    return dt


def main():
    rng = np.random.default_rng(0)
    ids_np = rng.integers(0, G, N).astype(np.int32)
    v_np = rng.integers(-(10**7), 10**7, N).astype(np.int64)
    w_np = rng.integers(0, 2**63, N, dtype=np.int64).astype(np.uint64)
    ids = jax.device_put(jnp.asarray(ids_np))
    v = jax.device_put(jnp.asarray(v_np))
    w = jax.device_put(jnp.asarray(w_np))
    active = jnp.ones(N, dtype=bool)

    print(f"platform={jax.devices()[0].platform} n={N} G={G}")

    timeit("scatter_add int64 (n->G)",
           lambda i, x: jnp.zeros(G, dtype=jnp.int64).at[i].add(x), ids, v)

    timeit("sort by int32 ids (2 operands)",
           lambda i: jax.lax.sort([i, jnp.arange(N, dtype=jnp.int32)],
                                  num_keys=1), ids)

    timeit("sort by 4 uint64 words",
           lambda a: jax.lax.sort([a, a ^ jnp.uint64(1), a ^ jnp.uint64(2),
                                   a ^ jnp.uint64(3),
                                   jnp.arange(N, dtype=jnp.int32)],
                                  num_keys=4), w)

    timeit("cumsum int64", lambda x: jnp.cumsum(x), v)

    def masked_reduce_loop(i, x):
        outs = [jnp.sum(jnp.where(i == g, x, 0)) for g in range(G)]
        return jnp.stack(outs)

    timeit("masked-reduce loop (G passes)", masked_reduce_loop, ids, v)

    def onehot_matmul_limb(i, x):
        KC = 2048
        C = -(-N // KC)
        pad = C * KC - N
        i = jnp.pad(i, (0, pad), constant_values=G)  # pad -> no group
        x = jnp.pad(x, (0, pad))
        # 13-bit limbs, top limb signed: exact in f32 per chunk
        limbs = []
        rem = x
        for _ in range(4):
            limbs.append((rem & 0x1FFF).astype(jnp.float32))
            rem = rem >> 13
        limbs.append(rem.astype(jnp.float32))  # signed top (52-13*4=12 bits used)
        lm = jnp.stack(limbs, axis=1).reshape(C, KC, 5)
        i = i.reshape(C, KC)
        oh = (i[:, :, None] ==
              jnp.arange(G, dtype=jnp.int32)).astype(jnp.float32)
        part = jnp.einsum('ckg,ckl->cgl', oh, lm,
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
        tot = jnp.sum(part.astype(jnp.int64), axis=0)  # (G, 5)
        scale = (jnp.int64(1) << (13 * jnp.arange(5, dtype=jnp.int64)))
        return jnp.sum(tot * scale[None, :], axis=1)

    r = jax.jit(onehot_matmul_limb)(ids, v)
    oracle = np.zeros(G, dtype=np.int64)
    np.add.at(oracle, ids_np, v_np)
    assert np.array_equal(np.asarray(r), oracle), (np.asarray(r), oracle)
    timeit("one-hot limb matmul (exact int64)", onehot_matmul_limb, ids, v)

    def seg_sum_via_sort(i, x):
        s = jax.lax.sort([i, x], num_keys=1)
        si, sx = s
        c = jnp.cumsum(sx)
        ends = jnp.searchsorted(si, jnp.arange(1, G + 1, dtype=jnp.int32)) - 1
        tot = c[jnp.clip(ends, 0, N - 1)]
        starts = jnp.concatenate([jnp.zeros(1, dtype=tot.dtype), tot[:-1]])
        return tot - starts

    r2 = jax.jit(seg_sum_via_sort)(ids, v)
    assert np.array_equal(np.asarray(r2), oracle)
    timeit("sort-by-id + cumsum segment sum", seg_sum_via_sort, ids, v)

    # the current hash-slot id kernel, isolated
    from presto_tpu.ops.aggregation import _group_ids
    from presto_tpu.block import Column
    from presto_tpu import types as T
    col = Column(v, jnp.zeros(N, dtype=bool), T.BIGINT)
    # inputs passed as jit ARGUMENTS (not closure constants) so XLA
    # cannot constant-fold any of the kernel away
    timeit("hash-slot _group_ids (1 int64 col)",
           lambda c, a: _group_ids([c], a, G), col, active)

    from presto_tpu.ops.aggregation import _group_ids_sort
    timeit("sort-based _group_ids (1 int64 col)",
           lambda c, a: _group_ids_sort([c], a, G), col, active)

    def first_occurrence_ids(words, act):
        """Candidate small-G id kernel: iteratively extract the first
        unresolved row's key, match all equal rows -- G data passes,
        zero scatters."""
        n = act.shape[0]
        rows = jnp.arange(n, dtype=jnp.int32)

        def body(state):
            g, ids = state
            unres = act & (ids < 0)
            i = jnp.min(jnp.where(unres, rows, n))
            i_safe = jnp.clip(i, 0, n - 1)
            match = unres
            for w in words:
                match = match & (w == w[i_safe])
            ids = jnp.where(match, g, ids)
            return g + 1, ids

        def cond(state):
            g, ids = state
            return (g < G) & jnp.any(act & (ids < 0))

        g, ids = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.full(n, -1, dtype=jnp.int32)))
        return g, ids

    ids16 = (w % jnp.uint64(G)).astype(jnp.uint64)  # 16 distinct "keys"
    timeit("first-occurrence ids (G rounds, 1 word)",
           lambda ww, a: first_occurrence_ids([ww], a), ids16, active)


def narrow_ab():
    """`--narrow-ab`: narrow-vs-wide A/B per primitive -- staged bytes
    and wall for each (staged lane dtype x kernel form) cell, so
    chip-day measurements slot straight into PERF.md. Toggles
    PRESTO_TPU_NARROW around each trace (the kernel forms are
    trace-time static) and stages the value column at int64/int32/int16
    physical lanes. All forms are exact; equality is asserted against a
    numpy oracle every cell."""
    from presto_tpu.ops.aggregation import (_limb_matmul_sum,
                                            last_smallg_form)

    rng = np.random.default_rng(0)
    ids_np = rng.integers(0, G, N).astype(np.int32)
    # int16-safe domain so every staged lane width is value-preserving
    v_np = rng.integers(-(2 ** 14), 2 ** 14, N).astype(np.int64)
    oracle = np.zeros(G, dtype=np.int64)
    np.add.at(oracle, ids_np, v_np)
    ids = jax.device_put(jnp.asarray(ids_np))

    print(f"platform={jax.devices()[0].platform} n={N} G={G} "
          f"(narrow-vs-wide A/B; oracle-checked)")
    print(f"{'cell':42s} {'staged':>10s} {'wall':>10s}")

    def cell(name, narrow, fn, *args):
        os.environ["PRESTO_TPU_NARROW"] = "1" if narrow else "0"
        # force the bf16 form for the narrow cells so the A/B is
        # kernel-vs-kernel even off-TPU (where bf16 is emulated; the
        # chip numbers are the ones PERF.md wants)
        os.environ["PRESTO_TPU_BF16"] = "1" if narrow else "0"
        from presto_tpu.ops import aggregation as _agg
        _agg._LAST_SMALLG_FORM[0] = None  # tag only THIS cell's trace
        try:
            staged = sum(int(np.asarray(a).nbytes) for a in args)
            r = np.asarray(jax.jit(fn)(*args))
            assert np.array_equal(r, oracle), name
            fn_j = jax.jit(fn)
            jax.device_get(fn_j(*args))

            def window(k):
                t0 = time.time()
                out = None
                for _ in range(k):
                    out = fn_j(*args)
                jax.device_get(out)
                return time.time() - t0

            t1, t2 = window(ITERS), window(2 * ITERS)
            dt = (t2 - t1) / ITERS
            if dt <= 0:
                dt = t2 / (2 * ITERS)
            print(f"{name:42s} {staged / 1e6:8.1f}MB {dt * 1e3:8.2f}ms"
                  f"  [{last_smallg_form()}]")
        finally:
            os.environ.pop("PRESTO_TPU_NARROW", None)
            os.environ.pop("PRESTO_TPU_BF16", None)

    for dt_name in ("int64", "int32", "int16"):
        v = jax.device_put(jnp.asarray(v_np.astype(dt_name)))
        vb = {"int64": 64, "int32": 32, "int16": 16}[dt_name]

        def scatter(i, x):
            return jnp.zeros(G, dtype=jnp.int64).at[i].add(
                x.astype(jnp.int64))

        cell(f"scatter-add ({dt_name} lanes)", False, scatter, ids, v)
        cell(f"limb matmul wide f32-HIGHEST ({dt_name})", False,
             lambda i, x: _limb_matmul_sum(i, x, G, value_bits=vb), ids, v)
        cell(f"limb matmul narrow bf16 ({dt_name})", True,
             lambda i, x: _limb_matmul_sum(i, x, G, value_bits=vb), ids, v)

    # fused cross-aggregate pool: 8 accumulators in ONE matmul vs 8
    from presto_tpu.ops.aggregation import _fused_limb_sums
    v64 = jax.device_put(jnp.asarray(v_np))

    def fused(i, x):
        return jnp.stack(_fused_limb_sums(i, [(x, 16)] * 8, G))

    def unfused(i, x):
        return jnp.stack([_limb_matmul_sum(i, x, G, value_bits=16)
                          for _ in range(8)])

    for narrow in (True, False):
        tag = "narrow-bf16" if narrow else "wide-f32"
        # force both gates so the A/B is kernel-vs-kernel off-TPU too
        # (same as cell(); on CPU bf16 is emulated -- chip numbers are
        # the ones PERF.md wants)
        os.environ["PRESTO_TPU_NARROW"] = "1" if narrow else "0"
        os.environ["PRESTO_TPU_BF16"] = "1" if narrow else "0"
        oracle8 = np.tile(oracle, (8, 1))

        def chk(fn, name):
            r = np.asarray(jax.jit(fn)(ids, v64))
            assert np.array_equal(r, oracle8), name

        chk(fused, "fused")
        chk(unfused, "unfused")
        timeit(f"8-accumulator FUSED pool ({tag})", fused, ids, v64)
        timeit(f"8-accumulator unfused ({tag})", unfused, ids, v64)
    os.environ.pop("PRESTO_TPU_NARROW", None)
    os.environ.pop("PRESTO_TPU_BF16", None)


if __name__ == "__main__":
    if "--narrow-ab" in sys.argv:
        narrow_ab()
    else:
        main()
