"""The device boundary, as far as the CPU can hold it to account:
one backend question, explicit interpret, the pass-per-word sort, the
compile cache's placement, and the entry scripts' refusal to measure
anything but the chip. (What the chip's compiler says is
tests/test_tpu_compile.py; what the chip says is chip_smoke.py.)"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import presto_tpu  # noqa: F401  (x64 on before any array exists)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _root_module(name):
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    return __import__(name)


# -- one question to the device ---------------------------------------------

def test_every_tpu_branch_asks_the_one_helper():
    """`jax.default_backend()` / `jax.devices()` are read in exactly one
    place in the operator + expression layers."""
    import re
    hits = []
    for sub in ("ops", "expr"):
        d = os.path.join(REPO, "presto_tpu", sub)
        for f in sorted(os.listdir(d)):
            if f.endswith(".py"):
                text = open(os.path.join(d, f)).read()
                if re.search(r"default_backend\(|jax\.devices\(", text):
                    hits.append(f"{sub}/{f}")
    assert hits == ["ops/device.py"]


def test_interpret_is_a_required_keyword_of_the_kernel():
    from presto_tpu.ops import pallas_kernels as pk
    with pytest.raises(TypeError):
        pk.limb_partial_sums(jnp.zeros(8, jnp.int32),
                             jnp.zeros((8, 2), jnp.float32), 4)


@pytest.mark.parametrize("compute", ["bf16", "f32"])
def test_limb_partial_sums_forms_agree_with_numpy(compute):
    """Both MXU operand forms, interpret mode, x64 on: exact."""
    from presto_tpu.ops.pallas_kernels import limb_partial_sums
    rng = np.random.default_rng(11)
    n, L, G = 3000, 5, 16
    ids = rng.integers(0, G + 1, n).astype(np.int32)  # G == dropped
    limbs = rng.integers(-128, 256, (n, L))
    lane, cdt = ((np.int16, jnp.bfloat16) if compute == "bf16"
                 else (np.float32, jnp.float32))
    parts = limb_partial_sums(jnp.asarray(ids), jnp.asarray(
        limbs.astype(lane)), G, interpret=True, compute_dtype=cdt)
    want = np.zeros((G, L), np.int64)
    np.add.at(want, ids[ids < G], limbs[ids < G])
    assert (np.asarray(parts).astype(np.int64).sum(axis=0) == want).all()


@pytest.mark.parametrize("pattern,matcher", [
    ("%sleep%", lambda s: "sleep" in s),
    ("sleep%", lambda s: s.startswith("sleep")),
    ("%sle_p%", lambda s: any(s[i:i + 3] == "sle" and s[i + 4:i + 5] == "p"
                              for i in range(len(s)))),
])
def test_like_through_the_front_door_equals_python(pattern, matcher):
    """One LIKE path (`expr/compile._like`) for every pattern shape,
    called twice through the one front door `presto_tpu.sql.sql`."""
    from presto_tpu.connectors import tpch
    from presto_tpu.sql import sql
    names = tpch.generate_columns("part", 0.01, ["name"])["name"]
    want = sum(matcher(s) for s in names)
    q = f"SELECT count(*) FROM part WHERE name LIKE '{pattern}'"
    for _ in range(2):
        assert sql(q, sf=0.01).rows() == [(want,)]


# -- the pass-per-word sort -------------------------------------------------

def _key_operands(n, num_keys, seed=4):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, 3, n).astype(np.uint64))
            for _ in range(num_keys)] + [jnp.arange(n, dtype=jnp.int32)]


@pytest.mark.parametrize("stable", [True, False])
@pytest.mark.parametrize("n", [64, 4096])
def test_lex_sort_passes_equal_the_single_sort(n, stable):
    """One path on every backend and at every size: one single-key
    stable sort inside a scan over the key words."""
    from presto_tpu.ops import keys
    ops = _key_operands(n, 3)
    want = jax.lax.sort(ops, num_keys=3, is_stable=True)
    jaxpr = str(jax.make_jaxpr(
        lambda *o: keys.lex_sort(o, num_keys=3, is_stable=stable))(*ops))
    assert "scan" in jaxpr and jaxpr.count("sort[") == 1
    got = keys.lex_sort(ops, num_keys=3, is_stable=stable)
    for g, w in zip(got, want):
        assert (np.asarray(g) == np.asarray(w)).all()


def test_lex_sort_traces_inside_a_checked_shard_map():
    """The scan's carry must vary over the mesh axes going in as it
    does coming out: a default (check_vma=True) shard_map refuses a
    carry seeded from a plain arange. The engine's own shard_maps set
    check_vma=False and could not see that."""
    from jax.sharding import PartitionSpec as P
    from presto_tpu.ops import keys
    from presto_tpu.parallel import make_mesh
    n, shards = 4096, 4
    ops = _key_operands(n, 3)
    got = jax.jit(jax.shard_map(
        lambda *o: tuple(keys.lex_sort(o, num_keys=3)),
        mesh=make_mesh(shards), in_specs=P("workers"),
        out_specs=P("workers")))(*ops)
    got = [np.asarray(g) for g in got]
    for i in range(shards):
        sl = slice(i * n // shards, (i + 1) * n // shards)
        want = jax.lax.sort([np.asarray(o)[sl] for o in ops], num_keys=3,
                            is_stable=True)
        for g, w in zip(got, want):
            assert (g[sl] == np.asarray(w)).all()


# -- the compile cache ------------------------------------------------------

def test_compile_cache_env_placement_sets_no_directory(monkeypatch,
                                                       tmp_path):
    """The environment places the cache, the code sets no other
    directory -- but keeps every program there too: the thresholds are
    the same wherever the cache lives."""
    from presto_tpu.utils import compile_cache
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.update({name: value}))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert calls == {"jax_persistent_cache_min_compile_time_secs": 0.0,
                     "jax_persistent_cache_min_entry_size_bytes": 0}


def test_compile_cache_default_is_a_fixed_path_in_the_checkout(monkeypatch):
    from presto_tpu.utils import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".cache", "jax")
    assert compile_cache.setup_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


# -- entry scripts: the chip or nothing -------------------------------------

def test_chip_smoke_refuses_without_a_tpu(capsys):
    assert _root_module("chip_smoke").main([]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "no CPU fallback" in out.err


def test_chip_smoke_rehearsal_says_ok_false(capsys):
    smoke = _root_module("chip_smoke")
    assert smoke.main(["--allow-cpu-rehearsal", "--sf", "0.01"]) == 0
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    assert [l["statement"] for l in lines[1:-1]] == \
        ["q1", "q6", "q3", "q14", "like"]
    assert all(l["matches_numpy"] for l in lines[1:-1])
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"


def test_bench_refuses_without_a_tpu(capsys):
    bench = _root_module("bench")
    for entry in (bench.main, bench._bench_full):
        with pytest.raises(SystemExit) as e:
            entry()
        assert e.value.code == 1
    assert "tpch" not in capsys.readouterr().out
    assert "subprocess" not in open(os.path.join(REPO, "bench.py")).read()
