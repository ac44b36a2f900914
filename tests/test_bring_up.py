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

import presto_tpu
from presto_tpu.ops import device

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


def test_interpret_is_a_required_keyword_of_both_kernels():
    from presto_tpu.ops import pallas_kernels as pk
    with pytest.raises(TypeError):
        pk.limb_partial_sums(jnp.zeros(8, jnp.int32),
                             jnp.zeros((8, 2), jnp.float32), 4)
    with pytest.raises(TypeError):
        pk.contains_bytes(jnp.zeros((8, 4), jnp.uint8),
                          jnp.zeros(8, jnp.int32), b"x")


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


def test_like_substring_routes_through_contains_pattern(monkeypatch):
    """`LIKE '%x%'` is the substring search (the op with a Pallas form
    on TPU); anchored / wildcard patterns keep the general matcher."""
    from presto_tpu.expr import functions
    seen = []
    real = functions.contains_pattern
    monkeypatch.setattr(functions, "contains_pattern",
                        lambda a, needle: seen.append(needle) or
                        real(a, needle))
    q = "SELECT count(*) FROM part WHERE name LIKE '{}'"
    n_sub = presto_tpu.sql(q.format("%sleep%"), sf=0.01).rows()[0][0]
    assert seen == [b"sleep"] and n_sub > 0
    presto_tpu.sql(q.format("sleep%"), sf=0.01)
    presto_tpu.sql(q.format("%sle_p%"), sf=0.01)
    assert seen == [b"sleep"]


def test_front_door_function_answers_every_call():
    """`presto_tpu.sql(text)` used to work exactly once per process:
    importing the subpackage rebound the attribute to the module."""
    for _ in range(2):
        assert presto_tpu.sql("SELECT count(*) FROM region",
                              sf=0.01).rows() == [(5,)]
    assert callable(presto_tpu.sql.plan_sql)


# -- the pass-per-word sort -------------------------------------------------

@pytest.mark.parametrize("stable", [True, False])
def test_lex_sort_tpu_form_equals_the_single_sort(monkeypatch, stable):
    from presto_tpu.ops import keys
    rng = np.random.default_rng(4)
    n = 2 * keys._ONE_SORT_MAX_ROWS
    ops = [jnp.asarray(rng.integers(0, 3, n).astype(np.uint64))
           for _ in range(3)] + [jnp.arange(n, dtype=jnp.int32)]
    want = jax.lax.sort(ops, num_keys=3, is_stable=True)
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    jaxpr = str(jax.make_jaxpr(
        lambda *o: keys.lex_sort(o, num_keys=3, is_stable=stable))(*ops))
    assert "scan" in jaxpr and jaxpr.count("sort[") == 1
    got = keys.lex_sort(ops, num_keys=3, is_stable=stable)
    for g, w in zip(got, want):
        assert (np.asarray(g) == np.asarray(w)).all()


def test_lex_sort_small_or_single_key_stays_one_sort(monkeypatch):
    from presto_tpu.ops import keys
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    small = [jnp.zeros(64, jnp.uint64)] * 3 + [jnp.arange(64)]
    big1 = [jnp.zeros(1 << 13, jnp.uint64), jnp.arange(1 << 13)]
    for ops, nk in ((small, 3), (big1, 1)):
        jaxpr = str(jax.make_jaxpr(
            lambda *o: keys.lex_sort(o, num_keys=nk))(*ops))
        assert "scan" not in jaxpr


# -- the compile cache ------------------------------------------------------

def test_compile_cache_env_placement_sets_nothing(monkeypatch, tmp_path):
    from presto_tpu.utils import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert calls == []


def test_compile_cache_default_is_a_fixed_path_in_the_checkout(monkeypatch):
    from presto_tpu.utils import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".cache", "jax")
    assert compile_cache.setup_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


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
