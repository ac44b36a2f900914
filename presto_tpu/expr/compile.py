"""RowExpression -> JAX compiler: the TPU ExpressionCompiler.

Reference surface: presto-main-base/.../sql/gen/ExpressionCompiler.java:144
(compilePageProcessor -> PageFunctionCompiler emitting JVM bytecode) and
presto-native-execution/.../types/PrestoToVeloxExpr.cpp. Here the
"compilation" is tracing: an expression tree becomes a pure function
over a Batch; XLA does the actual codegen and fusion that
PageFunctionCompiler/common-subexpression machinery does by hand on the
JVM (CommonSubExpressionRewriter is subsumed by XLA CSE).

Null semantics are Presto's three-valued logic:
  * scalar calls: NULL if any argument is NULL (functions may override)
  * AND/OR: Kleene
  * IF/SWITCH/COALESCE: lazy *selection* -- all branches are computed
    (no branches in SIMD), selection picks lanes; branch kernels must be
    total (no side effects, finite under any input), which the function
    registry guarantees.

Compile-time-constant interception: LIKE patterns, date_add units, and
IN lists are specialized during tracing -- the analog of the reference
constant-folding these in LocalExecutionPlanner/bytecode gen.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Callable, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..block import Batch, Column, DictionaryColumn, StringColumn
from . import functions as F
from .ir import (BatchParam, Call, Constant, InputReference, Lambda,
                 LambdaVariable, RowExpression, SpecialForm)

Block = Union[Column, StringColumn]

__all__ = ["compile_expression", "compile_filter", "compile_projections",
           "evaluate", "bound_params"]


# ---------------------------------------------------------------------------
# batch-parameter scope (exec/batching.py)
# ---------------------------------------------------------------------------
#
# A parameterized template plan contains BatchParam leaves instead of
# Constants; evaluation reads slot `index` of the params bound on THIS
# thread while the program traces. The batching executor binds traced
# (value, null) scalar pairs inside its vmapped wrapper, so one traced
# program serves every member of a query batch with per-member values.

_PARAM_SCOPE = threading.local()


@contextlib.contextmanager
def bound_params(values: Sequence):
    """Bind the ambient parameter vector (sequence of (value, is_null)
    scalars -- concrete or traced) for BatchParam evaluation on this
    thread for the duration of a trace."""
    prev = getattr(_PARAM_SCOPE, "values", None)
    _PARAM_SCOPE.values = values
    try:
        yield
    finally:
        _PARAM_SCOPE.values = prev


def _param_block(p: BatchParam, capacity: int) -> Block:
    values = getattr(_PARAM_SCOPE, "values", None)
    if values is None:
        raise RuntimeError(
            "BatchParam evaluated outside a bound_params scope -- "
            "template plans only execute through exec/batching.py")
    v, null = values[p.index]
    dt = p.type.to_dtype()
    vals = jnp.broadcast_to(jnp.asarray(v, dtype=dt), (capacity,))
    nulls = jnp.broadcast_to(jnp.asarray(null, dtype=bool), (capacity,))
    return Column(vals, nulls, p.type)


# ---------------------------------------------------------------------------
# constants -> broadcast blocks
# ---------------------------------------------------------------------------

def _constant_block(c: Constant, capacity: int) -> Block:
    ty = c.type
    if c.value is None:
        if ty.is_string:
            return StringColumn(jnp.zeros((capacity, 1), dtype=jnp.uint8),
                                jnp.zeros(capacity, dtype=jnp.int32),
                                jnp.ones(capacity, dtype=bool), ty)
        dt = ty.to_dtype() if ty != T.UNKNOWN else np.bool_
        return Column(jnp.zeros(capacity, dtype=dt),
                      jnp.ones(capacity, dtype=bool), ty)
    if ty.is_string:
        b = str(c.value).encode("utf-8")
        w = max(len(b), 1)
        chars = jnp.tile(jnp.asarray(bytearray(b.ljust(w, b"\x00")),
                                     dtype=jnp.uint8)[None, :], (capacity, 1))
        return StringColumn(chars,
                            jnp.full(capacity, len(b), dtype=jnp.int32),
                            jnp.zeros(capacity, dtype=bool), ty)
    v = c.value
    if ty.base == "date" and isinstance(v, str):
        v = int((np.datetime64(v) - np.datetime64("1970-01-01")).astype(int))
    return Column(jnp.full(capacity, v, dtype=ty.to_dtype()),
                  jnp.zeros(capacity, dtype=bool), ty)


# ---------------------------------------------------------------------------
# LIKE pattern compilation
# ---------------------------------------------------------------------------

def _like(a: StringColumn, pattern: str) -> jnp.ndarray:
    """Full LIKE matcher for patterns of %/_ wildcards, vectorized:
    segments between % marks are located left-to-right greedily (each
    segment's first feasible window), with '_' matching any single char.
    Greedy works because segments are matched earliest-first, which never
    eliminates a later feasible assignment (classic glob argument)."""
    pat = pattern.encode("utf-8")
    anchored_left = not pat.startswith(b"%")
    anchored_right = not pat.endswith(b"%")
    segments = [s for s in pat.split(b"%") if s != b""]
    n, w = a.chars.shape
    lengths = a.lengths

    if not segments:
        # pattern is only % signs (or empty)
        if pat == b"":
            return lengths == 0
        return jnp.ones(n, dtype=bool)

    def seg_match_windows(seg: bytes):
        """(N, windows) bool: seg matches at window start i ('_' = any)."""
        L = len(seg)
        windows = w - L + 1
        if windows <= 0:
            return None
        idx = (jnp.arange(windows, dtype=jnp.int32)[:, None]
               + jnp.arange(L, dtype=jnp.int32)[None, :])
        g = a.chars[:, idx]  # (N, windows, L)
        sarr = jnp.asarray(bytearray(seg), dtype=jnp.uint8)
        wild = sarr == ord("_")
        m = jnp.all((g == sarr[None, None, :]) | wild[None, None, :], axis=2)
        ends_ok = (jnp.arange(windows, dtype=jnp.int32)[None, :] + L) <= lengths[:, None]
        return m & ends_ok

    ok = jnp.ones(n, dtype=bool)
    earliest = jnp.zeros(n, dtype=jnp.int32)

    # all segments except (if right-anchored) the last: greedy earliest match
    loop_segments = segments[:-1] if anchored_right else segments
    for si, seg in enumerate(loop_segments):
        m = seg_match_windows(seg)
        if m is None:
            return jnp.zeros(n, dtype=bool)
        windows = m.shape[1]
        pos = jnp.arange(windows, dtype=jnp.int32)[None, :]
        feasible = m & (pos >= earliest[:, None])
        if si == 0 and anchored_left:
            feasible = feasible & (pos == 0)
        found = jnp.any(feasible, axis=1)
        # lax.argmax with an explicit int32 index dtype: jnp.argmax
        # materializes int64 indices under x64 and the immediate
        # .astype(int32) threw the wide lane away (kernaudit K001)
        first = jax.lax.argmax(feasible, 1, jnp.int32)
        ok = ok & found
        earliest = first + len(seg)

    if anchored_right:
        last = segments[-1]
        m = seg_match_windows(last)
        if m is None:
            return jnp.zeros(n, dtype=bool)
        # the last segment must match ending exactly at the string end,
        # starting no earlier than where the previous segments finished
        end_pos = lengths - len(last)
        at_end = jnp.take_along_axis(
            m, jnp.clip(end_pos, 0, m.shape[1] - 1)[:, None], axis=1)[:, 0]
        ok = ok & at_end & (end_pos >= earliest)
        if anchored_left and len(segments) == 1:
            ok = ok & (lengths == len(last))  # no % at all: exact-width match
    return ok


# ---------------------------------------------------------------------------
# evaluator
# ---------------------------------------------------------------------------

def evaluate(expr: RowExpression, batch: Batch) -> Block:
    cap = batch.capacity

    if isinstance(expr, InputReference):
        b = batch.column(expr.channel)
        if isinstance(b, DictionaryColumn):
            b = b.decode()
        return b

    if isinstance(expr, Constant):
        return _constant_block(expr, cap)

    if isinstance(expr, BatchParam):
        return _param_block(expr, cap)

    if isinstance(expr, SpecialForm):
        return _eval_special(expr, batch)

    if isinstance(expr, Call):
        name = expr.name.lower()
        # compile-time interceptions
        if name == "row_field":
            # the field index is plan structure, not data: resolve it
            # at trace time (a traced index would force a dynamic gather
            # across fields of possibly different types)
            from ..block import RowColumn, gather_block
            r = evaluate(expr.arguments[0], batch)
            idx = expr.arguments[1]
            assert isinstance(idx, Constant), "row_field index is static"
            assert isinstance(r, RowColumn), type(r)
            import jax.numpy as _jnp
            return gather_block(r.fields[int(idx.value)],
                                _jnp.arange(len(r), dtype=_jnp.int32),
                                ~r.nulls)
        if name == "like":
            a = evaluate(expr.arguments[0], batch)
            pat = expr.arguments[1]
            assert isinstance(pat, Constant), "LIKE pattern must be constant"
            v = _like(a, str(pat.value))
            return Column(v, a.nulls, expr.type)
        if name == "regexp_like":
            a = evaluate(expr.arguments[0], batch)
            pat = expr.arguments[1]
            assert isinstance(pat, Constant), \
                "regexp_like pattern must be constant"
            from ..ops.regex import compile_dfa, regexp_like_kernel
            table, accepting = compile_dfa(str(pat.value))
            v = regexp_like_kernel(a.chars, a.lengths, table, accepting)
            return Column(v, a.nulls, expr.type)
        if name in ("transform", "filter", "any_match", "all_match",
                    "none_match", "reduce") and \
                any(isinstance(a, Lambda) for a in expr.arguments):
            return _eval_array_lambda(expr, batch)
        if name in ("transform_values", "transform_keys", "map_filter"):
            return _eval_map_lambda(expr, batch)
        if name == "array_constructor":
            from ..block import ArrayColumn
            elems = [evaluate(a, batch) for a in expr.arguments]
            k = max(len(elems), 1)
            ety = expr.type.element_type
            if not elems:
                z = jnp.zeros((cap, 1), dtype=ety.to_dtype()
                              if ety != T.UNKNOWN else jnp.int64)
                return ArrayColumn(z, jnp.ones((cap, 1), bool),
                                   jnp.zeros(cap, dtype=jnp.int32),
                                   jnp.zeros(cap, bool), expr.type)
            assert all(not isinstance(e, StringColumn) for e in elems), \
                "ARRAY[] of strings is not yet supported"
            vals = jnp.stack([e.values.astype(ety.to_dtype())
                              for e in elems], axis=1)
            nls = jnp.stack([e.nulls for e in elems], axis=1)
            return ArrayColumn(vals, nls,
                               jnp.full(cap, k, dtype=jnp.int32),
                               jnp.zeros(cap, bool), expr.type)
        if name == "sequence":
            a0, a1 = expr.arguments[0], expr.arguments[1]
            assert isinstance(a0, Constant) and isinstance(a1, Constant), \
                "sequence bounds must be constant"
            from ..block import ArrayColumn
            lo, hi = int(a0.value), int(a1.value)
            step = int(expr.arguments[2].value) \
                if len(expr.arguments) > 2 else (1 if hi >= lo else -1)
            seq = np.arange(lo, hi + (1 if step > 0 else -1), step,
                            dtype=np.int64)
            k = max(len(seq), 1)
            vals = jnp.tile(jnp.asarray(seq.reshape(1, -1)
                                        if len(seq) else
                                        np.zeros((1, 1), np.int64)),
                            (cap, 1))
            return ArrayColumn(vals, jnp.zeros((cap, k), bool),
                               jnp.full(cap, len(seq), dtype=jnp.int32),
                               jnp.zeros(cap, bool), expr.type)
        if name == "at_timezone":
            # zone is plan structure: resolve the key at trace time
            a = evaluate(expr.arguments[0], batch)
            zc = expr.arguments[1]
            assert isinstance(zc, Constant), \
                "AT TIME ZONE zone must be constant"
            from ..tz import zone_key
            key = zone_key(str(zc.value))
            if a.type.base == "timestamp with time zone":
                inst = a.values >> 12
            else:  # naive timestamp = UTC instant (session zone)
                inst = a.values
            return Column((inst << 12) | jnp.int64(key), a.nulls, expr.type)
        if name == "regexp_replace":
            # constant pattern+replacement give the static output width:
            # at most len+1 insertions of the replacement text
            a = evaluate(expr.arguments[0], batch)
            pat = expr.arguments[1]
            rep = expr.arguments[2] if len(expr.arguments) > 2 else None
            assert isinstance(pat, Constant) and \
                (rep is None or isinstance(rep, Constant)), \
                "regexp_replace pattern/replacement must be constant"
            import re as _re
            p = str(pat.value)
            r = "" if rep is None else str(rep.value)
            w = a.chars.shape[1]
            width = max(w + (w + 1) * len(r.encode("utf-8")), 1)
            # Presto spells group references $g; python re.sub uses \g
            py_rep = _re.sub(r"\$(\d+)", r"\\\1", r)
            return F.host_string_kernel(
                lambda s: _re.sub(p, py_rep, s.decode("utf-8")),
                expr.type, width, a)
        if name == "date_format":
            d = evaluate(expr.arguments[0], batch)
            fmt = expr.arguments[1]
            assert isinstance(fmt, Constant), \
                "date_format format must be constant"
            chars, lengths = F.date_format_kernel(d.values, d.type,
                                                  str(fmt.value))
            return StringColumn(chars, lengths, d.nulls, expr.type)
        if name == "date_add":
            unit = expr.arguments[0]
            assert isinstance(unit, Constant)
            n = evaluate(expr.arguments[1], batch)
            d = evaluate(expr.arguments[2], batch)
            step = {"day": 1, "week": 7}.get(str(unit.value))
            if step is not None:
                vals = d.values + (n.values * step).astype(d.values.dtype)
            elif str(unit.value) in ("month", "year"):
                y, m, day = F._civil(d.values)
                months = n.values * 12 if str(unit.value) == "year" else n.values
                tot = (y * 12 + (m - 1)) + months
                ny, nm = tot // 12, tot % 12 + 1
                nd = jnp.minimum(day, F.last_day_kernel(ny, nm))
                vals = F._days_from_civil(ny, nm, nd).astype(d.values.dtype)
            else:
                raise NotImplementedError(f"date_add unit {unit.value!r}")
            return Column(vals, F._default_nulls(n, d), expr.type)

        if name == "date_trunc":
            unit = expr.arguments[0]
            assert isinstance(unit, Constant)
            d = evaluate(expr.arguments[1], batch)
            u = str(unit.value)
            if d.type.base == "timestamp":
                micros = d.values
                if u in ("second", "minute", "hour"):
                    step = {"second": 1_000_000, "minute": 60_000_000,
                            "hour": 3_600_000_000}[u]
                    vals = (micros // step) * step
                else:  # calendar units truncate through days
                    days = micros // 86_400_000_000
                    vals = F.date_trunc_kernel(u, days) * 86_400_000_000
                return Column(vals.astype(d.values.dtype), d.nulls, expr.type)
            assert d.type.base == "date", d.type
            vals = F.date_trunc_kernel(u, d.values).astype(d.values.dtype)
            return Column(vals, d.nulls, expr.type)
        if name == "date_diff":
            unit = expr.arguments[0]
            assert isinstance(unit, Constant)
            d1 = evaluate(expr.arguments[1], batch)
            d2 = evaluate(expr.arguments[2], batch)
            u = str(unit.value)
            if d1.type.base == "timestamp" or d2.type.base == "timestamp":
                m1 = _as_micros(d1)
                m2 = _as_micros(d2)
                if u in ("millisecond", "second", "minute", "hour", "day",
                         "week"):
                    # whole elapsed units, truncated toward zero
                    step = {"millisecond": 1_000, "second": 1_000_000,
                            "minute": 60_000_000, "hour": 3_600_000_000,
                            "day": 86_400_000_000,
                            "week": 7 * 86_400_000_000}[u]
                    delta = m2 - m1
                    vals = jnp.sign(delta) * (jnp.abs(delta) // step)
                else:
                    # calendar units on days, with a time-of-day partial
                    # adjustment when the day-of-month boundary ties
                    day_us = 86_400_000_000
                    vals = F.date_diff_kernel(u, m1 // day_us, m2 // day_us)
                    _, _, dd1 = F._civil(m1 // day_us)
                    _, _, dd2 = F._civil(m2 // day_us)
                    tod1 = m1 % day_us
                    tod2 = m2 % day_us
                    tie = dd1 == dd2
                    adj = jnp.where((vals > 0) & tie & (tod2 < tod1), 1,
                                    jnp.where((vals < 0) & tie & (tod2 > tod1),
                                              -1, 0))
                    vals = vals - adj
                return Column(vals.astype(expr.type.to_dtype()),
                              F._default_nulls(d1, d2), expr.type)
            assert d1.type.base == "date" and d2.type.base == "date"
            vals = F.date_diff_kernel(u, d1.values, d2.values)
            return Column(vals.astype(expr.type.to_dtype()),
                          F._default_nulls(d1, d2), expr.type)
        if name == "split_part":
            a = evaluate(expr.arguments[0], batch)
            delim = expr.arguments[1]
            idx = expr.arguments[2]
            assert isinstance(delim, Constant) and isinstance(idx, Constant)
            return F.split_part_kernel(a, str(delim.value).encode(),
                                       int(idx.value), expr.type)

        args = [evaluate(a, batch) for a in expr.arguments]
        sf = F.lookup(name)
        out = sf.fn(expr.type, *args)
        if sf.null_fn is not None:
            nulls = sf.null_fn(expr.type, *args)
            if nulls is None:
                return out  # kernel computed its own mask (host kernels)
            if isinstance(out, StringColumn):
                out = StringColumn(out.chars, out.lengths, nulls, out.type)
            else:
                from ..block import Int128Column
                if isinstance(out, Int128Column):
                    out = Int128Column(out.hi, out.lo, nulls, out.type)
                else:
                    out = Column(out.values, nulls, out.type)
        return out

    raise TypeError(f"cannot evaluate {type(expr)}")


def _as_micros(b: Block):
    if b.type.base == "date":
        return b.values.astype(jnp.int64) * 86_400_000_000
    return b.values


def _bool(b: Block):
    """(value, null) for a boolean block; value lanes under null are False."""
    return b.values & ~b.nulls, b.nulls


def _eval_special(expr: SpecialForm, batch: Batch) -> Block:
    form = expr.form
    args = expr.arguments

    if form == "AND":
        # Kleene: FALSE if any FALSE; else NULL if any NULL; else TRUE
        any_false, any_null = None, None
        for a in args:
            bv, bn = _bool(evaluate(a, batch))
            f = ~bv & ~bn
            any_false = f if any_false is None else (any_false | f)
            any_null = bn if any_null is None else (any_null | bn)
        nulls = ~any_false & any_null
        return Column(~any_false & ~nulls, nulls, expr.type)

    if form == "OR":
        # Kleene: TRUE if any TRUE; else NULL if any NULL; else FALSE
        any_true, any_null = None, None
        for a in args:
            bv, bn = _bool(evaluate(a, batch))
            any_true = bv if any_true is None else (any_true | bv)
            any_null = bn if any_null is None else (any_null | bn)
        nulls = ~any_true & any_null
        return Column(any_true, nulls, expr.type)

    if form == "IS_NULL":
        a = evaluate(args[0], batch)
        return Column(a.nulls, jnp.zeros(len(a), dtype=bool), expr.type)

    if form == "IF":
        cv, cn = _bool(evaluate(args[0], batch))
        t = evaluate(args[1], batch)
        f = evaluate(args[2], batch) if len(args) > 2 else \
            _constant_block(Constant(expr.type, None), batch.capacity)
        take_t = cv & ~cn
        return _select(take_t, t, f, expr.type)

    if form == "NULL_IF":
        a = evaluate(args[0], batch)
        b = evaluate(args[1], batch)
        eq = F._binary_cmp("eq")(T.BOOLEAN, a, b)
        ev, en = _bool(eq)
        nulls = a.nulls | (ev & ~en)
        if isinstance(a, StringColumn):
            return StringColumn(a.chars, a.lengths, nulls, expr.type)
        return Column(a.values, nulls, expr.type)

    if form == "COALESCE":
        out = evaluate(args[0], batch)
        for a in args[1:]:
            nxt = evaluate(a, batch)
            out = _select(~out.nulls, out, nxt, expr.type)
        return out

    if form == "IN":
        x = evaluate(args[0], batch)
        any_match = None
        any_null = x.nulls
        for a in args[1:]:
            b = evaluate(a, batch)
            eq = F._binary_cmp("eq")(T.BOOLEAN, x, b)
            ev, en = _bool(eq)
            any_match = ev if any_match is None else (any_match | ev)
            any_null = any_null | b.nulls
        # match -> TRUE; no match but saw null -> NULL; else FALSE
        nulls = ~any_match & any_null
        return Column(any_match & ~nulls, nulls, expr.type)

    if form == "BETWEEN":
        x = evaluate(args[0], batch)
        lo = evaluate(args[1], batch)
        hi = evaluate(args[2], batch)
        ge = F._binary_cmp("ge")(T.BOOLEAN, x, lo)
        le = F._binary_cmp("le")(T.BOOLEAN, x, hi)
        v = ge.values & le.values
        n = x.nulls | lo.nulls | hi.nulls
        return Column(v & ~n, n, expr.type)

    if form == "SWITCH":
        # args: operand, WHEN(value, result)..., [else]
        operand = args[0]
        whens = [a for a in args[1:] if isinstance(a, SpecialForm) and a.form == "WHEN"]
        els = [a for a in args[1:] if not (isinstance(a, SpecialForm) and a.form == "WHEN")]
        out = evaluate(els[0], batch) if els else \
            _constant_block(Constant(expr.type, None), batch.capacity)
        is_searched = isinstance(operand, Constant) and operand.value is True
        op_block = None if is_searched else evaluate(operand, batch)
        for wh in reversed(whens):
            cond_expr, res_expr = wh.arguments
            if is_searched:
                cv, cn = _bool(evaluate(cond_expr, batch))
            else:
                c = evaluate(cond_expr, batch)
                eq = F._binary_cmp("eq")(T.BOOLEAN, op_block, c)
                cv, cn = _bool(eq)
            res = evaluate(res_expr, batch)
            out = _select(cv & ~cn, res, out, expr.type)
        return out

    raise NotImplementedError(f"special form {form}")


def _bind_lambda(lam: Lambda, batch: Batch, param_blocks) -> Block:
    """Evaluate a lambda body over `batch` with its parameters bound to
    `param_blocks` (appended as extra channels; LambdaVariables become
    InputReferences into the extended space)."""
    from .logical import rewrite_bottom_up
    nc = len(batch.columns)
    mapping = {p: nc + i for i, p in enumerate(lam.parameters)}

    def sub(x):
        if isinstance(x, LambdaVariable) and x.name in mapping:
            return InputReference(x.type, mapping[x.name])
        return x

    body = rewrite_bottom_up(lam.body, sub)
    pseudo = Batch(tuple(batch.columns) + tuple(param_blocks), batch.active)
    return evaluate(body, pseudo)


def _eval_array_lambda(expr: Call, batch: Batch) -> Block:
    """Array higher-order functions (ArrayTransformFunction family).
    The element axis is materialized: the lambda body evaluates ONCE
    over the flattened (N*K,) element lanes with every outer column
    repeated K times -- XLA sees one wide fused elementwise program, no
    per-row loops (reduce iterates K static steps)."""
    from ..block import ArrayColumn, gather_block
    name = expr.name.lower()
    arr = evaluate(expr.arguments[0], batch)
    if isinstance(arr, DictionaryColumn):
        arr = arr.decode()
    assert isinstance(arr, ArrayColumn), f"{name} over {type(arr)}"
    n, k = arr.elements.shape
    ety = expr.arguments[0].type.element_type
    lanes = jnp.arange(k, dtype=jnp.int32)[None, :]
    in_range = lanes < arr.lengths[:, None]

    if name == "reduce":
        init = evaluate(expr.arguments[1], batch)
        comb, out_lam = expr.arguments[2], expr.arguments[3]
        state = init
        for j in range(k):
            elem = Column(arr.elements[:, j],
                          arr.elem_nulls[:, j] | arr.nulls, ety)
            new_state = _bind_lambda(comb, batch, [state, elem])
            live = (arr.lengths > j) & ~arr.nulls
            state = _select(live, new_state, state, new_state.type)
        res = _bind_lambda(out_lam, batch, [state])
        # a NULL array reduces to NULL
        if isinstance(res, StringColumn):
            return StringColumn(res.chars, res.lengths,
                                res.nulls | arr.nulls, expr.type)
        return Column(res.values, res.nulls | arr.nulls, expr.type)

    lam = expr.arguments[1]
    rep_idx = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    flat_elem = Column(arr.elements.reshape(-1),
                       (arr.elem_nulls | ~in_range).reshape(-1), ety)
    rep_cols = tuple(gather_block(c, rep_idx) for c in batch.columns)
    rep_batch = Batch(rep_cols, (batch.active[:, None]
                                 & in_range).reshape(-1))
    out = _bind_lambda(lam, rep_batch, [flat_elem])

    if name == "transform":
        assert not isinstance(out, StringColumn),             "transform to string elements is not yet supported"
        return ArrayColumn(out.values.reshape(n, k),
                           out.nulls.reshape(n, k) | ~in_range,
                           arr.lengths, arr.nulls, expr.type)
    pv = (out.values & ~out.nulls).reshape(n, k) & in_range
    pn = out.nulls.reshape(n, k) & in_range
    if name == "filter":
        keep = pv
        order = jnp.argsort(~keep, axis=1, stable=True)
        return ArrayColumn(jnp.take_along_axis(arr.elements, order, axis=1),
                           jnp.take_along_axis(arr.elem_nulls, order, axis=1),
                           jnp.sum(keep, axis=1).astype(arr.lengths.dtype),
                           arr.nulls, expr.type)
    any_true = jnp.any(pv, axis=1)
    any_null = jnp.any(pn, axis=1)
    if name == "all_match":
        any_false = jnp.any((~(out.values | out.nulls)).reshape(n, k)
                            & in_range, axis=1)
        nulls = ~any_false & any_null | arr.nulls
        return Column(~any_false & ~nulls, nulls, expr.type)
    v = any_true
    if name == "none_match":
        v = ~any_true
    nulls = ~any_true & any_null | arr.nulls
    return Column(v & ~nulls, nulls, expr.type)


def _eval_map_lambda(expr: Call, batch: Batch) -> Block:
    """Map higher-order functions (MapTransformValuesFunction family):
    the (key, value) lambda evaluates once over flattened (N*K,) entry
    lanes, outer columns repeated -- same shape as the array path."""
    from ..block import MapColumn, gather_block
    name = expr.name.lower()
    m = evaluate(expr.arguments[0], batch)
    assert isinstance(m, MapColumn), f"{name} over {type(m)}"
    lam = expr.arguments[1]
    n, k = m.keys.shape
    kty = expr.arguments[0].type.key_type
    vty = expr.arguments[0].type.value_type
    lanes = jnp.arange(k, dtype=jnp.int32)[None, :]
    in_range = lanes < m.lengths[:, None]
    flat_k = Column(m.keys.reshape(-1), (~in_range).reshape(-1), kty)
    flat_v = Column(m.values.reshape(-1),
                    (m.value_nulls | ~in_range).reshape(-1), vty)
    rep_idx = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    rep_cols = tuple(gather_block(c, rep_idx) for c in batch.columns)
    rep_batch = Batch(rep_cols, (batch.active[:, None]
                                 & in_range).reshape(-1))
    out = _bind_lambda(lam, rep_batch, [flat_k, flat_v])
    assert not isinstance(out, StringColumn), \
        f"{name} to string lanes is not yet supported"
    if name == "transform_values":
        return MapColumn(m.keys, out.values.reshape(n, k),
                         out.nulls.reshape(n, k) | ~in_range,
                         m.lengths, m.nulls, expr.type)
    if name == "transform_keys":
        # SQL contract: keys are non-null AND distinct; a lambda
        # producing a NULL or duplicate key is a per-row error (the
        # reference raises "Duplicate map keys are not allowed") --
        # total kernels surface it as a NULL map
        nk = out.values.reshape(n, k)
        bad = jnp.any(out.nulls.reshape(n, k) & in_range, axis=1)
        both = in_range[:, :, None] & in_range[:, None, :]
        eq = (nk[:, :, None] == nk[:, None, :]) & both
        dup = jnp.any(eq & ~jnp.eye(k, dtype=bool)[None], axis=(1, 2))
        return MapColumn(nk, m.values, m.value_nulls, m.lengths,
                         m.nulls | bad | dup, expr.type)
    # map_filter: keep entries whose predicate is TRUE
    keep = (out.values & ~out.nulls).reshape(n, k) & in_range
    order = jnp.argsort(~keep, axis=1, stable=True)
    return MapColumn(jnp.take_along_axis(m.keys, order, axis=1),
                     jnp.take_along_axis(m.values, order, axis=1),
                     jnp.take_along_axis(m.value_nulls, order, axis=1),
                     jnp.sum(keep, axis=1).astype(m.lengths.dtype),
                     m.nulls, expr.type)


def _select(take_a, a: Block, b: Block, ty: T.Type) -> Block:
    """Lane-select between two blocks of the same logical type."""
    from ..block import Int128Column
    if isinstance(a, Int128Column) or isinstance(b, Int128Column):
        # mixed representations happen (a long-decimal branch vs an
        # int64-lane literal of the same type): widen both to 128
        ah, al = F._as128(a)
        bh, bl = F._as128(b)
        return Int128Column(jnp.where(take_a, ah, bh),
                            jnp.where(take_a, al, bl),
                            jnp.where(take_a, a.nulls, b.nulls), ty)
    if isinstance(a, StringColumn) or isinstance(b, StringColumn):
        w = max(a.max_len, b.max_len)
        ca = jnp.pad(a.chars, ((0, 0), (0, w - a.max_len)))
        cb = jnp.pad(b.chars, ((0, 0), (0, w - b.max_len)))
        return StringColumn(jnp.where(take_a[:, None], ca, cb),
                            jnp.where(take_a, a.lengths, b.lengths),
                            jnp.where(take_a, a.nulls, b.nulls), ty)
    av, bv = a.values, b.values
    if av.dtype != bv.dtype:
        dt = jnp.promote_types(av.dtype, bv.dtype)
        av, bv = av.astype(dt), bv.astype(dt)
    return Column(jnp.where(take_a, av, bv),
                  jnp.where(take_a, a.nulls, b.nulls), ty)


# ---------------------------------------------------------------------------
# public compiled entry points (PageFilter / PageProjection analogs)
# ---------------------------------------------------------------------------

def compile_expression(expr: RowExpression) -> Callable[[Batch], Block]:
    return functools.partial(evaluate, expr)


def compile_filter(expr: RowExpression) -> Callable[[Batch], Batch]:
    """PageFilter analog: returns the input batch with rows failing the
    predicate (FALSE or NULL) deactivated -- selection stays a mask, no
    compaction (see block.py module docs)."""
    def run(batch: Batch) -> Batch:
        out = evaluate(expr, batch)
        keep = out.values & ~out.nulls
        return batch.with_active(batch.active & keep)
    return run


def compile_projections(exprs: Sequence[RowExpression]) -> Callable[[Batch], Batch]:
    """PageProjection analog: evaluates each expression into an output
    column; the active mask rides along unchanged."""
    def run(batch: Batch) -> Batch:
        cols = tuple(evaluate(e, batch) for e in exprs)
        return Batch(cols, batch.active)
    return run
