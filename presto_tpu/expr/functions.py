"""Built-in scalar function registry and their JAX implementations.

Reference surface: presto-main-base/.../operator/scalar/ (164 files) and
the annotation-driven registration machinery (operator/annotations/,
FunctionAndTypeManager). Here a function is a name plus a JAX
value-implementation; overload resolution happens inside the
implementation by inspecting argument Block types (the coordinator has
already type-checked the expression tree).

Null semantics: the compiler computes the default null mask (OR of
argument nulls, RETURNS NULL ON NULL INPUT) for every call; functions
only compute value lanes and must keep lanes finite/in-domain under
nulls so masked garbage never poisons downstream reductions. Functions
with non-default null behavior set `null_fn`.

Decimal arithmetic follows Presto's rules: add/subtract rescale to max
scale, multiply adds scales, divide rescales the dividend
(round-half-up like the reference). Short decimals (precision <= 18)
live in int64 lanes; LONG decimals (19..38) compute in exact 128-bit
(hi, lo) lane pairs (int128.py, the Int128ArrayBlock /
UnscaledDecimal128Arithmetic analog) -- results arrive as Int128Column
and every consumer (compare, sort, group, hash, serde) dispatches on
the representation.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..block import Column, Int128Column, StringColumn
from .. import int128 as I128

Block = Union[Column, StringColumn, Int128Column]
_T_UNKNOWN = T.UNKNOWN

__all__ = ["ScalarFunction", "REGISTRY", "register", "lookup",
           "rescale_decimal", "hash64_block", "combine_hash"]


@dataclasses.dataclass
class ScalarFunction:
    name: str
    fn: Callable            # (ret_type, *blocks) -> Block
    null_fn: Optional[Callable] = None  # (ret_type, *blocks) -> nulls | None=default


REGISTRY: Dict[str, ScalarFunction] = {}


def register(name: str, null_fn=None):
    def deco(fn):
        REGISTRY[name] = ScalarFunction(name, fn, null_fn)
        return fn
    return deco


def lookup(name: str) -> ScalarFunction:
    try:
        return REGISTRY[name]
    except KeyError:
        raise NotImplementedError(f"scalar function {name!r} is not registered") from None


def _default_nulls(*blocks: Block):
    nulls = None
    for b in blocks:
        nulls = b.nulls if nulls is None else (nulls | b.nulls)
    return nulls


def _col(ret_type: T.Type, values, *args: Block) -> Column:
    return Column(values, _default_nulls(*args), ret_type)


# ---------------------------------------------------------------------------
# numeric helpers
# ---------------------------------------------------------------------------

_POW10 = [10**i for i in range(19)]


def rescale_decimal(values, from_scale: int, to_scale: int):
    """Exact int64 rescale with round-half-away-from-zero on downscale."""
    if to_scale == from_scale:
        return values
    if to_scale > from_scale:
        return values * _POW10[to_scale - from_scale]
    f = _POW10[from_scale - to_scale]
    half = f // 2
    return jnp.where(values >= 0, (values + half) // f, -((-values + half) // f))


def _scale_of(ty: T.Type) -> int:
    return ty.scale if ty.is_decimal else 0


def _is_long_decimal(ty: T.Type) -> bool:
    return ty.is_decimal and not ty.is_short_decimal


def _any128(*blocks) -> bool:
    return any(isinstance(b, Int128Column) for b in blocks)


def _as128(b) -> tuple:
    """(hi, lo) lanes of a numeric block at ITS OWN scale."""
    if isinstance(b, Int128Column):
        return b.hi, b.lo
    return I128.from_int64(b.values.astype(jnp.int64))


def _as128_at_scale(b, to_scale: int) -> tuple:
    s = _scale_of(b.type)
    hi, lo = _as128(b)
    if to_scale > s:
        hi, lo = I128.rescale128_up(hi, lo, 10 ** (to_scale - s))
    elif to_scale < s:
        raise NotImplementedError("long-decimal downscale (round)")
    return hi, lo


def _promote(ret_type: T.Type, *blocks: Column):
    """Bring numeric args to the ret_type's representation: decimals to
    ret scale, everything to ret dtype family."""
    out = []
    rd = jnp.dtype(ret_type.to_dtype())
    for b in blocks:
        if isinstance(b, Int128Column):
            if ret_type.is_floating:
                # convert via the MAGNITUDE: for negative values the
                # two's-complement lo lane sits near 2^64 where float64
                # granularity is ~2048, so hi*2^64+lo would lose the low
                # bits (observed as ~1e-6 relative error on sums)
                neg = b.hi < 0
                mh, ml = I128.neg128(b.hi, b.lo)
                mh = jnp.where(neg, mh, b.hi)
                ml = jnp.where(neg, ml, b.lo)
                f = (mh.astype(jnp.float64) * np.float64(2.0 ** 64)
                     + ml.astype(jnp.float64))
                f = jnp.where(neg, -f, f)
                out.append(f / _POW10[_scale_of(b.type)])
                continue
            raise NotImplementedError(
                f"long-decimal lanes cannot promote to {ret_type}")
        v = b.values
        if ret_type.is_decimal:
            if b.type.is_decimal or b.type.is_integral:
                v = rescale_decimal(v.astype(jnp.int64), _scale_of(b.type),
                                    ret_type.scale)
            else:
                raise NotImplementedError("float->decimal arithmetic")
        elif ret_type.is_floating:
            if b.type.is_decimal:
                v = v.astype(rd) / _POW10[b.type.scale]
            else:
                v = v.astype(rd)
        else:
            if b.type.is_decimal:
                v = rescale_decimal(v.astype(jnp.int64), b.type.scale, 0)
            v = v.astype(rd)
        out.append(v)
    return out


# ---------------------------------------------------------------------------
# arithmetic
# ---------------------------------------------------------------------------

def _needs128(ret, *blocks) -> bool:
    """Long-decimal result or any 128-bit-lane argument routes an
    arithmetic op to the exact 128-bit path."""
    return (ret.is_decimal and _is_long_decimal(ret)) or _any128(*blocks)


@register("add")
def _add(ret, a, b):
    if ret.is_decimal and _needs128(ret, a, b):
        ah, al = _as128_at_scale(a, ret.scale)
        bh, bl = _as128_at_scale(b, ret.scale)
        hi, lo = I128.add128(ah, al, bh, bl)
        return Int128Column(hi, lo, _default_nulls(a, b), ret)
    x, y = _promote(ret, a, b)
    return _col(ret, x + y, a, b)


@register("subtract")
def _subtract(ret, a, b):
    if ret.is_decimal and _needs128(ret, a, b):
        ah, al = _as128_at_scale(a, ret.scale)
        bh, bl = _as128_at_scale(b, ret.scale)
        hi, lo = I128.add128(ah, al, *I128.neg128(bh, bl))
        return Int128Column(hi, lo, _default_nulls(a, b), ret)
    x, y = _promote(ret, a, b)
    return _col(ret, x - y, a, b)


@register("multiply")
def _multiply(ret, a, b):
    if ret.is_decimal:
        # multiply: scale_out = s1 + s2; operate on raw scaled ints
        assert _scale_of(a.type) + _scale_of(b.type) == ret.scale, \
            (a.type, b.type, ret)
        if _needs128(ret, a, b):
            # exact 128-bit product (decimal(38) domain); int64-lane
            # inputs widen through the signed 64x64 -> 128 multiply
            if not _any128(a, b):
                hi, lo = I128.mul_i64_i64_128(
                    a.values.astype(jnp.int64), b.values.astype(jnp.int64))
            else:
                ah, al = _as128(a)
                bh, bl = _as128(b)
                hi, lo = I128.mul128(ah, al, bh, bl)
            return Int128Column(hi, lo, _default_nulls(a, b), ret)
        return _col(ret, a.values.astype(jnp.int64) * b.values.astype(jnp.int64), a, b)
    x, y = _promote(ret, a, b)
    return _col(ret, x * y, a, b)


def _zero_lanes(b):
    if isinstance(b, Int128Column):
        return (b.hi == 0) & (b.lo == jnp.uint64(0))
    return b.values == 0


def _div_nulls(ret, a, b):
    zero = _zero_lanes(b) & ~b.nulls
    return _default_nulls(a, b) | zero


@register("divide", null_fn=_div_nulls)
def _divide(ret, a, b):
    """Division by zero yields NULL (the reference raises DIVISION_BY_ZERO;
    a jit'd kernel cannot throw -- task-level checking arrives with the
    error-channel in exec)."""
    nulls = _div_nulls(ret, a, b)
    if ret.is_decimal and (_needs128(ret, a, b) or
                           _scale_of(b.type) + ret.scale - _scale_of(a.type)
                           > 18):
        return _divide128(ret, a, b, nulls)
    if ret.is_decimal:
        sa, sb = _scale_of(a.type), _scale_of(b.type)
        # presto: rescale dividend by 10^(s_out + s_b - s_a), round half away
        num = a.values.astype(jnp.int64) * _POW10[ret.scale + sb - sa]
        den = jnp.where(b.values == 0, 1, b.values.astype(jnp.int64))
        neg = (num < 0) != (den < 0)
        an, ad = jnp.abs(num), jnp.abs(den)
        q = (2 * an + ad) // (2 * ad)
        return Column(jnp.where(neg, -q, q), nulls, ret)
    if ret.is_integral:
        x = a.values.astype(jnp.int64)
        y = jnp.where(b.values == 0, 1, b.values).astype(jnp.int64)
        neg = (x < 0) != (y < 0)
        q = jnp.abs(x) // jnp.abs(y)  # SQL integer division truncates toward zero
        return Column(jnp.where(neg, -q, q).astype(ret.to_dtype()), nulls, ret)
    x, y = _promote(ret, a, b)
    y = jnp.where(y == 0, 1.0, y)
    return Column(x / y, nulls, ret)


def _divide128(ret, a, b, nulls):
    """Exact long-decimal division, round half away from zero. The
    divisor must fit 64-bit lanes (|b| < 2^63 -- covers counts and every
    short-decimal divisor; a 128/128 division would need the full
    Knuth-D loop and no engine query shape produces one yet)."""
    sa, sb = _scale_of(a.type), _scale_of(b.type)
    ah, al = _as128(a)
    factor = 10 ** (ret.scale + sb - sa)
    if factor > 1:
        ah, al = I128.rescale128_up(ah, al, factor)
    if isinstance(b, Int128Column):
        bv = b.lo.astype(jnp.int64)  # valid when |b| < 2^63
        bneg = b.hi < 0
        bv = jnp.where(bneg, -bv, bv)  # magnitude (64-bit divisors only)
    else:
        bv = b.values.astype(jnp.int64)
        bneg = bv < 0
        bv = jnp.where(bneg, -bv, bv)
    bv = jnp.where(bv == 0, 1, bv)
    aneg = ah < 0
    mh, ml = I128.neg128(ah, al)
    mh = jnp.where(aneg, mh, ah)
    ml = jnp.where(aneg, ml, al)
    qh, ql, rem = I128.divmod128_by_u64(mh, ml, bv)
    half_up = (2 * rem >= bv.astype(jnp.uint64)).astype(jnp.int64)
    qh2, ql2 = I128.add128(qh.astype(jnp.int64), ql,
                           jnp.zeros_like(qh, dtype=jnp.int64),
                           half_up.astype(jnp.uint64))
    neg = aneg != bneg
    nh, nl = I128.neg128(qh2, ql2)
    hi = jnp.where(neg, nh, qh2)
    lo = jnp.where(neg, nl, ql2)
    return Int128Column(hi, lo, nulls, ret)


@register("modulus", null_fn=_div_nulls)
def _modulus(ret, a, b):
    x, y = _promote(ret, a, b)
    y = jnp.where(y == 0, 1, y)
    r = jnp.sign(x) * (jnp.abs(x) % jnp.abs(y))  # truncated mod (SQL semantics)
    return Column(r.astype(ret.to_dtype()), _div_nulls(ret, a, b), ret)


@register("negate")
def _negate(ret, a):
    if isinstance(a, Int128Column):
        hi, lo = I128.neg128(a.hi, a.lo)
        return Int128Column(hi, lo, a.nulls, ret)
    return _col(ret, -a.values, a)


@register("abs")
def _abs(ret, a):
    if isinstance(a, Int128Column):
        nh, nl = I128.neg128(a.hi, a.lo)
        neg = a.hi < 0
        return Int128Column(jnp.where(neg, nh, a.hi),
                            jnp.where(neg, nl, a.lo), a.nulls, ret)
    return _col(ret, jnp.abs(a.values), a)


# ---------------------------------------------------------------------------
# comparisons (work for numeric and string blocks)
# ---------------------------------------------------------------------------

def _cmp_values(a: Block, b: Block):
    """Return comparison key arrays for =, <, etc."""
    if isinstance(a, StringColumn) or isinstance(b, StringColumn):
        return None  # handled by string paths
    sa, sb = _scale_of(a.type), _scale_of(b.type)
    if (a.type.is_decimal or b.type.is_decimal) and not (a.type.is_floating or b.type.is_floating):
        s = max(sa, sb)
        return (rescale_decimal(a.values.astype(jnp.int64), sa, s),
                rescale_decimal(b.values.astype(jnp.int64), sb, s))
    if a.type.is_floating or b.type.is_floating:
        va = a.values.astype(jnp.float64)
        vb = b.values.astype(jnp.float64)
        if a.type.is_decimal:
            va = va / _POW10[sa]
        if b.type.is_decimal:
            vb = vb / _POW10[sb]
        return va, vb
    tz = "timestamp with time zone"
    bases = (a.type.base, b.type.base)
    if tz in bases or ("date" in bases and "timestamp" in bases):
        # mixed datetime comparison: align everything to UTC micros
        # (tz values unpack their zone key; dates scale from days)
        def inst(x):
            if x.type.base == tz:
                return x.values >> 12
            if x.type.base == "date":
                return x.values.astype(jnp.int64) * 86_400_000_000
            return x.values
        return inst(a), inst(b)
    return a.values, b.values


def _str_eq(a: StringColumn, b: StringColumn):
    w = max(a.max_len, b.max_len)
    ca = jnp.pad(a.chars, ((0, 0), (0, w - a.max_len)))
    cb = jnp.pad(b.chars, ((0, 0), (0, w - b.max_len)))
    return jnp.all(ca == cb, axis=1) & (a.lengths == b.lengths)


def _str_cmp(a: StringColumn, b: StringColumn):
    """Lexicographic compare: returns (-1, 0, 1) per row."""
    w = max(a.max_len, b.max_len)
    ca = jnp.pad(a.chars, ((0, 0), (0, w - a.max_len))).astype(jnp.int32)
    cb = jnp.pad(b.chars, ((0, 0), (0, w - b.max_len))).astype(jnp.int32)
    diff = jnp.sign(ca - cb)  # (N, w)
    first = jnp.argmax(jnp.abs(diff), axis=1)
    d = jnp.take_along_axis(diff, first[:, None], axis=1)[:, 0]
    # zero-padded chars make shorter strings compare smaller automatically
    return d


def _binary_cmp(op):
    def fn(ret, a, b):
        if isinstance(a, StringColumn) and isinstance(b, StringColumn):
            if op == "eq":
                v = _str_eq(a, b)
            elif op == "ne":
                v = ~_str_eq(a, b)
            else:
                d = _str_cmp(a, b)
                v = {"lt": d < 0, "le": d <= 0, "gt": d > 0, "ge": d >= 0}[op]
            return _col(ret, v, a, b)
        if _any128(a, b):
            s = max(_scale_of(a.type), _scale_of(b.type))
            ah, al = _as128_at_scale(a, s)
            bh, bl = _as128_at_scale(b, s)
            lt, eq = I128.cmp128(ah, al, bh, bl)
            v = {"eq": eq, "ne": ~eq, "lt": lt, "le": lt | eq,
                 "gt": ~(lt | eq), "ge": ~lt}[op]
            return _col(ret, v, a, b)
        x, y = _cmp_values(a, b)
        v = {"eq": x == y, "ne": x != y, "lt": x < y,
             "le": x <= y, "gt": x > y, "ge": x >= y}[op]
        return _col(ret, v, a, b)
    return fn


for _opname, _presto in [("eq", "$operator$equal"), ("ne", "$operator$not_equal"),
                         ("lt", "$operator$less_than"),
                         ("le", "$operator$less_than_or_equal"),
                         ("gt", "$operator$greater_than"),
                         ("ge", "$operator$greater_than_or_equal")]:
    _f = _binary_cmp(_opname)
    REGISTRY[_opname] = ScalarFunction(_opname, _f)
    REGISTRY[_presto] = ScalarFunction(_presto, _f)


@register("not")
def _not(ret, a):
    return _col(ret, ~a.values, a)


# ---------------------------------------------------------------------------
# math
# ---------------------------------------------------------------------------

@register("sqrt")
def _sqrt(ret, a):
    (x,) = _promote(ret, a)
    return _col(ret, jnp.sqrt(jnp.maximum(x, 0.0)), a)


@register("floor")
def _floor(ret, a):
    if a.type.is_decimal:
        f = _POW10[a.type.scale]
        v = jnp.where(a.values >= 0, a.values // f, -((-a.values + f - 1) // f))
        return _col(ret, rescale_decimal(v, 0, _scale_of(ret)), a)
    return _col(ret, jnp.floor(a.values.astype(jnp.float64)).astype(ret.to_dtype()), a)


@register("ceil")
@register("ceiling")
def _ceil(ret, a):
    if a.type.is_decimal:
        f = _POW10[a.type.scale]
        v = jnp.where(a.values >= 0, (a.values + f - 1) // f, -((-a.values) // f))
        return _col(ret, rescale_decimal(v, 0, _scale_of(ret)), a)
    return _col(ret, jnp.ceil(a.values.astype(jnp.float64)).astype(ret.to_dtype()), a)


@register("round")
def _round(ret, a, *rest):
    if a.type.is_decimal:
        s = a.type.scale
        if not rest:
            v = rescale_decimal(a.values, s, 0)
            return _col(ret, rescale_decimal(v, 0, _scale_of(ret)), a)
        # round(decimal, d): zero out digits below 10^-d, keep the scale.
        # d must be a compile-time-constant column to stay static; clamp to
        # the useful range and select per-row among the <= s+1 candidates.
        d = rest[0].values.astype(jnp.int32)
        candidates = [rescale_decimal(rescale_decimal(a.values, s, k), k,
                                      _scale_of(ret))
                      for k in range(0, s + 1)]
        v = candidates[-1]
        for k in range(s - 1, -1, -1):
            v = jnp.where(d <= k, candidates[k], v)
        return _col(ret, v, a, rest[0])
    x = a.values.astype(jnp.float64)
    if rest:
        d = rest[0].values.astype(jnp.float64)
        p = jnp.power(10.0, d)
        return _col(ret, jnp.round(x * p) / p, a, rest[0])
    return _col(ret, jnp.round(x).astype(ret.to_dtype()), a)


@register("power")
@register("pow")
def _power(ret, a, b):
    x, y = _promote(ret, a, b)
    return _col(ret, jnp.power(x, y), a, b)


@register("exp")
def _exp(ret, a):
    (x,) = _promote(ret, a)
    return _col(ret, jnp.exp(x), a)


@register("ln")
def _ln(ret, a):
    (x,) = _promote(ret, a)
    return _col(ret, jnp.log(jnp.maximum(x, 1e-300)), a)


@register("log10")
def _log10(ret, a):
    (x,) = _promote(ret, a)
    return _col(ret, jnp.log10(jnp.maximum(x, 1e-300)), a)


@register("greatest")
def _greatest(ret, *args):
    xs = _promote(ret, *args)
    v = xs[0]
    for x in xs[1:]:
        v = jnp.maximum(v, x)
    return _col(ret, v, *args)


@register("least")
def _least(ret, *args):
    xs = _promote(ret, *args)
    v = xs[0]
    for x in xs[1:]:
        v = jnp.minimum(v, x)
    return _col(ret, v, *args)


# ---------------------------------------------------------------------------
# date/time (DATE = days since epoch int32, TIMESTAMP = micros int64)
# civil-from-days per Howard Hinnant's algorithms, vectorized
# ---------------------------------------------------------------------------

def _civil(days):
    z = days.astype(jnp.int64) + 719468
    era = jnp.where(z >= 0, z, z - 146096) // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y, m, d


def _days_from_civil(y, m, d):
    y = y - (m <= 2)
    era = jnp.where(y >= 0, y, y - 399) // 400
    yoe = y - era * 400
    mp = jnp.where(m > 2, m - 3, m + 9)
    doy = (153 * mp + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


def _as_days(a: Column):
    if a.type.base == "timestamp":
        return a.values // 86_400_000_000
    return a.values


def last_day_kernel(y, m):
    """Day-of-month of the last day of civil (y, m) -- the single home of
    the next-month-minus-one trick (used by date_diff's clamp,
    last_day_of_month, and date_add's month arithmetic)."""
    ny = jnp.where(m == 12, y + 1, y)
    nm = jnp.where(m == 12, 1, m + 1)
    return _civil(_days_from_civil(ny, nm, jnp.ones_like(y)) - 1)[2]


@register("year")
def _year(ret, a):
    y, m, d = _civil(_as_days(a))
    return _col(ret, y.astype(ret.to_dtype()), a)


@register("month")
def _month(ret, a):
    y, m, d = _civil(_as_days(a))
    return _col(ret, m.astype(ret.to_dtype()), a)


@register("day")
@register("day_of_month")
def _day(ret, a):
    y, m, d = _civil(_as_days(a))
    return _col(ret, d.astype(ret.to_dtype()), a)


@register("quarter")
def _quarter(ret, a):
    y, m, d = _civil(_as_days(a))
    return _col(ret, ((m - 1) // 3 + 1).astype(ret.to_dtype()), a)


@register("day_of_week")
@register("dow")
def _dow(ret, a):
    days = _as_days(a).astype(jnp.int64)
    # 1970-01-01 was Thursday; ISO dow Mon=1..Sun=7
    v = (days + 3) % 7 + 1
    return _col(ret, v.astype(ret.to_dtype()), a)


@register("day_of_year")
@register("doy")
def _doy(ret, a):
    days = _as_days(a).astype(jnp.int64)
    y, m, d = _civil(days)
    jan1 = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
    return _col(ret, (days - jan1 + 1).astype(ret.to_dtype()), a)


# ---------------------------------------------------------------------------
# strings
# ---------------------------------------------------------------------------

@register("length")
def _length(ret, a: StringColumn):
    return _col(ret, a.lengths.astype(ret.to_dtype()), a)


@register("upper")
def _upper(ret, a: StringColumn):
    c = a.chars
    up = jnp.where((c >= 97) & (c <= 122), c - 32, c)
    return StringColumn(up, a.lengths, a.nulls, ret)


@register("lower")
def _lower(ret, a: StringColumn):
    c = a.chars
    lo = jnp.where((c >= 65) & (c <= 90), c + 32, c)
    return StringColumn(lo, a.lengths, a.nulls, ret)


@register("substr")
def _substr(ret, a: StringColumn, start: Column, *rest):
    """substr(s, start[, length]); 1-based start, negative counts from end."""
    n, w = a.chars.shape
    st0 = start.values.astype(jnp.int32)
    # Presto: start==0 or |negative start| > length -> empty result
    valid = (st0 != 0) & (jnp.where(st0 < 0, -st0, st0) <= a.lengths)
    st = jnp.where(st0 < 0, a.lengths + st0, st0 - 1)  # -> 0-based
    st = jnp.clip(st, 0, a.lengths)
    if rest:
        ln = jnp.clip(rest[0].values.astype(jnp.int32), 0, w)
    else:
        ln = a.lengths - st
    ln = jnp.clip(jnp.minimum(ln, a.lengths - st), 0, w)
    ln = jnp.where(valid, ln, 0)
    idx = st[:, None] + jnp.arange(w, dtype=jnp.int32)[None, :]
    # jax 0.9.0's take_along_axis widens int32 indices to the default
    # int (int64 under x64) before the gather -- its choice, not ours
    gathered = jnp.take_along_axis(a.chars, jnp.clip(idx, 0, w - 1), axis=1)  # kernaudit: disable=K001
    keep = jnp.arange(w, dtype=jnp.int32)[None, :] < ln[:, None]
    out = jnp.where(keep, gathered, 0).astype(jnp.uint8)
    extra = [rest[0]] if rest else []
    return StringColumn(out, ln, _default_nulls(a, start, *extra), ret)


@register("concat")
def _concat(ret, *args: StringColumn):
    out = args[0]
    for b in args[1:]:
        w = out.max_len + b.max_len
        n = out.chars.shape[0]
        pos = jnp.arange(w, dtype=jnp.int32)[None, :]
        l1 = out.lengths[:, None]
        from_first = pos < l1
        ia = jnp.clip(pos, 0, out.max_len - 1)
        ib = jnp.clip(pos - l1, 0, b.max_len - 1)
        ca = jnp.take_along_axis(out.chars, ia, axis=1)
        cb = jnp.take_along_axis(b.chars, ib, axis=1)
        lens = out.lengths + b.lengths
        chars = jnp.where(from_first, ca, jnp.where(pos < lens[:, None], cb, 0))
        out = StringColumn(chars.astype(jnp.uint8), lens,
                           _default_nulls(out, b), ret)
    return out


@register("trim")
def _trim(ret, a: StringColumn):
    c = a.chars
    n, w = c.shape
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    is_sp = (c == 32) | (pos >= a.lengths[:, None])
    first = jnp.argmin(is_sp, axis=1).astype(jnp.int32)  # first non-space
    all_sp = jnp.all(is_sp, axis=1)
    last = (w - 1 - jnp.argmin(is_sp[:, ::-1], axis=1)).astype(jnp.int32)
    st = jnp.where(all_sp, 0, first)
    ln = jnp.where(all_sp, 0, last - first + 1)
    idx = st[:, None] + pos
    g = jnp.take_along_axis(c, jnp.clip(idx, 0, w - 1), axis=1)
    out = jnp.where(pos < ln[:, None], g, 0).astype(jnp.uint8)
    return StringColumn(out, ln, a.nulls, ret)


@register("starts_with")
def _starts_with(ret, a: StringColumn, b: StringColumn):
    # compare b against a's head; pad a if the needle is wider
    wa = a.chars[:, :b.max_len] if b.max_len <= a.max_len else \
        jnp.pad(a.chars, ((0, 0), (0, b.max_len - a.max_len)))
    pos = jnp.arange(b.max_len, dtype=jnp.int32)[None, :]
    cmp = (wa == b.chars) | (pos >= b.lengths[:, None])
    v = jnp.all(cmp, axis=1) & (b.lengths <= a.lengths)
    return _col(ret, v, a, b)


@register("strpos")
def _strpos(ret, a: StringColumn, b: StringColumn):
    """1-based position of first occurrence of b in a, 0 if absent.
    Requires b to be row-constant in practice; implemented generally via
    windows compare."""
    n, w = a.chars.shape
    L = b.max_len
    if L == 0 or L > w:
        return _col(ret, jnp.zeros(n, dtype=ret.to_dtype()), a, b)
    windows = w - L + 1
    idx = (jnp.arange(windows, dtype=jnp.int32)[:, None]
           + jnp.arange(L, dtype=jnp.int32)[None, :])
    g = a.chars[:, idx]  # (N, windows, L)
    pos = jnp.arange(L, dtype=jnp.int32)[None, None, :]
    match = jnp.all((g == b.chars[:, None, :]) | (pos >= b.lengths[:, None, None]),
                    axis=2)
    ok = (jnp.arange(windows, dtype=jnp.int32)[None, :] + b.lengths[:, None]) <= a.lengths[:, None]
    m = match & ok
    found = jnp.any(m, axis=1)
    first = jnp.argmax(m, axis=1).astype(jnp.int64)
    return _col(ret, jnp.where(found, first + 1, 0).astype(ret.to_dtype()), a, b)


@register("sign")
def _sign(ret, a):
    return _col(ret, jnp.sign(a.values).astype(ret.to_dtype()), a)


@register("truncate")
def _truncate(ret, a, *rest):
    if a.type.is_decimal:
        s = a.type.scale
        if not rest:
            f = _POW10[s]
            v = jnp.where(a.values >= 0, a.values // f, -((-a.values) // f))
            return _col(ret, rescale_decimal(v, 0, _scale_of(ret)), a)
        # truncate(decimal, d): zero digits below 10^-d, keep the scale.
        # Negative d zeroes digits LEFT of the point (reference TruncateN);
        # d at or below -(18 - s) truncates everything to 0.
        d = rest[0].values.astype(jnp.int32)

        def trunc_to(k):
            f = _POW10[s - k]
            return jnp.where(a.values >= 0, a.values // f,
                             -((-a.values) // f)) * f
        k_min = -(18 - s)
        ks = list(range(k_min, s + 1))
        candidates = {k: rescale_decimal(trunc_to(k), s, _scale_of(ret))
                      for k in ks}
        out = candidates[ks[-1]]
        for k in reversed(ks[:-1]):
            out = jnp.where(d <= k, candidates[k], out)
        out = jnp.where(d <= k_min, 0, out)  # p - s + d <= 0 -> 0 (TruncateN)
        return _col(ret, out, a, rest[0])
    x = a.values.astype(jnp.float64)
    if rest:
        p = jnp.power(10.0, rest[0].values.astype(jnp.float64))
        return _col(ret, (jnp.trunc(x * p) / p).astype(ret.to_dtype()),
                    a, rest[0])
    return _col(ret, jnp.trunc(x).astype(ret.to_dtype()), a)


REGISTRY["mod"] = REGISTRY["modulus"]


def _null_safe_eq_nulls(ret, a, b):
    return jnp.zeros(len(a), dtype=bool)  # IS [NOT] DISTINCT FROM is never null


@register("is_distinct_from", null_fn=_null_safe_eq_nulls)
def _is_distinct_from(ret, a, b):
    eq = _binary_cmp("eq")(T.BOOLEAN, a, b)
    both_null = a.nulls & b.nulls
    same = both_null | (~a.nulls & ~b.nulls & eq.values)
    return Column(~same, jnp.zeros(len(a), dtype=bool), ret)


@register("is_not_distinct_from", null_fn=_null_safe_eq_nulls)
def _is_not_distinct_from(ret, a, b):
    d = _is_distinct_from(T.BOOLEAN, a, b)
    return Column(~d.values, jnp.zeros(len(a), dtype=bool), ret)


# ---------------------------------------------------------------------------
# more datetime kernels (unit arguments are compile-time constants,
# specialized by the compiler like date_add)
# ---------------------------------------------------------------------------

_DATE_FMT_WIDTHS = {"Y": 4, "y": 2, "m": 2, "d": 2, "H": 2, "i": 2,
                    "s": 2, "j": 3, "%": 1}


def date_format_width(fmt: str) -> int:
    """Output width of a date_format pattern; raises NotImplementedError
    on unsupported specifiers (the validator calls this so unsupported
    formats reject at plan time, not mid-trace). %e (unpadded day) is
    deliberately unsupported: it is variable-width mid-string, which a
    fixed-width char matrix cannot express without per-row shifts."""
    width = 0
    i = 0
    while i < len(fmt):
        if fmt[i] == "%" and i + 1 < len(fmt):
            sp = fmt[i + 1]
            if sp not in _DATE_FMT_WIDTHS:
                raise NotImplementedError(f"date_format %{sp}")
            width += _DATE_FMT_WIDTHS[sp]
            i += 2
        else:
            width += 1
            i += 1
    return max(width, 1)


def date_format_kernel(values, ty, fmt: str):
    """date_format(x, 'mysql-format') -> (chars, lengths); the
    DateTimeFunctions.dateFormat analog with the common specifiers
    (%Y %y %m %d %H %i %s %j), built as fixed-width digit columns
    (strings are (chars, lengths) matrices here, so formatting is pure
    integer arithmetic per output column -- no per-row loop)."""
    if ty.base == "timestamp":
        days = values // 86_400_000_000
        secs_of_day = (values // 1_000_000) % 86_400
    else:
        days = values
        secs_of_day = jnp.zeros_like(values)
    y, m, d = _civil(days)
    hh = secs_of_day // 3600
    mi = (secs_of_day // 60) % 60
    ss = secs_of_day % 60
    jan1 = _days_from_civil(y, jnp.ones_like(m), jnp.ones_like(m))
    doy = (days - jan1 + 1).astype(jnp.int64)

    def digits(v, k):
        return [((v // (10 ** (k - 1 - i))) % 10 + 48).astype(jnp.uint8)
                for i in range(k)]

    cols = []
    i = 0
    n = values.shape[0]
    while i < len(fmt):
        c = fmt[i]
        if c == "%" and i + 1 < len(fmt):
            sp = fmt[i + 1]
            i += 2
            if sp == "Y":
                cols += digits(y, 4)
            elif sp == "y":
                cols += digits(y % 100, 2)
            elif sp == "m":
                cols += digits(m, 2)
            elif sp == "d":
                cols += digits(d, 2)
            elif sp == "H":
                cols += digits(hh, 2)
            elif sp == "i":
                cols += digits(mi, 2)
            elif sp == "s":
                cols += digits(ss, 2)
            elif sp == "j":
                cols += digits(doy, 3)
            elif sp == "%":
                cols.append(jnp.full(n, ord("%"), dtype=jnp.uint8))
            else:
                raise NotImplementedError(f"date_format %{sp}")
        else:
            cols.append(jnp.full(n, ord(c), dtype=jnp.uint8))
            i += 1
    chars = jnp.stack(cols, axis=1)
    lengths = jnp.full(n, chars.shape[1], dtype=jnp.int32)
    return chars, lengths


def date_trunc_kernel(unit: str, days):
    y, m, d = _civil(days)
    one = jnp.ones_like(y)
    if unit == "day":
        return days
    if unit == "week":  # ISO Monday
        return days - (days.astype(jnp.int64) + 3) % 7
    if unit == "month":
        return _days_from_civil(y, m, one)
    if unit == "quarter":
        return _days_from_civil(y, ((m - 1) // 3) * 3 + 1, one)
    if unit == "year":
        return _days_from_civil(y, one, one)
    raise NotImplementedError(f"date_trunc unit {unit!r}")


def date_diff_kernel(unit: str, d1, d2):
    """Presto date_diff(unit, start, end) = end - start in whole units,
    truncated toward zero."""
    if unit == "day":
        return (d2 - d1).astype(jnp.int64)
    if unit == "week":
        delta = (d2 - d1).astype(jnp.int64)
        return jnp.sign(delta) * (jnp.abs(delta) // 7)
    y1, m1, dd1 = _civil(d1)
    y2, m2, dd2 = _civil(d2)
    months = (y2 * 12 + m2) - (y1 * 12 + m1)
    # truncate partial months toward zero, with end-of-month clamping
    # (Joda chronology: Jan 31 + 1 month = Feb 28/29, so Jan 31 ->
    # Feb 29 counts as a whole month)
    eom2 = dd2 == last_day_kernel(y2, m2)
    eom1 = dd1 == last_day_kernel(y1, m1)
    partial_fwd = (dd2 < dd1) & ~eom2
    partial_bwd = (dd2 > dd1) & ~eom1
    adj = jnp.where((months > 0) & partial_fwd, 1,
                    jnp.where((months < 0) & partial_bwd, -1, 0))
    months = months - adj
    if unit == "month":
        return months
    if unit == "quarter":
        return jnp.sign(months) * (jnp.abs(months) // 3)
    if unit == "year":
        return jnp.sign(months) * (jnp.abs(months) // 12)
    raise NotImplementedError(f"date_diff unit {unit!r}")


@register("last_day_of_month")
def _last_day_of_month(ret, a):
    y, m, _ = _civil(_as_days(a))
    v = _days_from_civil(y, m, last_day_kernel(y, m))
    return _col(ret, v.astype(ret.to_dtype()), a)


# ---------------------------------------------------------------------------
# more string kernels
# ---------------------------------------------------------------------------

@register("reverse")
def _reverse(ret, a: StringColumn):
    n, w = a.chars.shape
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    idx = jnp.clip(a.lengths[:, None] - 1 - pos, 0, w - 1)
    out = jnp.take_along_axis(a.chars, idx, axis=1)
    out = jnp.where(pos < a.lengths[:, None], out, 0).astype(jnp.uint8)
    return StringColumn(out, a.lengths, a.nulls, ret)


@register("ltrim")
def _ltrim(ret, a: StringColumn):
    n, w = a.chars.shape
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    is_sp = (a.chars == 32) | (pos >= a.lengths[:, None])
    first = jnp.argmin(is_sp, axis=1).astype(jnp.int32)
    all_sp = jnp.all(is_sp, axis=1)
    st = jnp.where(all_sp, 0, first)
    ln = jnp.where(all_sp, 0, a.lengths - st)
    idx = jnp.clip(st[:, None] + pos, 0, w - 1)
    out = jnp.where(pos < ln[:, None],
                    jnp.take_along_axis(a.chars, idx, axis=1), 0)
    return StringColumn(out.astype(jnp.uint8), ln, a.nulls, ret)


@register("rtrim")
def _rtrim(ret, a: StringColumn):
    n, w = a.chars.shape
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    is_sp = (a.chars == 32) | (pos >= a.lengths[:, None])
    all_sp = jnp.all(is_sp, axis=1)
    last = (w - 1 - jnp.argmin(is_sp[:, ::-1], axis=1)).astype(jnp.int32)
    ln = jnp.where(all_sp, 0, last + 1)
    out = jnp.where(pos < ln[:, None], a.chars, 0)
    return StringColumn(out.astype(jnp.uint8), ln, a.nulls, ret)


@register("chr")
def _chr(ret, a: Column):
    v = jnp.clip(a.values, 0, 255).astype(jnp.uint8)[:, None]
    return StringColumn(v, jnp.ones(len(a), dtype=jnp.int32), a.nulls, ret)


@register("codepoint")
def _codepoint(ret, a: StringColumn):
    v = a.chars[:, 0].astype(ret.to_dtype())
    return _col(ret, v, a)


REGISTRY["position"] = REGISTRY["strpos"]


def split_part_kernel(a: StringColumn, delim: bytes, index: int, ret):
    """split_part(s, delim, n): the n-th (1-based) field. Constant delim
    of length 1 in round 1 (covers the common CSV-ish uses)."""
    assert len(delim) == 1, "split_part delimiter must be 1 byte in round 1"
    assert index >= 1, "split_part index must be greater than zero"
    n, w = a.chars.shape
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    in_str = pos < a.lengths[:, None]
    is_d = (a.chars == delim[0]) & in_str
    field = jnp.cumsum(is_d, axis=1) - is_d.astype(jnp.int32)  # field id per char
    target = index - 1
    sel = (field == target) & ~is_d & in_str
    ln = jnp.sum(sel, axis=1).astype(jnp.int32)
    # start = first position with field==target that's not a delimiter
    has = jnp.any(sel, axis=1)
    st = jnp.argmax(sel, axis=1).astype(jnp.int32)
    idx = jnp.clip(st[:, None] + pos, 0, w - 1)
    g = jnp.take_along_axis(a.chars, idx, axis=1)
    out = jnp.where(pos < ln[:, None], g, 0).astype(jnp.uint8)
    ln = jnp.where(has, ln, 0)
    # index beyond field count -> empty string (Presto returns NULL if
    # index > fields; approximate with NULL via nulls flag)
    nfields = jnp.sum(is_d, axis=1) + 1
    nulls = a.nulls | (index > nfields)
    return StringColumn(out, ln, nulls, ret)


# ---------------------------------------------------------------------------
# casts (one registry entry; dispatch on (from, to))
# ---------------------------------------------------------------------------

@register("try_cast")
def _try_cast(ret, a):
    """TRY_CAST: CAST with out-of-range results becoming NULL instead of
    wrapping. String->number parsing lands with the string-parse
    kernels (clean error until then)."""
    if isinstance(a, StringColumn) and not ret.is_string:
        raise NotImplementedError(
            "TRY_CAST(varchar AS numeric) needs the string-parse kernels "
            "(ROADMAP: function library breadth)")
    out = _cast(ret, a)
    ft = a.type
    if ret.is_integral and (ft.is_integral or ft.is_decimal):
        info = jnp.iinfo(ret.to_dtype())
        src = a.values
        if ft.is_decimal:
            src = rescale_decimal(src.astype(jnp.int64), ft.scale, 0)
        oob = (src.astype(jnp.int64) < info.min) | \
              (src.astype(jnp.int64) > info.max)
        return Column(out.values, out.nulls | oob, ret)
    if ret.is_integral and ft.is_floating:
        info = jnp.iinfo(ret.to_dtype())
        oob = (a.values < float(info.min)) | (a.values > float(info.max)) | \
            jnp.isnan(a.values)
        return Column(out.values, out.nulls | oob, ret)
    return out


@register("cast")
def _cast(ret, a):
    ft = a.type
    if isinstance(a, Int128Column):
        # long decimal -> double / integral / decimal (exact where the
        # target can hold it; double conversion rounds like the
        # reference's Int128 -> double path)
        f = a.hi.astype(jnp.float64) * (2.0 ** 64) + a.lo.astype(jnp.float64)
        if ret.is_floating:
            return _col(ret, f / _POW10[ft.scale], a)
        if ret.is_decimal and _is_long_decimal(ret):
            if ret.scale >= ft.scale:
                hi, lo = I128.rescale128_up(a.hi, a.lo,
                                            10 ** (ret.scale - ft.scale))
                return Int128Column(hi, lo, a.nulls, ret)
            raise NotImplementedError("long-decimal downscale cast")
        if ret.is_decimal or ret.is_integral:
            # narrow through int64 lanes (values must fit; the domain of
            # a query casting down is short by declaration)
            v = a.lo.astype(jnp.int64)
            v = rescale_decimal(v, ft.scale, _scale_of(ret))
            return _col(ret, v.astype(ret.to_dtype()), a)
        raise NotImplementedError(f"cast long decimal -> {ret}")
    if isinstance(a, StringColumn) and not ret.is_string:
        raise NotImplementedError(
            "CAST(varchar AS numeric) needs the string-parse kernels "
            "(ROADMAP: function library breadth)")
    if isinstance(a, StringColumn) and ret.is_string:
        return StringColumn(a.chars, a.lengths, a.nulls, ret)
    if ft == _T_UNKNOWN and ret.is_string:
        # typed NULL literal -> string column of NULLs
        n = len(a)
        return StringColumn(jnp.zeros((n, 1), dtype=jnp.uint8),
                            jnp.zeros(n, dtype=jnp.int32),
                            jnp.ones(n, dtype=bool) | a.nulls, ret)
    if ft.is_decimal and ret.is_floating:
        return _col(ret, a.values.astype(ret.to_dtype()) / _POW10[ft.scale], a)
    if (ft.is_decimal or ft.is_integral) and _is_long_decimal(ret):
        # widen onto int128 lanes, then rescale exactly
        src_scale = ft.scale if ft.is_decimal else 0
        hi, lo = I128.from_int64(a.values.astype(jnp.int64))
        if ret.scale > src_scale:
            hi, lo = I128.rescale128_up(hi, lo,
                                        10 ** (ret.scale - src_scale))
        elif ret.scale < src_scale:
            raise NotImplementedError("long-decimal downscale cast")
        return Int128Column(hi, lo, a.nulls, ret)
    if ft.is_decimal and ret.is_decimal:
        return _col(ret, rescale_decimal(a.values, ft.scale, ret.scale), a)
    if ft.is_decimal and ret.is_integral:
        return _col(ret, rescale_decimal(a.values, ft.scale, 0).astype(ret.to_dtype()), a)
    if ft.is_integral and ret.is_decimal:
        return _col(ret, a.values.astype(jnp.int64) * _POW10[ret.scale], a)
    if ft.is_floating and ret.is_decimal:
        return _col(ret, jnp.round(a.values * _POW10[ret.scale]).astype(jnp.int64), a)
    if ft.is_floating and ret.is_integral:
        return _col(ret, jnp.round(a.values).astype(ret.to_dtype()), a)
    if ft.base == "boolean" and ret.is_numeric:
        return _col(ret, a.values.astype(ret.to_dtype()), a)
    if ft.base == "date" and ret.base == "timestamp":
        return _col(ret, a.values.astype(jnp.int64) * 86_400_000_000, a)
    tzb = "timestamp with time zone"
    if ft.base == tzb and ret.base == "timestamp":
        # the value's local datetime (reference cast semantics)
        return _col(ret, _as_local_micros(a), a)
    if ft.base == tzb and ret.base == "date":
        return _col(ret, (_as_local_micros(a) // 86_400_000_000
                          ).astype(ret.to_dtype()), a)
    if ft.base == tzb and ret.base == "time":
        return _col(ret, _as_local_micros(a) % 86_400_000_000, a)
    if ft.base in ("timestamp", "date") and ret.base == tzb:
        # a naive timestamp is a UTC instant in this engine (session
        # zone = UTC); pack with the UTC key
        from ..tz import UTC_KEY
        us = a.values.astype(jnp.int64) * (86_400_000_000
                                           if ft.base == "date" else 1)
        return _col(ret, (us << 12) | jnp.int64(UTC_KEY), a)
    if ft.base == "timestamp" and ret.base == "time":
        return _col(ret, a.values % 86_400_000_000, a)
    if ft.base == "timestamp" and ret.base == "date":
        return _col(ret, (a.values // 86_400_000_000).astype(ret.to_dtype()),
                    a)
    # plain numeric widening/narrowing
    return _col(ret, a.values.astype(ret.to_dtype()), a)


# ---------------------------------------------------------------------------
# array functions (fixed-fanout ArrayColumn; see block.py)
# ---------------------------------------------------------------------------

@register("cardinality")
def _cardinality(ret, a):
    from ..block import ArrayColumn, MapColumn
    assert isinstance(a, (ArrayColumn, MapColumn))
    return Column(a.lengths.astype(ret.to_dtype()), a.nulls, ret)


@register("element_at")
def _element_at(ret, a, idx: Column):
    """element_at(array, i): 1-based; negative counts from the end;
    out-of-range -> NULL. element_at(map, key): value at key or NULL
    (Presto element_at semantics)."""
    from ..block import ArrayColumn, MapColumn
    if isinstance(a, MapColumn):
        # per-row key probe across the fixed-fanout lanes (K is small:
        # one masked compare + argmax, no gather scatter)
        k = idx.values[:, None]
        lanes = jnp.arange(a.max_cardinality, dtype=jnp.int32)[None, :]
        in_range = lanes < a.lengths[:, None]
        hit = in_range & (a.keys == k)
        has = jnp.any(hit, axis=1)
        j = jnp.argmax(hit, axis=1)
        rows = jnp.arange(len(a), dtype=jnp.int32)
        vals = a.values[rows, j]
        nulls = a.nulls | idx.nulls | ~has | a.value_nulls[rows, j]
        return Column(vals, nulls, ret)
    assert isinstance(a, ArrayColumn)
    i0 = idx.values.astype(jnp.int32)
    pos = jnp.where(i0 < 0, a.lengths + i0, i0 - 1)
    oob = (pos < 0) | (pos >= a.lengths) | (i0 == 0)
    pc = jnp.clip(pos, 0, a.max_cardinality - 1)
    rows = jnp.arange(len(a), dtype=jnp.int32)
    vals = a.elements[rows, pc]
    nulls = a.nulls | idx.nulls | oob | a.elem_nulls[rows, pc]
    return Column(vals, nulls, ret)


@register("row_pack")
def _row_pack(ret, *fields):
    """Pack columns into one ROW-typed column (the wire shape of
    multi-column aggregation intermediate states: avg's (sum, count)
    pair ships as one row(sum_type, bigint) variable, exactly like the
    reference's serialized accumulator states)."""
    from ..block import RowColumn
    n = len(fields[0])
    return RowColumn(tuple(fields), jnp.zeros(n, dtype=bool), ret)


@register("row_field")
def _row_field(ret, r, idx: Column):
    """0-based struct field access (the dereference primitive)."""
    from ..block import RowColumn, gather_block
    assert isinstance(r, RowColumn)
    i = int(np.asarray(idx.values)[0])
    f = r.fields[i]
    # a NULL row nulls every field
    return gather_block(f, jnp.arange(len(r), dtype=jnp.int32), ~r.nulls)


@register("map_keys")
def _map_keys(ret, m):
    from ..block import ArrayColumn, MapColumn
    assert isinstance(m, MapColumn)
    return ArrayColumn(m.keys, jnp.zeros_like(m.value_nulls), m.lengths,
                       m.nulls, ret)


@register("map_values")
def _map_values(ret, m):
    from ..block import ArrayColumn, MapColumn
    assert isinstance(m, MapColumn)
    return ArrayColumn(m.values, m.value_nulls, m.lengths, m.nulls, ret)


@register("contains")
def _contains(ret, a, x: Column):
    from ..block import ArrayColumn
    assert isinstance(a, ArrayColumn)
    k = a.max_cardinality
    in_len = jnp.arange(k, dtype=jnp.int32)[None, :] < a.lengths[:, None]
    eq = (a.elements == x.values[:, None]) & ~a.elem_nulls & in_len
    found = jnp.any(eq, axis=1)
    saw_null = jnp.any(a.elem_nulls & in_len, axis=1)
    nulls = a.nulls | x.nulls | (~found & saw_null)  # NULL-in-array 3VL
    return Column(found & ~nulls, nulls, ret)


@register("array_max")
def _array_max(ret, a):
    from ..block import ArrayColumn
    assert isinstance(a, ArrayColumn)
    k = a.max_cardinality
    in_len = jnp.arange(k, dtype=jnp.int32)[None, :] < a.lengths[:, None]
    live = in_len & ~a.elem_nulls
    ident = jnp.iinfo(jnp.int64).min if not ret.is_floating else -jnp.inf
    v = jnp.max(jnp.where(live, a.elements, ident), axis=1)
    empty = ~jnp.any(live, axis=1)
    return Column(v.astype(ret.to_dtype()), a.nulls | empty, ret)


@register("array_min")
def _array_min(ret, a):
    from ..block import ArrayColumn
    assert isinstance(a, ArrayColumn)
    k = a.max_cardinality
    in_len = jnp.arange(k, dtype=jnp.int32)[None, :] < a.lengths[:, None]
    live = in_len & ~a.elem_nulls
    ident = jnp.iinfo(jnp.int64).max if not ret.is_floating else jnp.inf
    v = jnp.min(jnp.where(live, a.elements, ident), axis=1)
    empty = ~jnp.any(live, axis=1)
    return Column(v.astype(ret.to_dtype()), a.nulls | empty, ret)


# ---------------------------------------------------------------------------
# hashing (for partitioned exchange / group-by; splitmix64 on device)
# ---------------------------------------------------------------------------

# np (not jnp) constants: importing this module must not initialize a
# device backend -- coordinator-side code builds IR without any chip.
_GOLD = np.uint64(0x9E3779B97F4A7C15)
_H1 = np.uint64(0xBF58476D1CE4E5B9)
_H2 = np.uint64(0x94D049BB133111EB)


def _mix64(z):
    z = (z + _GOLD).astype(jnp.uint64)
    z = (z ^ (z >> np.uint64(30))) * _H1
    z = (z ^ (z >> np.uint64(27))) * _H2
    return z ^ (z >> np.uint64(31))


def hash64_block(b: Block):
    """Per-row 64-bit hash of a block (nulls hash to a fixed value),
    the analog of the $hashValue channels HashGenerationOptimizer adds."""
    if isinstance(b, Int128Column):
        h = _mix64(_mix64(b.hi.astype(jnp.uint64)) ^ b.lo)
        return jnp.where(b.nulls, jnp.uint64(0x9E3779B97F4A7C15), h)
    if isinstance(b, StringColumn):
        h = jnp.zeros(b.chars.shape[0], dtype=jnp.uint64)
        # mix 8 chars at a time as a little-endian word. Only words that
        # carry content (i*8 < length) participate, so the hash is
        # WIDTH-INDEPENDENT: equal strings from columns of different
        # declared varchar widths hash identically -- the contract
        # distributed partitioned joins route by.
        w = b.chars.shape[1]
        padded = jnp.pad(b.chars, ((0, 0), (0, (-w) % 8)))
        words = padded.reshape(padded.shape[0], -1, 8).astype(jnp.uint64)
        shifts = (jnp.arange(8, dtype=jnp.uint64) * 8)[None, None, :]
        packed = jnp.sum(words << shifts, axis=2)
        for i in range(packed.shape[1]):
            live = (i * 8) < b.lengths
            h = jnp.where(live, _mix64(h ^ packed[:, i]), h)
        h = _mix64(h ^ b.lengths.astype(jnp.uint64))
    else:
        v = b.values
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.uint64)
        elif v.dtype in (jnp.float32, jnp.float64):
            f = v.astype(jnp.float64)
            f = jnp.where(f == 0.0, 0.0, f)        # -0.0 hashes like 0.0
            f = jnp.where(jnp.isnan(f), jnp.nan, f)  # canonical NaN bits
            v = jax.lax.bitcast_convert_type(f, jnp.uint64)
        else:
            v = v.astype(jnp.int64).astype(jnp.uint64)  # two's-complement wrap
        h = _mix64(v)
    return jnp.where(b.nulls, jnp.uint64(0x9E3779B97F4A7C15), h)


def combine_hash(h1, h2):
    return _mix64(h1 ^ (h2 + _GOLD + (h1 << jnp.uint64(6)) + (h1 >> jnp.uint64(2))))


# ---------------------------------------------------------------------------
# round-4 breadth: trig/log/bitwise/unixtime/array positionals -- each an
# elementwise VPU kernel with the registry's shared null handling
# (reference: operator/scalar/MathFunctions.java, BitwiseFunctions.java,
# DateTimeFunctions.java, ArrayFunctions)
# ---------------------------------------------------------------------------


def _f64(a):
    (x,) = _promote(T.DOUBLE, a)  # descale decimals, widen ints
    return x


def _register_float1(name, fn):
    @register(name)
    def _impl(ret, a, _fn=fn):
        return _col(ret, _fn(_f64(a)), a)
    return _impl


for _name, _fn in [
        ("sin", jnp.sin), ("cos", jnp.cos), ("tan", jnp.tan),
        ("asin", jnp.arcsin), ("acos", jnp.arccos), ("atan", jnp.arctan),
        ("sinh", jnp.sinh), ("cosh", jnp.cosh), ("tanh", jnp.tanh),
        ("cbrt", jnp.cbrt), ("log2", jnp.log2),
        ("degrees", jnp.degrees), ("radians", jnp.radians)]:
    _register_float1(_name, _fn)


@register("atan2")
def _atan2(ret, y, x):
    return _col(ret, jnp.arctan2(_f64(y), _f64(x)), y, x)


@register("log")
def _log(ret, base, x):
    return _col(ret, jnp.log(_f64(x)) / jnp.log(_f64(base)), base, x)


@register("is_nan")
def _is_nan(ret, a):
    return _col(ret, jnp.isnan(_f64(a)), a)


@register("is_finite")
def _is_finite(ret, a):
    return _col(ret, jnp.isfinite(_f64(a)), a)


@register("is_infinite")
def _is_infinite(ret, a):
    return _col(ret, jnp.isinf(_f64(a)), a)


def _bitwise(name, op):
    @register(name)
    def _impl(ret, a, b, _op=op):
        return _col(ret, _op(a.values.astype(jnp.int64),
                             b.values.astype(jnp.int64)), a, b)
    return _impl


_bitwise("bitwise_and", jnp.bitwise_and)
_bitwise("bitwise_or", jnp.bitwise_or)
_bitwise("bitwise_xor", jnp.bitwise_xor)


@register("bitwise_not")
def _bitwise_not(ret, a):
    return _col(ret, ~a.values.astype(jnp.int64), a)


@register("bitwise_left_shift")
def _shl(ret, a, b):
    s = b.values.astype(jnp.int64) & 63  # Java/Presto shift mod 64
    return _col(ret, a.values.astype(jnp.int64) << s, a, b)


@register("bitwise_right_shift")
def _shr(ret, a, b):
    s = b.values.astype(jnp.int64) & 63
    # Presto's logical shift over the 64-bit pattern
    u = a.values.astype(jnp.int64).astype(jnp.uint64)
    return _col(ret, (u >> s.astype(jnp.uint64)).astype(jnp.int64), a, b)


@register("bitwise_right_shift_arithmetic")
def _sar(ret, a, b):
    s = b.values.astype(jnp.int64) & 63
    return _col(ret, a.values.astype(jnp.int64) >> s, a, b)


@register("bit_count")
def _bit_count(ret, a, bits=None):
    u = a.values.astype(jnp.int64).astype(jnp.uint64)
    if bits is not None:
        width = bits.values.astype(jnp.uint64)
        mask = jnp.where(width >= jnp.uint64(64),
                         jnp.uint64(0xFFFFFFFFFFFFFFFF),
                         (jnp.uint64(1) << width) - jnp.uint64(1))
        u = u & mask
    cnt = jax.lax.population_count(u).astype(jnp.int64)
    return _col(ret, cnt, a) if bits is None else _col(ret, cnt, a, bits)


@register("from_unixtime")
def _from_unixtime(ret, a):
    # seconds (possibly fractional) -> TIMESTAMP micros
    us = (_f64(a) * 1e6)
    return _col(ret, jnp.round(us).astype(jnp.int64), a)


@register("to_unixtime")
def _to_unixtime(ret, a):
    return _col(ret, a.values.astype(jnp.float64) / 1e6, a)


@register("ends_with")
def _ends_with(ret, a: StringColumn, b: StringColumn):
    # gather each row's suffix window of b.max_len chars, compare to b;
    # pad the haystack when the needle BATCH is wider (a short needle in
    # a wide column must still match -- same padding as starts_with)
    chars = a.chars
    L = b.max_len
    if L == 0:
        return _col(ret, b.lengths == 0, a, b)
    if L > chars.shape[1]:
        chars = jnp.pad(chars, ((0, 0), (0, L - chars.shape[1])))
    w = chars.shape[1]
    starts = jnp.clip(a.lengths - b.lengths, 0, w - 1)
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    idx = jnp.clip(starts[:, None] + pos, 0, w - 1)
    window = jnp.take_along_axis(chars, idx, axis=1)
    cmp = (window == b.chars[:, :L]) | (pos >= b.lengths[:, None])
    v = jnp.all(cmp, axis=1) & (b.lengths <= a.lengths)
    return _col(ret, v, a, b)


@register("array_position")
def _array_position(ret, a, x: Column):
    """1-based index of the first element equal to x; 0 if absent."""
    from ..block import ArrayColumn
    assert isinstance(a, ArrayColumn)
    lanes = jnp.arange(a.max_cardinality, dtype=jnp.int64)[None, :]
    in_range = lanes < a.lengths[:, None]
    hit = in_range & ~a.elem_nulls & (a.elements == x.values[:, None])
    has = jnp.any(hit, axis=1)
    first = jnp.argmax(hit, axis=1).astype(jnp.int64)
    return _col(ret, jnp.where(has, first + 1, 0), a, x)


@register("array_sum")
def _array_sum(ret, a):
    from ..block import ArrayColumn
    assert isinstance(a, ArrayColumn)
    lanes = jnp.arange(a.max_cardinality, dtype=jnp.int64)[None, :]
    live = (lanes < a.lengths[:, None]) & ~a.elem_nulls
    dt = jnp.float64 if ret.is_floating else jnp.int64
    s = jnp.sum(jnp.where(live, a.elements.astype(dt), dt(0)), axis=1)
    return _col(ret, s, a)


# ---------------------------------------------------------------------------
# zoned timestamps, TIME, intervals (types TIMESTAMP_TZ / TIME /
# INTERVAL_YM / INTERVAL_DS; packing in tz.py)
#
# Reference surface: presto-main-base/.../operator/scalar/DateTimeFunctions.java
# and presto-common/.../type/TimestampWithTimeZoneType.java. Field
# extraction and calendar arithmetic operate on the value's own wall
# clock (local micros); comparisons/keys use the instant (keys.py).
# ---------------------------------------------------------------------------

_DAY_US = 86_400_000_000
_TZ_BASE = "timestamp with time zone"


def _as_local_micros(a: Column):
    """Wall-clock micros of a date/time/timestamp/timestamptz block."""
    base = a.type.base
    if base == _TZ_BASE:
        from ..tz import local_micros
        return local_micros(a.values)
    if base == "date":
        return a.values.astype(jnp.int64) * _DAY_US
    return a.values.astype(jnp.int64)  # timestamp (epoch) / time (midnight)


def _instant_micros(a: Column):
    base = a.type.base
    if base == _TZ_BASE:
        return a.values >> 12
    if base == "date":
        return a.values.astype(jnp.int64) * _DAY_US
    return a.values.astype(jnp.int64)


def _register_tod_field(name, divisor, modulus):
    @register(name)
    def _field(ret, a, _d=divisor, _m=modulus):
        us = _as_local_micros(a) % _DAY_US
        return _col(ret, ((us // _d) % _m).astype(ret.to_dtype()), a)
    return _field


_register_tod_field("hour", 3_600_000_000, 24)
_register_tod_field("minute", 60_000_000, 60)
_register_tod_field("second", 1_000_000, 60)
_register_tod_field("millisecond", 1_000, 1000)


@register("timezone_hour")
def _timezone_hour(ret, a):
    from ..tz import UTC_KEY
    assert a.type.base == _TZ_BASE, \
        f"timezone_hour needs timestamp with time zone, got {a.type}"
    minutes = (a.values & jnp.int64(0xFFF)) - UTC_KEY
    h = jnp.sign(minutes) * (jnp.abs(minutes) // 60)  # truncate to zero
    return _col(ret, h.astype(ret.to_dtype()), a)


@register("timezone_minute")
def _timezone_minute(ret, a):
    from ..tz import UTC_KEY
    assert a.type.base == _TZ_BASE, \
        f"timezone_minute needs timestamp with time zone, got {a.type}"
    minutes = (a.values & jnp.int64(0xFFF)) - UTC_KEY
    return _col(ret, jnp.sign(minutes) * (jnp.abs(minutes) % 60), a)


def _month_add(days, months):
    """Calendar month arithmetic with end-of-month clamping (the
    date_add month-path rule, shared here with interval arithmetic)."""
    y, m, d = _civil(days)
    tot = (y * 12 + (m - 1)) + months
    ny, nm = tot // 12, tot % 12 + 1
    nd = jnp.minimum(d, last_day_kernel(ny, nm))
    return _days_from_civil(ny, nm, nd)


@register("datetime_interval_add")
def _datetime_interval_add(ret, a, b):
    """datetime-typed a + interval-typed b (subtraction negates b in
    the planner). DS intervals shift the instant; YM intervals do
    calendar month math on the value's wall clock."""
    base = a.type.base
    if b.type.base == "interval day to second":
        if base == _TZ_BASE:
            v = (((a.values >> 12) + b.values) << 12) | \
                (a.values & jnp.int64(0xFFF))
        elif base == "date":
            v = a.values.astype(jnp.int64) * _DAY_US + b.values
            if ret.base == "date":
                v = v // _DAY_US
        elif base == "time":
            v = (a.values + b.values) % _DAY_US
        else:
            v = a.values + b.values
        return _col(ret, v.astype(ret.to_dtype()), a, b)
    months = b.values
    if base == "date":
        v = _month_add(a.values.astype(jnp.int64), months)
    elif base == "timestamp":
        days, tod = a.values // _DAY_US, a.values % _DAY_US
        v = _month_add(days, months) * _DAY_US + tod
    elif base == _TZ_BASE:
        from ..tz import MICROS_PER_MINUTE, UTC_KEY
        key = a.values & jnp.int64(0xFFF)
        off = (key - UTC_KEY) * MICROS_PER_MINUTE
        local = (a.values >> 12) + off
        days, tod = local // _DAY_US, local % _DAY_US
        nlocal = _month_add(days, months) * _DAY_US + tod
        v = ((nlocal - off) << 12) | key
    else:
        raise NotImplementedError(f"{base} + year-month interval")
    return _col(ret, v.astype(ret.to_dtype()), a, b)


@register("datetime_diff_micros")
def _datetime_diff_micros(ret, a, b):
    """a - b as INTERVAL DAY TO SECOND (micros), instants compared."""
    return _col(ret, _instant_micros(a) - _instant_micros(b), a, b)


# ---------------------------------------------------------------------------
# VARBINARY (uint8 rows in the string layout)
# Reference: operator/scalar/VarbinaryFunctions.java
# ---------------------------------------------------------------------------

def _hex_digit(d):
    return jnp.where(d < 10, d + ord("0"), d - 10 + ord("A")).astype(jnp.uint8)


@register("to_hex")
def _to_hex(ret, a: StringColumn):
    n, w = a.chars.shape
    chars = jnp.stack([_hex_digit(a.chars >> 4), _hex_digit(a.chars & 0xF)],
                      axis=2).reshape(n, 2 * w)
    return StringColumn(chars, a.lengths * 2, a.nulls, ret)


@register("from_hex", null_fn=lambda ret, *b: None)
def _from_hex(ret, a: StringColumn):
    n, w = a.chars.shape
    chars = jnp.pad(a.chars, ((0, 0), (0, w % 2)))
    c = chars.astype(jnp.int32)
    digit = jnp.where(c >= ord("a"), c - ord("a") + 10,
                      jnp.where(c >= ord("A"), c - ord("A") + 10,
                                c - ord("0")))
    lanes = jnp.arange(chars.shape[1], dtype=jnp.int32)[None, :]
    in_len = lanes < a.lengths[:, None]
    ok_digit = (digit >= 0) & (digit <= 15) | ~in_len
    # invalid hex (odd length, non-hex chars) -> NULL ("errors produce
    # NULL lanes" -- the engine's total-kernel contract; Presto raises)
    invalid = (a.lengths % 2 != 0) | ~jnp.all(ok_digit, axis=1)
    pairs = digit.reshape(n, -1, 2)
    vals = (pairs[:, :, 0] * 16 + pairs[:, :, 1]).astype(jnp.uint8)
    return StringColumn(vals, jnp.where(invalid, 0, a.lengths // 2),
                        a.nulls | invalid, ret)


@register("to_utf8")
def _to_utf8(ret, a: StringColumn):
    return StringColumn(a.chars, a.lengths, a.nulls, ret)


@register("from_utf8")
def _from_utf8(ret, a: StringColumn):
    return StringColumn(a.chars, a.lengths, a.nulls, ret)


# ---------------------------------------------------------------------------
# host-row kernels: irregular-grammar functions (JSON, regex capture,
# cryptographic digests) run per-row on the HOST via jax.pure_callback
# with static output shapes -- the same work the reference does row-wise
# in Java (JsonFunctions.java, RegexpFunctions re2, VarbinaryFunctions
# digests). The device pipeline stays jit'd; these lanes round-trip
# through host DRAM. A Pallas JSON scanner is the planned upgrade for
# the hot paths.
# ---------------------------------------------------------------------------

def _rows_of(block):
    """Host-side decode plan for one block: returns (operands, reader)
    where reader(row_index, *host_arrays) -> python value or None."""
    if isinstance(block, StringColumn):
        ops = (block.chars, block.lengths, block.nulls)

        def read(i, chars, lengths, nulls):
            if nulls[i]:
                return None
            return bytes(chars[i, :lengths[i]])
        return ops, read
    ops = (block.values, block.nulls)

    def read(i, values, nulls):
        return None if nulls[i] else values[i].item()
    return ops, read


def host_string_kernel(py_fn, ret: T.Type, out_width: int, *blocks):
    """Apply py_fn(*row_values) -> bytes|str|None per row, returning a
    StringColumn of static width `out_width` (overlong results are an
    engine limit: raised, not truncated)."""
    n = len(blocks[0])
    out_width = max(int(out_width), 1)
    plans = [_rows_of(b) for b in blocks]
    counts = [len(p[0]) for p in plans]

    def host(*arrs):
        chars = np.zeros((n, out_width), dtype=np.uint8)
        lengths = np.zeros(n, dtype=np.int32)
        nulls = np.ones(n, dtype=bool)
        split = []
        k = 0
        for c in counts:
            split.append(arrs[k:k + c])
            k += c
        for i in range(n):
            vals = [p[1](i, *s) for p, s in zip(plans, split)]
            if any(v is None for v in vals):
                continue
            try:
                r = py_fn(*vals)
            except Exception:  # noqa: BLE001 - row error -> SQL NULL
                continue
            if r is None:
                continue
            if isinstance(r, str):
                r = r.encode("utf-8")
            if len(r) > out_width:
                raise ValueError(
                    f"host kernel result exceeds static width {out_width}")
            chars[i, :len(r)] = np.frombuffer(r, dtype=np.uint8)
            lengths[i] = len(r)
            nulls[i] = False
        return chars, lengths, nulls

    shapes = (jax.ShapeDtypeStruct((n, out_width), np.uint8),
              jax.ShapeDtypeStruct((n,), np.int32),
              jax.ShapeDtypeStruct((n,), np.bool_))
    ops = [x for p in plans for x in p[0]]
    chars, lengths, nulls = jax.pure_callback(host, shapes, *ops)
    return StringColumn(chars, lengths, nulls, ret)


def host_scalar_kernel(py_fn, ret: T.Type, *blocks):
    """Apply py_fn(*row_values) -> int|float|bool|None per row,
    returning a fixed-width Column."""
    n = len(blocks[0])
    dt = ret.to_dtype()
    plans = [_rows_of(b) for b in blocks]
    counts = [len(p[0]) for p in plans]

    def host(*arrs):
        values = np.zeros(n, dtype=dt)
        nulls = np.ones(n, dtype=bool)
        split = []
        k = 0
        for c in counts:
            split.append(arrs[k:k + c])
            k += c
        for i in range(n):
            vals = [p[1](i, *s) for p, s in zip(plans, split)]
            if any(v is None for v in vals):
                continue
            try:
                r = py_fn(*vals)
            except Exception:  # noqa: BLE001
                continue
            if r is None:
                continue
            values[i] = r
            nulls[i] = False
        return values, nulls

    shapes = (jax.ShapeDtypeStruct((n,), dt),
              jax.ShapeDtypeStruct((n,), np.bool_))
    ops = [x for p in plans for x in p[0]]
    values, nulls = jax.pure_callback(host, shapes, *ops)
    return Column(values, nulls, ret)


def _host_nulls(ret, *blocks):
    """null_fn for host kernels: the kernel computes its own null mask
    (row errors and absent paths are NULL, not just null inputs)."""
    return None


# -- JSON ------------------------------------------------------------------

def _json_loads(doc: bytes):
    import json as _json
    return _json.loads(doc.decode("utf-8"))


def _json_dumps(v) -> str:
    import json as _json
    return _json.dumps(v, separators=(",", ":"), ensure_ascii=False)


def _json_path_get(v, path: bytes):
    """Tiny JsonPath subset: $, $.key, $["key"], $[idx], chained."""
    import re as _re
    p = path.decode("utf-8").strip()
    if not p.startswith("$"):
        raise ValueError(f"bad json path {p!r}")
    pos = 1
    steps = []
    token = _re.compile(
        r"\.(\*|[A-Za-z_][A-Za-z_0-9]*)|\[\s*(\d+)\s*\]|\[\s*\"([^\"]*)\"\s*\]")
    while pos < len(p):
        m = token.match(p, pos)
        if m is None:
            raise ValueError(f"bad json path {p!r}")
        if m.group(1) is not None:
            steps.append(("key", m.group(1)))
        elif m.group(2) is not None:
            steps.append(("idx", int(m.group(2))))
        else:
            steps.append(("key", m.group(3)))
        pos = m.end()
    for kind, s in steps:
        if kind == "key":
            if not isinstance(v, dict) or s not in v:
                return None, False
            v = v[s]
        else:
            if not isinstance(v, list) or s >= len(v):
                return None, False
            v = v[s]
    return v, True


def _json_width(blocks) -> int:
    return max(int(b.chars.shape[1]) for b in blocks
               if isinstance(b, StringColumn))


# canonicalization can LENGTHEN text (e.g. '1e2' -> '100.0', escapes
# expanding): budget 6x input + slack, measured against repr() float
# expansion worst cases
def _json_out_width(a: StringColumn) -> int:
    return 6 * int(a.chars.shape[1]) + 16


@register("json_parse", null_fn=_host_nulls)
def _json_parse(ret, a: StringColumn):
    return host_string_kernel(lambda d: _json_dumps(_json_loads(d)), ret,
                              _json_out_width(a), a)


@register("json_format", null_fn=_host_nulls)
def _json_format(ret, a: StringColumn):
    return host_string_kernel(lambda d: d, ret, a.chars.shape[1], a)


@register("json_extract", null_fn=_host_nulls)
def _json_extract(ret, a: StringColumn, p: StringColumn):
    def fn(doc, path):
        v, ok = _json_path_get(_json_loads(doc), path)
        return _json_dumps(v) if ok else None
    return host_string_kernel(fn, ret, _json_out_width(a), a, p)


@register("json_extract_scalar", null_fn=_host_nulls)
def _json_extract_scalar(ret, a: StringColumn, p: StringColumn):
    def fn(doc, path):
        v, ok = _json_path_get(_json_loads(doc), path)
        if not ok or isinstance(v, (dict, list)) or v is None:
            return None
        if isinstance(v, bool):
            return "true" if v else "false"
        if isinstance(v, float) and v == int(v):
            return _json_dumps(v)
        return str(v)
    return host_string_kernel(fn, ret, _json_out_width(a), a, p)


@register("json_array_length", null_fn=_host_nulls)
def _json_array_length(ret, a: StringColumn):
    def fn(doc):
        v = _json_loads(doc)
        return len(v) if isinstance(v, list) else None
    return host_scalar_kernel(fn, ret, a)


@register("json_size", null_fn=_host_nulls)
def _json_size(ret, a: StringColumn, p: StringColumn):
    def fn(doc, path):
        v, ok = _json_path_get(_json_loads(doc), path)
        if not ok:
            return None
        return len(v) if isinstance(v, (dict, list)) else 0
    return host_scalar_kernel(fn, ret, a, p)


@register("json_array_contains", null_fn=_host_nulls)
def _json_array_contains(ret, a: StringColumn, x):
    def fn(doc, needle):
        v = _json_loads(doc)
        if not isinstance(v, list):
            return None
        if isinstance(needle, bytes):
            return needle.decode("utf-8") in \
                [x_ for x_ in v if isinstance(x_, str)]
        if isinstance(needle, bool) or isinstance(needle, np.bool_):
            return any(x_ is bool(needle) for x_ in v)
        # numeric needle matches JSON numbers only (never booleans)
        return any(x_ == needle for x_ in v
                   if isinstance(x_, (int, float))
                   and not isinstance(x_, bool))
    return host_scalar_kernel(fn, ret, a, x)


@register("is_json_scalar", null_fn=_host_nulls)
def _is_json_scalar(ret, a: StringColumn):
    def fn(doc):
        return not isinstance(_json_loads(doc), (dict, list))
    return host_scalar_kernel(fn, ret, a)


# -- regex capture / replace (host; regexp_like has the on-device DFA) ----

@register("regexp_extract", null_fn=_host_nulls)
def _regexp_extract(ret, a: StringColumn, p: StringColumn, *group):
    import re as _re

    def fn(s, pat, g=1 if group else 0):
        m = _re.search(pat.decode("utf-8"), s.decode("utf-8"))
        if m is None:
            return None
        return m.group(g)
    if group:
        def fn(s, pat, g):  # noqa: F811 - group-index overload
            m = _re.search(pat.decode("utf-8"), s.decode("utf-8"))
            return None if m is None else m.group(int(g))
        return host_string_kernel(fn, ret, a.chars.shape[1], a, p, group[0])
    return host_string_kernel(fn, ret, a.chars.shape[1], a, p)


@register("regexp_position", null_fn=_host_nulls)
def _regexp_position(ret, a: StringColumn, p: StringColumn):
    import re as _re

    def fn(s, pat):
        m = _re.search(pat.decode("utf-8"), s.decode("utf-8"))
        return -1 if m is None else m.start() + 1
    return host_scalar_kernel(fn, ret, a, p)


@register("regexp_count", null_fn=_host_nulls)
def _regexp_count(ret, a: StringColumn, p: StringColumn):
    import re as _re

    def fn(s, pat):
        return sum(1 for _ in _re.finditer(pat.decode("utf-8"),
                                           s.decode("utf-8")))
    return host_scalar_kernel(fn, ret, a, p)


# -- digests ---------------------------------------------------------------

def _register_digest(name, width):
    @register(name, null_fn=_host_nulls)
    def _digest(ret, a: StringColumn, _n=name):
        import hashlib

        def fn(data):
            return getattr(hashlib, _n)(data).digest()
        return host_string_kernel(fn, ret, width, a)
    return _digest


_register_digest("md5", 16)
_register_digest("sha1", 20)
_register_digest("sha256", 32)
_register_digest("sha512", 64)


@register("crc32")
def _crc32(ret, a: StringColumn):
    import zlib

    def fn(data):
        return zlib.crc32(data)
    return host_scalar_kernel(fn, ret, a)


# ---------------------------------------------------------------------------
# array algebra (ArrayDistinctFunction / ArraySortFunction / ArraySliceFunction)
# ---------------------------------------------------------------------------

def _arr_in_range(a):
    lanes = jnp.arange(a.max_cardinality, dtype=jnp.int32)[None, :]
    return lanes < a.lengths[:, None]


@register("array_sort")
def _array_sort(ret, a):
    """Per-row ascending sort, NULL elements last (reference default)."""
    from ..block import ArrayColumn
    assert isinstance(a, ArrayColumn)
    in_range = _arr_in_range(a)
    dead = ~in_range | a.elem_nulls
    v = a.elements
    if v.dtype in (jnp.float32, jnp.float64):
        key = jnp.where(dead, jnp.inf, v)
    else:
        key = jnp.where(dead, jnp.iinfo(v.dtype).max, v)
    # two-key sort (lane class, then value) via two stable argsort
    # passes: class 0 = live value, 1 = NULL element, 2 = padding --
    # values ascend, nulls follow, padding stays at the tail
    cls = jnp.where(in_range & ~a.elem_nulls, 0,
                    jnp.where(in_range, 1, 2))
    o1 = jnp.argsort(key, axis=1, stable=True)
    o2 = jnp.argsort(jnp.take_along_axis(cls, o1, axis=1), axis=1,
                     stable=True)
    order = jnp.take_along_axis(o1, o2, axis=1)
    return ArrayColumn(jnp.take_along_axis(a.elements, order, axis=1),
                       jnp.take_along_axis(a.elem_nulls, order, axis=1),
                       a.lengths, a.nulls, ret)


@register("array_distinct")
def _array_distinct(ret, a):
    """First occurrence of each distinct element (NULL counts once)."""
    from ..block import ArrayColumn
    assert isinstance(a, ArrayColumn)
    in_range = _arr_in_range(a)
    v = a.elements
    eq = (v[:, :, None] == v[:, None, :]) & \
        ~a.elem_nulls[:, :, None] & ~a.elem_nulls[:, None, :]
    both_null = a.elem_nulls[:, :, None] & a.elem_nulls[:, None, :]
    same = (eq | both_null) & in_range[:, :, None] & in_range[:, None, :]
    k = a.max_cardinality
    earlier = jnp.tril(jnp.ones((k, k), dtype=bool), k=-1)[None, :, :]
    dup = jnp.any(same & earlier, axis=2)  # dup[j] = any l<j equal
    keep = in_range & ~dup
    order = jnp.argsort(~keep, axis=1, stable=True)
    return ArrayColumn(jnp.take_along_axis(v, order, axis=1),
                       jnp.take_along_axis(a.elem_nulls, order, axis=1),
                       jnp.sum(keep, axis=1).astype(a.lengths.dtype),
                       a.nulls, ret)


@register("slice")
def _array_slice(ret, a, start: Column, length: Column):
    """slice(arr, start, length): 1-based start; negative counts from
    the end (reference ArraySliceFunction)."""
    from ..block import ArrayColumn
    assert isinstance(a, ArrayColumn)
    k = a.max_cardinality
    lens = a.lengths.astype(jnp.int64)
    s = start.values.astype(jnp.int64)
    s0 = jnp.where(s > 0, s - 1, lens + s)  # 0-based start
    cnt = jnp.clip(length.values.astype(jnp.int64), 0, None)
    s0c = jnp.clip(s0, 0, k)
    new_len = jnp.where(s0 < 0, 0,  # |negative start| > length: empty
                        jnp.clip(jnp.minimum(cnt, lens - s0c), 0, None))
    lanes = jnp.arange(k, dtype=jnp.int64)[None, :]
    idx = jnp.clip(s0c[:, None] + lanes, 0, k - 1).astype(jnp.int32)
    # start index 0 is invalid (SQL arrays are 1-based; the reference
    # raises) -- total kernels surface it as NULL
    nulls = _default_nulls(a, start, length) | (s == 0)
    return ArrayColumn(jnp.take_along_axis(a.elements, idx, axis=1),
                       jnp.take_along_axis(a.elem_nulls, idx, axis=1),
                       new_len.astype(a.lengths.dtype), nulls, ret)


# ---------------------------------------------------------------------------
# geospatial scalars (the coordinate-native slice of presto-geospatial:
# GeoFunctions.great_circle_distance + BingTileFunctions.bing_tile_at /
# bing_tile_quadkey. Geometry-typed functions (WKT parsing, spatial
# joins, R-trees) are outside this engine's current type surface --
# these are the functions whose inputs are plain doubles, which
# vectorize onto the VPU directly.)
# ---------------------------------------------------------------------------

_EARTH_RADIUS_KM = 6371.01


def decimal_to_f64(b):
    """Any numeric block's lanes as float64 (decimals unscale) -- the
    ONE home of the scaled-int conversion (aggregation's moment
    kernels and the geo functions share it)."""
    f = b.values.astype(jnp.float64)
    if b.type.is_decimal:
        f = f / _POW10[b.type.scale]
    return f


_geo_f64 = decimal_to_f64  # coordinate lanes in degrees


@register("great_circle_distance")
def _great_circle_distance(ret, lat1, lon1, lat2, lon2):
    """Haversine distance in KILOMETERS between two (lat, lon) points
    in degrees (GeoFunctions.stDistance's spherical sibling; same
    radius constant as the reference)."""
    to_rad = jnp.pi / 180.0
    p1 = _geo_f64(lat1) * to_rad
    p2 = _geo_f64(lat2) * to_rad
    dphi = p2 - p1
    dlam = (_geo_f64(lon2) - _geo_f64(lon1)) * to_rad
    a = jnp.sin(dphi / 2.0) ** 2 + \
        jnp.cos(p1) * jnp.cos(p2) * jnp.sin(dlam / 2.0) ** 2
    d = 2.0 * _EARTH_RADIUS_KM * jnp.arcsin(jnp.sqrt(jnp.clip(a, 0.0, 1.0)))
    return _col(ret, d, lat1, lon1, lat2, lon2)


def _bing_xy(lat, lon, zoom):
    """(lat, lon, zoom) -> integer tile (x, y) lanes (the Bing tile
    system's Mercator mapping; BingTileUtils.latitudeLongitudeToTile)."""
    lat = jnp.clip(lat.astype(jnp.float64), -85.05112878, 85.05112878)
    lon = jnp.clip(lon.astype(jnp.float64), -180.0, 180.0)
    sin_lat = jnp.sin(lat * jnp.pi / 180.0)
    x_frac = (lon + 180.0) / 360.0
    y_frac = 0.5 - jnp.log((1.0 + sin_lat) / (1.0 - sin_lat)) \
        / (4.0 * jnp.pi)
    size = (jnp.int64(1) << zoom.astype(jnp.int64)).astype(jnp.float64)
    tx = jnp.clip(jnp.floor(x_frac * size), 0, size - 1).astype(jnp.int64)
    ty = jnp.clip(jnp.floor(y_frac * size), 0, size - 1).astype(jnp.int64)
    return tx, ty


def _zoom_ok(zoom):
    """The Bing system's zoom domain is 0..23 (BingTileUtils raises
    outside it; total kernels surface NULL instead)."""
    z = zoom.values.astype(jnp.int64)
    return (z >= 0) & (z <= 23)


@register("bing_tile_x", null_fn=lambda ret, *b: None)
def _bing_tile_x(ret, lat, lon, zoom):
    zc = jnp.clip(zoom.values.astype(jnp.int64), 0, 23)
    tx, _ = _bing_xy(_geo_f64(lat), _geo_f64(lon), zc)
    return Column(tx, _default_nulls(lat, lon, zoom) | ~_zoom_ok(zoom),
                  ret)


@register("bing_tile_y", null_fn=lambda ret, *b: None)
def _bing_tile_y(ret, lat, lon, zoom):
    zc = jnp.clip(zoom.values.astype(jnp.int64), 0, 23)
    _, ty = _bing_xy(_geo_f64(lat), _geo_f64(lon), zc)
    return Column(ty, _default_nulls(lat, lon, zoom) | ~_zoom_ok(zoom),
                  ret)


@register("bing_tile_quadkey_at", null_fn=lambda ret, *b: None)
def _bing_tile_quadkey_at(ret, lat, lon, zoom):
    """Quadkey string of the tile containing (lat, lon) at `zoom`
    (bing_tile_quadkey(bing_tile_at(...)) fused -- the tile OBJECT type
    is not surfaced; the quadkey digits build as vector lanes)."""
    z = jnp.clip(zoom.values.astype(jnp.int64), 0, 23)
    tx, ty = _bing_xy(_geo_f64(lat), _geo_f64(lon), z)
    n = len(lat)
    maxz = 23  # the Bing system's max zoom (BingTileUtils.MAX_ZOOM_LEVEL)
    chars = jnp.zeros((n, maxz), dtype=jnp.uint8)
    for i in range(maxz):
        # digit i of the quadkey reads bit (z-1-i) of x and y
        bit = z - 1 - i
        valid = bit >= 0
        b = jnp.clip(bit, 0, 62)
        digit = ((tx >> b) & 1) | (((ty >> b) & 1) << 1)
        chars = chars.at[:, i].set(
            jnp.where(valid, digit + ord("0"), 0).astype(jnp.uint8))
    lengths = jnp.clip(z, 0, maxz).astype(jnp.int32)
    return StringColumn(chars, lengths,
                        _default_nulls(lat, lon, zoom)
                        | ~_zoom_ok(zoom), ret)
