"""Expression lowering: IR -> pure JAX functions over Columns.

Spark 3-valued null logic is carried as (data, validity) pairs.
Invariants:

- a column's data in *invalid* rows may be garbage; every lowering must
  be garbage-safe (logic ops mask by validity, divisions use safe
  divisors, aggregations mask).
- padding rows are invalid, so kernels need no separate padding mask.

Division semantics are Spark non-ANSI: x/0 -> null, int `/` -> double,
decimal `/` -> decimal with Spark's result scale.  Decimal multiply /
divide / rescale beyond int64 range run on exact two-limb int128
(``exprs/int128.py``) with HALF_UP rounding — the same arithmetic the
reference gets from Arrow decimal128 (cast.rs, check_overflow).
"""

from __future__ import annotations

import datetime
import logging
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..batch import Column
from ..schema import (
    DataType,
    Schema,
    TypeKind,
    decimal_add_type,
    decimal_div_type,
    decimal_mul_type,
    string_width_for,
)
from . import strings as S
from .cast import decimal_overflow_null, lower_cast, rescale_decimal
from .ir import (
    Alias,
    BinOp,
    Case,
    Cast,
    Col,
    Expr,
    GetIndexedField,
    GetMapValue,
    GetStructField,
    InList,
    IsNotNull,
    IsNull,
    Like,
    Lit,
    NamedStruct,
    Not,
    ScalarFunc,
    Slot,
)

_RANK = {
    TypeKind.INT8: 0,
    TypeKind.INT16: 1,
    TypeKind.INT32: 2,
    TypeKind.INT64: 3,
    TypeKind.FLOAT32: 4,
    TypeKind.FLOAT64: 5,
}
_INT_DECIMAL_PRECISION = {
    TypeKind.BOOL: 1,
    TypeKind.INT8: 3,
    TypeKind.INT16: 5,
    TypeKind.INT32: 10,
    TypeKind.INT64: 20,
}

_CMP_OPS = ("==", "!=", "<", "<=", ">", ">=")
_LOGIC_OPS = ("and", "or")
_ARITH_OPS = ("+", "-", "*", "/", "%")


# ------------------------------------------------------------- inference

def infer_lit_dtype(value, dtype: Optional[DataType]) -> DataType:
    if dtype is not None:
        return dtype
    if value is None:
        return DataType.null()
    if isinstance(value, bool):
        return DataType.bool_()
    if isinstance(value, int):
        return DataType.int32() if -(2**31) <= value < 2**31 else DataType.int64()
    if isinstance(value, float):
        return DataType.float64()
    if isinstance(value, str):
        return DataType.string(string_width_for(len(value.encode("utf-8"))))
    if isinstance(value, bytes):
        return DataType.binary(string_width_for(len(value)))
    if isinstance(value, datetime.date):
        return DataType.date32()
    raise TypeError(f"cannot infer literal type of {value!r}")


def _common_type(a: DataType, b: DataType) -> DataType:
    if a == b:
        return a
    if a.kind == TypeKind.NULL:
        return b
    if b.kind == TypeKind.NULL:
        return a
    if a.is_string and b.is_string:
        return DataType.string(max(a.string_width, b.string_width))
    if a.is_decimal or b.is_decimal:
        if a.is_float or b.is_float:
            return DataType.float64()
        da = a if a.is_decimal else DataType.decimal(_INT_DECIMAL_PRECISION[a.kind], 0)
        db = b if b.is_decimal else DataType.decimal(_INT_DECIMAL_PRECISION[b.kind], 0)
        scale = max(da.scale, db.scale)
        intd = max(da.precision - da.scale, db.precision - db.scale)
        return DataType.decimal(min(intd + scale, 38), scale)
    if a.kind in _RANK and b.kind in _RANK:
        return a if _RANK[a.kind] >= _RANK[b.kind] else b
    if a.kind == b.kind:
        return a
    raise TypeError(f"no common type for {a!r} and {b!r}")


def infer_dtype(expr: Expr, schema: Schema) -> DataType:
    if isinstance(expr, Col):
        return schema.field(expr.name).dtype
    if isinstance(expr, Alias):
        return infer_dtype(expr.child, schema)
    if isinstance(expr, Lit):
        return infer_lit_dtype(expr.value, expr.dtype)
    if isinstance(expr, Slot):
        return expr.dtype
    if isinstance(expr, Cast):
        return expr.to
    if isinstance(expr, (IsNull, IsNotNull, Not, InList, Like)):
        return DataType.bool_()
    if isinstance(expr, BinOp):
        if expr.op in _CMP_OPS or expr.op in _LOGIC_OPS:
            return DataType.bool_()
        lt = infer_dtype(expr.left, schema)
        rt = infer_dtype(expr.right, schema)
        if lt.is_decimal or rt.is_decimal:
            if lt.is_float or rt.is_float:
                return DataType.float64()
            ld = lt if lt.is_decimal else DataType.decimal(_INT_DECIMAL_PRECISION[lt.kind], 0)
            rd = rt if rt.is_decimal else DataType.decimal(_INT_DECIMAL_PRECISION[rt.kind], 0)
            if expr.op in ("+", "-"):
                return decimal_add_type(ld, rd)
            if expr.op == "*":
                return decimal_mul_type(ld, rd)
            if expr.op == "/":
                return decimal_div_type(ld, rd)
            return DataType.decimal(max(ld.precision, rd.precision), max(ld.scale, rd.scale))
        if expr.op == "/":
            return DataType.float64()
        return _common_type(lt, rt)
    if isinstance(expr, Case):
        t = DataType.null()
        for _, v in expr.branches:
            t = _common_type(t, infer_dtype(v, schema))
        if expr.else_ is not None:
            t = _common_type(t, infer_dtype(expr.else_, schema))
        return t
    if isinstance(expr, ScalarFunc):
        from .functions import infer_func_dtype

        return infer_func_dtype(expr, schema)
    if isinstance(expr, GetIndexedField):
        t = infer_dtype(expr.child, schema)
        assert t.kind == TypeKind.ARRAY, f"get_item over {t!r}"
        return t.elem
    if isinstance(expr, GetMapValue):
        t = infer_dtype(expr.child, schema)
        assert t.kind == TypeKind.MAP, f"map_value over {t!r}"
        return t.value
    if isinstance(expr, GetStructField):
        t = infer_dtype(expr.child, schema)
        assert t.kind == TypeKind.STRUCT, f"get_field over {t!r}"
        for f in t.struct_fields:
            if f.name == expr.name:
                return f.dtype
        raise KeyError(f"no struct field {expr.name!r} in {t!r}")
    if isinstance(expr, NamedStruct):
        from ..schema import Field as _Field

        return DataType.struct(
            [_Field(nm, infer_dtype(e, schema)) for nm, e in zip(expr.names, expr.exprs)]
        )
    from .ir import PythonUdf, SparkUdfWrapper

    if isinstance(expr, (PythonUdf, SparkUdfWrapper)):
        return expr.dtype
    raise TypeError(f"cannot infer type of {expr!r}")


# ------------------------------------------------------------- lowering

def _coerce(col: Column, to: DataType) -> Column:
    if col.dtype == to:
        return col
    if col.dtype.kind == TypeKind.NULL:
        n = col.data.shape[0]
        if to.is_string:
            return Column(
                to,
                jnp.zeros((n, to.string_width), jnp.uint8),
                jnp.zeros(n, jnp.bool_),
                jnp.zeros(n, jnp.int32),
            )
        return Column(to, jnp.zeros(n, to.np_dtype), jnp.zeros(n, jnp.bool_))
    if to.is_string and col.dtype.is_string:
        if to.string_width == col.data.shape[1]:
            return Column(to, col.data, col.validity, col.lengths)
        return Column(to, S._pad_to(col.data, to.string_width), col.validity, col.lengths)
    return lower_cast(col, to)


def null_nested_column(dtype: DataType, shape: Tuple[int, ...]) -> Column:
    """All-null device column of any dtype with leading dims ``shape``
    (element layouts recurse with an extra axis)."""
    zeros_b = jnp.zeros(shape, jnp.bool_)
    if dtype.kind == TypeKind.ARRAY:
        kid = null_nested_column(dtype.elem, shape + (dtype.max_elems,))
        return Column(dtype, None, zeros_b, jnp.zeros(shape, jnp.int32), (kid,))
    if dtype.kind == TypeKind.MAP:
        k = null_nested_column(dtype.key, shape + (dtype.max_elems,))
        v = null_nested_column(dtype.value, shape + (dtype.max_elems,))
        return Column(dtype, None, zeros_b, jnp.zeros(shape, jnp.int32), (k, v))
    if dtype.kind == TypeKind.STRUCT:
        kids = tuple(null_nested_column(f.dtype, shape) for f in dtype.struct_fields)
        return Column(dtype, None, zeros_b, None, kids)
    if dtype.is_string:
        return Column(
            dtype,
            jnp.zeros(shape + (dtype.string_width,), jnp.uint8),
            zeros_b,
            jnp.zeros(shape, jnp.int32),
        )
    return Column(dtype, jnp.zeros(shape, dtype.np_dtype), zeros_b)


class RawUnscaled(int):
    """Marker: the literal int is ALREADY the unscaled decimal value
    (a literal decoded from plan bytes, a scalar-subquery result) —
    ``Lit`` values are otherwise logical.  The repr differs from the
    plain int's so :func:`expr_key` never shares a baked-constant
    kernel between ``Lit(100)`` and ``Lit(RawUnscaled(100))``."""

    def __repr__(self) -> str:
        return f"RawUnscaled({int(self)})"


def decimal_unscaled(value, scale: int) -> int:
    """The int64 device value of a decimal literal — the ONE definition
    the baked-constant lowering, the literal slots and the plan
    serializer share."""
    if isinstance(value, RawUnscaled):
        return int(value)
    if isinstance(value, str):
        from decimal import Decimal

        return int(Decimal(value).scaleb(scale).to_integral_value())
    if isinstance(value, float):
        return int(round(value * 10**scale))
    return int(value) * 10**scale


def _lit_column(value, dtype: DataType, n: int) -> Column:
    if value is None:
        if dtype.is_nested:
            return null_nested_column(dtype, (n,))
        return _coerce(Column(DataType.null(), jnp.zeros(n, jnp.bool_), jnp.zeros(n, jnp.bool_)), dtype)
    valid = jnp.ones(n, jnp.bool_)
    if dtype.is_string:
        b = value.encode("utf-8") if isinstance(value, str) else bytes(value)
        w = dtype.string_width
        row = np.zeros(w, np.uint8)
        row[: len(b)] = np.frombuffer(b, np.uint8)
        data = jnp.broadcast_to(jnp.asarray(row), (n, w))
        return Column(dtype, data, valid, jnp.full(n, len(b), jnp.int32))
    if dtype.is_decimal:
        unscaled = decimal_unscaled(value, dtype.scale)
        return Column(dtype, jnp.full(n, unscaled, jnp.int64), valid)
    if dtype.kind == TypeKind.DATE32:
        if isinstance(value, str):
            value = datetime.date.fromisoformat(value)
        if isinstance(value, datetime.date):
            value = (value - datetime.date(1970, 1, 1)).days
        return Column(dtype, jnp.full(n, int(value), jnp.int32), valid)
    return Column(dtype, jnp.full(n, value, dtype.np_dtype), valid)


def _decimal_binop(op: str, l: Column, r: Column) -> Column:
    ld = l if l.dtype.is_decimal else _coerce(l, DataType.decimal(_INT_DECIMAL_PRECISION[l.dtype.kind], 0))
    rd = r if r.dtype.is_decimal else _coerce(r, DataType.decimal(_INT_DECIMAL_PRECISION[r.dtype.kind], 0))
    validity = ld.validity & rd.validity
    if op in ("+", "-"):
        out_t = decimal_add_type(ld.dtype, rd.dtype)
        a = rescale_decimal(ld.data, ld.dtype.scale, out_t.scale)
        b = rescale_decimal(rd.data, rd.dtype.scale, out_t.scale)
        data = a + b if op == "+" else a - b
        return Column(out_t, data, decimal_overflow_null(data, validity, out_t.precision))
    if op == "*":
        out_t = decimal_mul_type(ld.dtype, rd.dtype)
        raw_scale = ld.dtype.scale + rd.dtype.scale
        if ld.dtype.precision + rd.dtype.precision + 1 <= 18:
            # the raw product provably fits int64
            data = ld.data * rd.data
            if out_t.scale != raw_scale:
                data = rescale_decimal(data, raw_scale, out_t.scale)
            return Column(out_t, data, decimal_overflow_null(data, validity, out_t.precision))
        # wide multiply: exact int128 product + HALF_UP rescale
        # (≙ reference decimal128 with check_overflow, cast.rs)
        from . import int128 as I

        hi, lo = I.mul_i64(ld.data, rd.data)
        if out_t.scale < raw_scale:
            data, fits = I.rescale_down(hi, lo, raw_scale - out_t.scale)
        else:
            if out_t.scale > raw_scale:
                # guard the up-shift against int128 wrap (float64
                # magnitude estimate errs toward NULL at the boundary,
                # where Spark overflows to NULL anyway)
                k = out_t.scale - raw_scale
                lim = float((2**127 - 1) // (10**k))
                est = jnp.abs(ld.data.astype(jnp.float64) * rd.data.astype(jnp.float64))
                validity = validity & (est <= lim * 0.999)
                hi, lo = I.mul_pow10(hi, lo, k)
            data, fits = I.to_i64(hi, lo)
        validity = validity & fits
        return Column(out_t, data, decimal_overflow_null(data, validity, out_t.precision))
    if op == "/":
        out_t = decimal_div_type(ld.dtype, rd.dtype)
        validity = validity & (rd.data != 0)
        shift = out_t.scale - ld.dtype.scale + rd.dtype.scale
        den = jnp.where(rd.data == 0, jnp.int64(1), rd.data)
        # exact int64 path only when the shifted numerator provably fits
        if ld.dtype.precision + shift <= 18:
            num = ld.data * jnp.int64(10**shift)
            half = jnp.abs(den) // 2
            adj = jnp.where(num >= 0, num + jnp.sign(den) * half, num - jnp.sign(den) * half)
            q = jnp.where(
                (adj >= 0) == (den > 0),
                jnp.abs(adj) // jnp.abs(den),
                -(jnp.abs(adj) // jnp.abs(den)),
            )
            return Column(out_t, q, validity)
        # wide divide: int128 shifted numerator, exact HALF_UP quotient
        from . import int128 as I

        hi, lo = I.from_i64(ld.data)
        if shift >= 0:
            # mul_pow10 wraps silently past 2^127: numerators whose
            # shifted magnitude cannot fit int128 overflow to NULL
            # (their true quotients exceed 38 digits in Spark too)
            lim = (2**127 - 1) // (10**shift)
            if lim < 2**63:
                fits_num = jnp.abs(ld.data) <= jnp.int64(lim)
                validity = validity & fits_num
                hi = jnp.where(fits_num, hi, jnp.int64(0))
                lo = jnp.where(fits_num, lo, jnp.uint64(0))
            hi, lo = I.mul_pow10(hi, lo, shift)
        else:
            # fold the down-shift into the divisor (single rounding);
            # folded divisors past int64 imply |quotient| <= 1: HALF_UP
            # gives ±1 iff 2|num| >= |den|*10^k (int128 compare), else 0
            k10 = -shift
            if k10 <= 18:
                k = 10**k10
                fits_den = jnp.abs(den) <= (2**63 - 1) // k
                den = jnp.where(fits_den, den * jnp.int64(k), jnp.int64(1))
            else:
                fits_den = jnp.zeros(den.shape, jnp.bool_)
                den = jnp.ones_like(den)
            if k10 <= 19:
                # |den|*10^19 < 9.3e37 < 2^127: the int128 product is exact
                dh, dl = I.abs128(*I.from_i64(rd.data))
                dh, dl = I.mul_pow10(dh, dl, k10)
                nh2, nl2 = I.abs128(*I.from_i64(ld.data))
                nh2, nl2 = I.add(nh2, nl2, nh2, nl2)  # 2|num|
                ge_half = (dh < nh2) | ((dh == nh2) & (dl <= nl2))
            else:
                # k >= 20: |den|*10^k >= 10^20 > max 2|num| ≈ 1.85e19
                ge_half = jnp.zeros(den.shape, jnp.bool_)
            sign_q = (ld.data < 0) ^ (rd.data < 0)
            tiny = jnp.where(
                ge_half, jnp.where(sign_q, jnp.int64(-1), jnp.int64(1)), jnp.int64(0)
            )
        q, fits = I.div_round_half_up(hi, lo, den)
        if shift < 0:
            q = jnp.where(fits_den, q, tiny)
            fits = fits | ~fits_den
        validity = validity & fits
        return Column(out_t, q, decimal_overflow_null(q, validity, out_t.precision))
    if op == "%":
        scale = max(ld.dtype.scale, rd.dtype.scale)
        out_t = DataType.decimal(min(38, max(ld.dtype.precision, rd.dtype.precision)), scale)
        a = rescale_decimal(ld.data, ld.dtype.scale, scale)
        b = rescale_decimal(rd.data, rd.dtype.scale, scale)
        validity = validity & (b != 0)
        b = jnp.where(b == 0, jnp.int64(1), b)
        import jax.lax as lax

        return Column(out_t, lax.rem(a, b), validity)
    raise NotImplementedError(op)


def _arith(op: str, l: Column, r: Column) -> Column:
    if l.dtype.is_decimal or r.dtype.is_decimal:
        if l.dtype.is_float or r.dtype.is_float:
            l = _coerce(l, DataType.float64())
            r = _coerce(r, DataType.float64())
        else:
            return _decimal_binop(op, l, r)
    validity = l.validity & r.validity
    if op == "/":
        l = _coerce(l, DataType.float64())
        r = _coerce(r, DataType.float64())
        validity = validity & (r.data != 0.0)
        den = jnp.where(r.data == 0.0, 1.0, r.data)
        return Column(DataType.float64(), l.data / den, validity)
    common = _common_type(l.dtype, r.dtype)
    l = _coerce(l, common)
    r = _coerce(r, common)
    if op == "+":
        data = l.data + r.data
    elif op == "-":
        data = l.data - r.data
    elif op == "*":
        data = l.data * r.data
    elif op == "%":
        import jax.lax as lax

        if common.is_float:
            validity = validity & (r.data != 0.0)
            den = jnp.where(r.data == 0.0, jnp.asarray(1.0, r.data.dtype), r.data)
        else:
            validity = validity & (r.data != 0)
            den = jnp.where(r.data == 0, jnp.asarray(1, r.data.dtype), r.data)
        data = lax.rem(l.data, den)
    else:
        raise NotImplementedError(op)
    return Column(common, data, validity)


def _cmp(op: str, l: Column, r: Column) -> Column:
    validity = l.validity & r.validity
    if l.dtype.is_string or r.dtype.is_string:
        if op == "==":
            v = S.str_eq(l, r)
        elif op == "!=":
            v = ~S.str_eq(l, r)
        elif op == "<":
            v = S.str_lt(l, r)
        elif op == "<=":
            v = S.str_le(l, r)
        elif op == ">":
            v = S.str_lt(r, l)
        else:
            v = S.str_le(r, l)
        return Column(DataType.bool_(), v, validity)
    if l.dtype.is_decimal or r.dtype.is_decimal:
        common = _common_type(l.dtype, r.dtype)
        l = _coerce(l, common)
        r = _coerce(r, common)
    else:
        common = _common_type(l.dtype, r.dtype)
        l = _coerce(l, common)
        r = _coerce(r, common)
    a, b = l.data, r.data
    if op == "==":
        v = a == b
    elif op == "!=":
        v = a != b
    elif op == "<":
        v = a < b
    elif op == "<=":
        v = a <= b
    elif op == ">":
        v = a > b
    else:
        v = a >= b
    return Column(DataType.bool_(), v, validity)


def _logic(op: str, l: Column, r: Column) -> Column:
    la = l.validity & l.data.astype(jnp.bool_)
    lf = l.validity & ~l.data.astype(jnp.bool_)
    ra = r.validity & r.data.astype(jnp.bool_)
    rf = r.validity & ~r.data.astype(jnp.bool_)
    if op == "and":
        validity = (l.validity & r.validity) | lf | rf
        value = la & ra
    else:
        validity = (l.validity & r.validity) | la | ra
        value = la | ra
    return Column(DataType.bool_(), value, validity)


def expr_key(e: Expr):
    """Structural identity key for common-subexpression caching
    (≙ CachedExprsEvaluator, common/cached_exprs_evaluator.rs:48-506).
    Aliases are transparent; PythonUdf nodes never share."""
    if isinstance(e, Col):
        return ("col", e.name)
    if isinstance(e, Lit):
        return ("lit", repr(e.value), e.dtype)
    if isinstance(e, Slot):
        # the whole point of slots: shifted literal VALUES share a key
        return ("slot", e.index, e.dtype)
    if isinstance(e, Alias):
        return expr_key(e.child)
    if isinstance(e, BinOp):
        return ("bin", e.op, expr_key(e.left), expr_key(e.right))
    if isinstance(e, Not):
        return ("not", expr_key(e.child))
    if isinstance(e, IsNull):
        return ("isnull", expr_key(e.child))
    if isinstance(e, IsNotNull):
        return ("isnotnull", expr_key(e.child))
    if isinstance(e, Cast):
        return ("cast", e.to, expr_key(e.child))
    if isinstance(e, Case):
        return (
            "case",
            tuple((expr_key(c), expr_key(v)) for c, v in e.branches),
            None if e.else_ is None else expr_key(e.else_),
        )
    if isinstance(e, InList):
        return ("inlist", expr_key(e.child), tuple(expr_key(v) for v in e.values), e.negated)
    if isinstance(e, Like):
        return ("like", expr_key(e.child), e.pattern, e.negated)
    if isinstance(e, ScalarFunc):
        return ("fn", e.name, tuple(expr_key(a) for a in e.args))
    if isinstance(e, GetIndexedField):
        return ("gidx", expr_key(e.child), e.index)
    if isinstance(e, GetMapValue):
        return ("gmap", expr_key(e.child), repr(e.key))
    if isinstance(e, GetStructField):
        return ("gfield", expr_key(e.child), e.name)
    if isinstance(e, NamedStruct):
        return ("nstruct", tuple(e.names), tuple(expr_key(x) for x in e.exprs))
    return ("opaque", id(e))  # PythonUdf etc: never shared


def _lit_bool(e: Expr):
    """True/False if e is a non-null boolean literal, else None."""
    if isinstance(e, Alias):
        return _lit_bool(e.child)
    if isinstance(e, Lit) and isinstance(e.value, bool):
        return e.value
    return None


def fold_literals(e: Expr) -> Expr:
    """PLAN-TIME boolean constant folding: false AND x == false,
    true OR x == true, true AND x == x, false OR x == x.  Applied
    before host-fallback extraction (split_host_exprs), so a dead side
    containing host-only functions (regex/hash/json) is never
    evaluated at all — the full short-circuit contract the reference's
    SC and/or provides (cached_exprs_evaluator.rs)."""
    if isinstance(e, Alias):
        return Alias(fold_literals(e.child), e.name)
    if isinstance(e, Not):
        return Not(fold_literals(e.child))
    if isinstance(e, BinOp):
        l = fold_literals(e.left)
        r = fold_literals(e.right)
        if e.op in ("and", "or"):
            for a, b in ((l, r), (r, l)):
                lb = _lit_bool(a)
                if lb is None:
                    continue
                if e.op == "and" and lb is False:
                    return Lit(False)
                if e.op == "or" and lb is True:
                    return Lit(True)
                if (e.op == "and" and lb is True) or (e.op == "or" and lb is False):
                    return b
        return BinOp(e.op, l, r)
    if isinstance(e, Case):
        branches = [(fold_literals(c), fold_literals(v)) for c, v in e.branches]
        kept = [(c, v) for c, v in branches if _lit_bool(c) is not False]
        else_ = None if e.else_ is None else fold_literals(e.else_)
        if kept and _lit_bool(kept[0][0]) is True:
            return kept[0][1]
        return Case(kept, else_)
    if isinstance(e, ScalarFunc):
        return ScalarFunc(e.name, [fold_literals(a) for a in e.args])
    if isinstance(e, InList):
        return InList(fold_literals(e.child), [fold_literals(v) for v in e.values], e.negated)
    if isinstance(e, Cast):
        return Cast(fold_literals(e.child), e.to)
    return e


# ------------------------------------------------- literal slotification

def _slot_physical(value, dtype: DataType):
    """The traced scalar a slotified literal ships: EXACTLY the device
    value :func:`_lit_column` would bake for (value, dtype), as a numpy
    scalar so the jit argument dtype is pinned host-side (a python int
    would retrace on the int32/int64 weak-type boundary)."""
    if dtype.is_decimal:
        return np.int64(decimal_unscaled(value, dtype.scale))
    if dtype.kind == TypeKind.DATE32:
        if isinstance(value, str):
            value = datetime.date.fromisoformat(value)
        if isinstance(value, datetime.date):
            value = (value - datetime.date(1970, 1, 1)).days
        return np.int32(int(value))
    return np.asarray(value, dtype.np_dtype)[()]


def slot_eligible(e: Expr) -> bool:
    """Literal leaves that may become slots: scalar numerics, decimals
    and dates.  Excluded: nulls and bools (both drive TRACE-TIME
    short-circuits — `_lit_bool`, validity folding — so their value is
    plan structure, not data), strings/binary (their width is part of
    the column SHAPE) and nested values."""
    if not isinstance(e, Lit) or e.value is None or isinstance(e.value, bool):
        return False
    dtype = infer_lit_dtype(e.value, e.dtype)
    return not (dtype.is_string or dtype.is_nested
                or dtype.kind in (TypeKind.NULL, TypeKind.BOOL))


def slotify_literals(exprs: List[Optional[Expr]], start: int = 0):
    """Rewrite eligible ``Lit`` leaves into :class:`Slot` nodes so
    parameter-shifted variants of one expression shape share one
    structural key (and therefore one compiled program).  Returns
    ``(new_exprs, slot_values)`` where ``slot_values`` are the numpy
    scalars to pass as the operator's ``trace_slots()`` tail, in slot
    index order (indices begin at ``start``).  The input trees are not
    mutated — callers keep the original exprs for plan rewrites,
    pruning, and scan pushdown."""
    from .functions import STRUCTURAL_LIT_ARGS as structural

    _EMPTY: frozenset = frozenset()
    values: List = []

    def walk(e: Optional[Expr]) -> Optional[Expr]:
        if e is None:
            return None
        if isinstance(e, Lit):
            if not slot_eligible(e):
                return e
            dtype = infer_lit_dtype(e.value, e.dtype)
            values.append(_slot_physical(e.value, dtype))
            return Slot(start + len(values) - 1, dtype)
        if isinstance(e, Alias):
            return Alias(walk(e.child), e.name)
        if isinstance(e, BinOp):
            return BinOp(e.op, walk(e.left), walk(e.right))
        if isinstance(e, Not):
            return Not(walk(e.child))
        if isinstance(e, IsNull):
            return IsNull(walk(e.child))
        if isinstance(e, IsNotNull):
            return IsNotNull(walk(e.child))
        if isinstance(e, Cast):
            return Cast(walk(e.child), e.to)
        if isinstance(e, Case):
            return Case([(walk(c), walk(v)) for c, v in e.branches],
                        None if e.else_ is None else walk(e.else_))
        if isinstance(e, InList):
            return InList(walk(e.child), [walk(v) for v in e.values],
                          e.negated)
        if isinstance(e, Like):
            return Like(walk(e.child), e.pattern, e.negated)
        if isinstance(e, ScalarFunc):
            # structural literal args (decimal precision/scale, slice
            # bounds, pad widths) are read with ``.value`` at trace
            # time — they must stay ``Lit``, never become Slots
            keep = structural.get(e.name, _EMPTY)
            return ScalarFunc(e.name, [a if i in keep else walk(a)
                                       for i, a in enumerate(e.args)])
        if isinstance(e, GetIndexedField):
            return GetIndexedField(walk(e.child), e.index)
        if isinstance(e, GetStructField):
            return GetStructField(walk(e.child), e.name)
        # PythonUdf/SparkUdfWrapper (host-evaluated), NamedStruct,
        # GetMapValue, Col: leave as-is — their literals stay baked
        return e

    return [walk(e) for e in exprs], tuple(values)


# counts _lower_node invocations (CSE effectiveness; tests assert on it)
LOWER_STATS = {"nodes": 0}


def lower(
    expr: Expr, schema: Schema, cols: Dict[str, Column], n: int,
    memo: Optional[Dict] = None,
) -> Column:
    """Recursively lower an expression against resolved input columns.
    Runs under jax tracing; must stay functional and shape-static.

    ``memo`` caches lowered subtrees by structural key — pass ONE dict
    across sibling expressions evaluated against the same columns (a
    projection's output list) to lower each distinct subtree once
    (≙ the reference's CachedExprsEvaluator; here the win is trace/
    compile time, XLA already CSEs the runtime ops)."""
    if memo is None:
        memo = {}
    # key binds the column environment + capacity, so a memo shared
    # across different inputs can never alias wrong columns
    key = (id(cols), n, expr_key(expr))
    hit = memo.get(key)
    if hit is not None:
        return hit
    out = _lower_node(expr, schema, cols, n, memo)
    memo[key] = out
    return out


def _lower_node(expr: Expr, schema: Schema, cols: Dict[str, Column], n: int, memo) -> Column:
    LOWER_STATS["nodes"] += 1
    if isinstance(expr, Col):
        return cols[expr.name]
    if isinstance(expr, Alias):
        return lower(expr.child, schema, cols, n, memo)
    if isinstance(expr, Lit):
        return _lit_column(expr.value, infer_lit_dtype(expr.value, expr.dtype), n)
    if isinstance(expr, Slot):
        slots = cols.get("__slots__")
        if slots is None:
            raise KeyError(
                "slotified expression lowered without a '__slots__' "
                "environment entry — the owning operator must pass its "
                "trace_slots() values through the column env")
        return Column(expr.dtype,
                      jnp.full(n, slots[expr.index], expr.dtype.np_dtype),
                      jnp.ones(n, jnp.bool_))
    if isinstance(expr, Cast):
        return lower_cast(lower(expr.child, schema, cols, n, memo), expr.to)
    if isinstance(expr, Not):
        c = lower(expr.child, schema, cols, n, memo)
        return Column(DataType.bool_(), ~c.data.astype(jnp.bool_), c.validity)
    if isinstance(expr, IsNull):
        c = lower(expr.child, schema, cols, n, memo)
        return Column(DataType.bool_(), ~c.validity, jnp.ones_like(c.validity))
    if isinstance(expr, IsNotNull):
        c = lower(expr.child, schema, cols, n, memo)
        return Column(DataType.bool_(), c.validity, jnp.ones_like(c.validity))
    if isinstance(expr, BinOp):
        if expr.op in _LOGIC_OPS:
            # trace-time short-circuit on literal operands (≙ the
            # reference's SC and/or): false AND x == false, true OR x
            # == true — the other side is never lowered at all
            for a, b in ((expr.left, expr.right), (expr.right, expr.left)):
                lb = _lit_bool(a)
                if lb is None:
                    continue
                if expr.op == "and" and lb is False:
                    return _lit_column(False, DataType.bool_(), n)
                if expr.op == "or" and lb is True:
                    return _lit_column(True, DataType.bool_(), n)
                if (expr.op == "and" and lb is True) or (expr.op == "or" and lb is False):
                    other = lower(b, schema, cols, n, memo)
                    return _coerce(other, DataType.bool_())
            l = lower(expr.left, schema, cols, n, memo)
            r = lower(expr.right, schema, cols, n, memo)
            return _logic(expr.op, l, r)
        l = lower(expr.left, schema, cols, n, memo)
        r = lower(expr.right, schema, cols, n, memo)
        if expr.op in _CMP_OPS:
            return _cmp(expr.op, l, r)
        return _arith(expr.op, l, r)
    if isinstance(expr, InList):
        c = lower(expr.child, schema, cols, n, memo)
        acc = None
        for v in expr.values:
            eq = _cmp("==", c, lower(v, schema, cols, n, memo))
            acc = eq if acc is None else _logic("or", acc, eq)
        if expr.negated:
            return Column(DataType.bool_(), ~acc.data.astype(jnp.bool_), acc.validity)
        return acc
    if isinstance(expr, Like):
        return _lower_like(expr, schema, cols, n, memo)
    if isinstance(expr, Case):
        return _lower_case(expr, schema, cols, n, memo)
    if isinstance(expr, ScalarFunc):
        from .functions import lower_func

        def lf(e, s, c, nn):
            return lower(e, s, c, nn, memo)

        return lower_func(expr, schema, cols, n, lf)
    if isinstance(expr, GetIndexedField):
        return _lower_get_indexed(expr, schema, cols, n, memo)
    if isinstance(expr, GetMapValue):
        return _lower_get_map_value(expr, schema, cols, n, memo)
    if isinstance(expr, GetStructField):
        c = lower(expr.child, schema, cols, n, memo)
        fi = [f.name for f in c.dtype.struct_fields].index(expr.name)
        kid = c.children[fi]
        return Column(kid.dtype, kid.data, kid.validity & c.validity, kid.lengths, kid.children)
    if isinstance(expr, NamedStruct):
        kids = tuple(lower(e, schema, cols, n, memo) for e in expr.exprs)
        out_t = infer_dtype(expr, schema)
        return Column(out_t, None, jnp.ones(n, jnp.bool_), None, kids)
    raise NotImplementedError(f"lowering of {type(expr).__name__}")


def elem_at(elem: Column, i: int) -> Column:
    """Slice element ``i`` out of an element-layout column
    ((cap, M, ...) buffers -> (cap, ...))."""
    s = lambda a: None if a is None else a[:, i]
    return Column(
        elem.dtype, s(elem.data), s(elem.validity), s(elem.lengths),
        None if elem.children is None else tuple(elem_at(k, i) for k in elem.children),
    )


def elem_gather(elem: Column, idx) -> Column:
    """Per-row element gather: pick element ``idx[r]`` from row ``r`` of
    an element-layout column."""

    def g(a):
        if a is None:
            return None
        ix = idx.astype(jnp.int32).reshape((idx.shape[0],) + (1,) * (a.ndim - 1))
        return jnp.take_along_axis(a, ix, axis=1)[:, 0]

    return Column(
        elem.dtype, g(elem.data), g(elem.validity), g(elem.lengths),
        None if elem.children is None else tuple(elem_gather(k, idx) for k in elem.children),
    )


def _lower_get_indexed(expr: GetIndexedField, schema, cols, n, memo=None) -> Column:
    c = lower(expr.child, schema, cols, n, memo)
    assert c.dtype.kind == TypeKind.ARRAY
    i, m = expr.index, c.dtype.max_elems
    if i < 0 or i >= m:
        return _lit_column(None, c.dtype.elem, n)
    out = elem_at(c.children[0], i)
    valid = c.validity & (c.lengths > i) & out.validity
    return Column(out.dtype, out.data, valid, out.lengths, out.children)


def _lower_get_map_value(expr: GetMapValue, schema, cols, n, memo=None) -> Column:
    from ..batch import _scalar_to_physical

    c = lower(expr.child, schema, cols, n, memo)
    assert c.dtype.kind == TypeKind.MAP
    keys, vals = c.children
    m = c.dtype.max_elems
    within = (jnp.arange(m)[None, :] < c.lengths[:, None]) & keys.validity
    if c.dtype.key.is_string:
        kb = expr.key.encode("utf-8") if isinstance(expr.key, str) else bytes(expr.key)
        w = keys.data.shape[-1]
        if len(kb) > w:
            eq = jnp.zeros_like(within)
        else:
            pat = jnp.asarray(
                np.frombuffer(kb.ljust(w, b"\x00"), dtype=np.uint8)
            )
            eq = jnp.all(keys.data == pat[None, None, :], axis=-1) & (
                keys.lengths == len(kb)
            )
    else:
        phys = _scalar_to_physical(c.dtype.key, expr.key)
        eq = keys.data == jnp.asarray(phys, keys.data.dtype)
    hit = eq & within
    found = jnp.any(hit, axis=1)
    idx = jnp.argmax(hit, axis=1)
    out = elem_gather(vals, idx)
    valid = c.validity & found & out.validity
    return Column(out.dtype, out.data, valid, out.lengths, out.children)


def _lower_case(expr: Case, schema, cols, n, memo=None) -> Column:
    out_t = infer_dtype(expr, schema)
    if expr.else_ is not None:
        result = _coerce(lower(expr.else_, schema, cols, n, memo), out_t)
    else:
        result = _lit_column(None, out_t, n)
    for cond, val in reversed(expr.branches):
        c = lower(cond, schema, cols, n, memo)
        v = _coerce(lower(val, schema, cols, n, memo), out_t)
        picked = c.validity & c.data.astype(jnp.bool_)
        if out_t.is_string:
            data = jnp.where(picked[:, None], S._pad_to(v.data, result.data.shape[1]), result.data)
            lengths = jnp.where(picked, v.lengths, result.lengths)
            result = Column(out_t, data, jnp.where(picked, v.validity, result.validity), lengths)
        else:
            result = Column(
                out_t,
                jnp.where(picked, v.data, result.data),
                jnp.where(picked, v.validity, result.validity),
            )
    return result


def like_pattern_parts(pattern: str) -> Optional[List[bytes]]:
    """Split a LIKE pattern on ``%``; None if it contains ``_`` (host
    fallback).  Returns segment list; empty leading/trailing segments
    encode anchoring."""
    if "_" in pattern:
        return None
    return [p.encode("utf-8") for p in pattern.split("%")]


def _lower_like(expr: Like, schema, cols, n, memo=None) -> Column:
    c = lower(expr.child, schema, cols, n, memo)
    parts = like_pattern_parts(expr.pattern)
    if parts is None:
        raise NotImplementedError(
            "LIKE with '_' requires host fallback (split_host_exprs)"
        )
    if len(parts) == 1:
        v = S.str_eq(c, _lit_column(parts[0], DataType.string(max(8, c.data.shape[1])), n))
        v = v & (c.lengths == len(parts[0]))
    else:
        v = jnp.ones(n, jnp.bool_)
        if parts[0]:
            v = v & S.starts_with(c, parts[0])
        if parts[-1]:
            v = v & S.ends_with(c, parts[-1])
        middle = [p for p in parts[1:-1] if p]
        if len(middle) == 1 and not parts[0] and not parts[-1]:
            v = S.contains(c, middle[0])
        elif middle:
            # multi-segment: conservative device approximation is wrong;
            # planner must route through split_host_exprs
            raise NotImplementedError("multi-segment LIKE requires host fallback")
        # length must cover anchored parts
        v = v & (c.lengths >= sum(len(p) for p in parts))
    if expr.negated:
        v = ~v
    return Column(DataType.bool_(), v, c.validity)


# ------------------------------------------------- host-fallback support

# scalar functions with data-dependent work no fixed-shape device
# kernel can express; evaluated per batch on host.  This matches the
# reference's architecture: ALL its scalar functions run on native CPU
# (datafusion-ext-functions) — here only the hot-path ones get device
# kernels, the long tail runs on host via functions.HOST_IMPLS.
_JSON_HOST_FUNCS = frozenset({"get_json_object", "get_parsed_json_object", "parse_json"})


class _HostFuncNames:
    """Set-like view over json host funcs + the registered HOST_IMPLS."""

    def __contains__(self, name) -> bool:
        from .functions import HOST_IMPLS

        return name in _JSON_HOST_FUNCS or name in HOST_IMPLS


HOST_SCALAR_FUNCS = _HostFuncNames()


def needs_host(expr: Expr) -> bool:
    """Does this tree contain a node only evaluable on host?  ≙ the
    reference's convertExprWithFallback wrapping unconvertible exprs
    into a JVM-callback UDF (NativeConverters.scala:407)."""
    from .ir import PythonUdf, SparkUdfWrapper

    if isinstance(expr, (PythonUdf, SparkUdfWrapper)):
        return True
    if isinstance(expr, ScalarFunc) and expr.name in HOST_SCALAR_FUNCS:
        return True
    if isinstance(expr, Like):
        parts = like_pattern_parts(expr.pattern)
        if parts is None:
            return True
        middle = [p for p in parts[1:-1] if p]
        if middle and (len(middle) > 1 or parts[0] or parts[-1]):
            return True
    children: List[Expr] = []
    if isinstance(expr, (Not, IsNull, IsNotNull, Alias)):
        children = [expr.child]
    elif isinstance(expr, Cast):
        children = [expr.child]
    elif isinstance(expr, BinOp):
        children = [expr.left, expr.right]
    elif isinstance(expr, InList):
        children = [expr.child] + expr.values
    elif isinstance(expr, Like):
        children = [expr.child]
    elif isinstance(expr, Case):
        children = [c for b in expr.branches for c in b] + ([expr.else_] if expr.else_ is not None else [])
    elif isinstance(expr, ScalarFunc):
        children = expr.args
    elif isinstance(expr, (GetIndexedField, GetMapValue, GetStructField)):
        children = [expr.child]
    elif isinstance(expr, NamedStruct):
        children = expr.exprs
    return any(needs_host(c) for c in children)


def device_only(exprs: List[Expr]) -> bool:
    """True when every tree lowers fully on device — the gate
    whole-stage fusion applies before folding an expression list (sort
    keys, absorbed predicates) into a traced program: a host-fallback
    subtree would need a per-batch host round trip mid-program."""
    return not any(needs_host(e) for e in exprs)


def split_host_exprs(exprs: List[Expr]) -> Tuple[List[Expr], List[Tuple[str, Expr]]]:
    """Replace host-only subtrees with synthetic column refs.  The
    operator evaluates the extracted subtrees on host per batch and
    injects them as extra input columns before the jitted kernel."""
    host_parts: List[Tuple[str, Expr]] = []

    def walk(e: Expr) -> Expr:
        from .ir import PythonUdf, SparkUdfWrapper

        if isinstance(e, (PythonUdf, SparkUdfWrapper)):
            name = f"__host_{len(host_parts)}"
            host_parts.append((name, e))
            return Col(name)
        if isinstance(e, Like) and needs_host(e) and not needs_host(e.child):
            name = f"__host_{len(host_parts)}"
            host_parts.append((name, e))
            return Col(name)
        if isinstance(e, ScalarFunc) and e.name in HOST_SCALAR_FUNCS:
            # hoist the OUTERMOST host call; host_eval recursively
            # evaluates nested host funcs and device-lowers other args
            name = f"__host_{len(host_parts)}"
            host_parts.append((name, e))
            return Col(name)
        if isinstance(e, (Not,)):
            return Not(walk(e.child))
        if isinstance(e, IsNull):
            return IsNull(walk(e.child))
        if isinstance(e, IsNotNull):
            return IsNotNull(walk(e.child))
        if isinstance(e, Alias):
            return Alias(walk(e.child), e.name)
        if isinstance(e, Cast):
            return Cast(walk(e.child), e.to)
        if isinstance(e, BinOp):
            return BinOp(e.op, walk(e.left), walk(e.right))
        if isinstance(e, InList):
            return InList(walk(e.child), [walk(v) for v in e.values], e.negated)
        if isinstance(e, Case):
            return Case([(walk(c), walk(v)) for c, v in e.branches], walk(e.else_) if e.else_ is not None else None)
        if isinstance(e, ScalarFunc):
            return ScalarFunc(e.name, [walk(a) for a in e.args])
        if isinstance(e, GetIndexedField):
            return GetIndexedField(walk(e.child), e.index)
        if isinstance(e, GetMapValue):
            return GetMapValue(walk(e.child), e.key)
        if isinstance(e, GetStructField):
            return GetStructField(walk(e.child), e.name)
        if isinstance(e, NamedStruct):
            return NamedStruct(e.names, [walk(x) for x in e.exprs])
        return e

    new = [walk(e) for e in exprs]
    return new, host_parts


def host_eval(expr: Expr, batch) -> Column:
    """Evaluate a host-fallback expression on the host (numpy/python):
    LIKE patterns beyond the device subset, and PythonUdf (the
    SparkUDFWrapperExpr round-trip analogue)."""
    import re

    from ..batch import column_from_numpy, column_from_strings, strings_to_list
    from .ir import PythonUdf, SparkUdfWrapper

    if isinstance(expr, SparkUdfWrapper):
        # ≙ SparkUDFWrapperExpr: ship the arg batch across the Arrow C
        # FFI to the registered (stand-in) JVM context.  Wire plans may
        # bind ARBITRARY converted child exprs (spark_udf_wrapper.rs
        # binds the converted children), so lower each arg to a column
        from ..batch import RecordBatch as _RB
        from ..schema import Field as _Field, Schema as _Schema
        from ..spark import udf_bridge

        # args containing host-only SUBTREES split the same way
        # operator projections do: hoist each host node, evaluate it,
        # inject as a synthetic column, lower the remainder on device
        dev_args, parts = split_host_exprs(list(expr.args))
        aug_fields = list(batch.schema.fields)
        aug_cols = list(batch.columns)
        for nm, sub in parts:
            c = host_eval(sub, batch)
            aug_fields.append(_Field(nm, c.dtype))
            aug_cols.append(c)
        aug_schema = _Schema(aug_fields)
        env = {f.name: c for f, c in zip(aug_fields, aug_cols)}
        arg_cols = [
            lower(a, aug_schema, env, batch.capacity) for a in dev_args
        ]
        arg_schema = _Schema([
            _Field(f"_{i}", infer_dtype(a, batch.schema))
            for i, a in enumerate(expr.args)
        ])
        args = _RB(arg_schema, arg_cols, batch.num_rows)
        return udf_bridge.evaluate(expr.serialized, args, expr.dtype,
                                   expr.expr_string, capacity=batch.capacity)

    if isinstance(expr, PythonUdf):
        from ..batch import batch_to_pydict

        arg_cols = {}
        for i, a in enumerate(expr.args):
            assert isinstance(a, Col), "PythonUdf args must be direct columns"
            arg_cols[a.name] = batch.column(a.name)
        d = batch_to_pydict(batch.select([a.name for a in expr.args]))
        names = [a.name for a in expr.args]
        out_vals = []
        for i in range(batch.num_rows):
            out_vals.append(expr.fn(*[d[nm][i] for nm in names]))
        if expr.dtype.is_string:
            return column_from_strings(out_vals, dtype=expr.dtype, capacity=batch.capacity).to_device()
        validity = np.array([v is not None for v in out_vals] + [False] * (batch.capacity - batch.num_rows))
        if expr.dtype.is_decimal:
            scale = 10 ** expr.dtype.scale
            vals = np.array(
                [int(round(v * scale)) if v is not None else 0 for v in out_vals]
                + [0] * (batch.capacity - batch.num_rows),
                np.int64,
            )
        else:
            vals = np.array(
                [v if v is not None else 0 for v in out_vals]
                + [0] * (batch.capacity - batch.num_rows),
                expr.dtype.np_dtype,
            )
        return column_from_numpy(expr.dtype, vals, validity, batch.capacity).to_device()

    if isinstance(expr, ScalarFunc) and expr.name in HOST_SCALAR_FUNCS and (
        expr.name not in _JSON_HOST_FUNCS
    ):
        # generic host function (functions.HOST_IMPLS): evaluate args
        # (device subtrees lowered eagerly, nested host calls recursed),
        # apply the python impl per row, rebuild a device column
        from ..batch import column_from_pylist, column_to_pylist
        from .functions import HOST_IMPLS

        impl, null_prop, wants_types = HOST_IMPLS[expr.name]
        out_dt = infer_dtype(expr, batch.schema)
        arg_types = [infer_dtype(a, batch.schema) for a in expr.args]

        def arg_values(a: Expr) -> List:
            if isinstance(a, Lit):
                return [a.value] * batch.num_rows
            if isinstance(a, ScalarFunc) and a.name in HOST_SCALAR_FUNCS:
                c = host_eval(a, batch)
            else:
                env = {f.name: c for f, c in zip(batch.schema.fields, batch.columns)}
                c = lower(a, batch.schema, env, batch.capacity)
            return column_to_pylist(c, batch.num_rows)

        args = [arg_values(a) for a in expr.args]
        out_vals: List = []
        for row in zip(*args) if args else [()] * batch.num_rows:
            if null_prop and any(v is None for v in row):
                out_vals.append(None)
            else:
                out_vals.append(impl(arg_types, *row) if wants_types else impl(*row))
        if out_dt.is_string:
            w = out_dt.string_width
            long = sum(
                1 for v in out_vals if v is not None and len(v.encode("utf-8")) > w
            )
            if long:
                logging.getLogger(__name__).warning(
                    "%s: %d result(s) exceeded string width %d and were nulled",
                    expr.name, long, w,
                )
                out_vals = [
                    v if v is None or len(v.encode("utf-8")) <= w else None
                    for v in out_vals
                ]
        return column_from_pylist(out_dt, out_vals, capacity=batch.capacity).to_device()

    if isinstance(expr, ScalarFunc) and expr.name in HOST_SCALAR_FUNCS:
        from .json_path import get_json_object, parse_json

        def arg_strings(a: Expr) -> List:
            if isinstance(a, Lit):
                return [a.value] * batch.num_rows
            if isinstance(a, Col):
                return strings_to_list(batch.column(a.name).to_host(), batch.num_rows)
            if isinstance(a, ScalarFunc) and a.name in HOST_SCALAR_FUNCS:
                c = host_eval(a, batch)  # nested host call
            else:
                # device-computable subtree (cast/concat/...): lower it
                # eagerly against this batch
                env = {f.name: c for f, c in zip(batch.schema.fields, batch.columns)}
                c = lower(a, batch.schema, env, batch.capacity)
            return strings_to_list(c.to_host(), batch.num_rows)

        src = arg_strings(expr.args[0])
        if expr.name == "parse_json":
            out_vals = [parse_json(s) for s in src]
        else:
            paths = arg_strings(expr.args[1])
            cache: dict = {}
            out_vals = [get_json_object(s, p, cache) for s, p in zip(src, paths)]
        out_dt = infer_dtype(expr, batch.schema)
        w = out_dt.string_width
        # fixed-width columns: a result longer than the declared width
        # cannot be stored — degrade to NULL rather than corrupt
        n_truncated = sum(
            1 for v in out_vals if v is not None and len(v.encode("utf-8")) > w
        )
        if n_truncated:
            logging.getLogger(__name__).warning(
                "%s: %d result(s) exceeded string width %d and were nulled",
                expr.name, n_truncated, w,
            )
        out_vals = [
            v if v is None or len(v.encode("utf-8")) <= w else None for v in out_vals
        ]
        return column_from_strings(out_vals, dtype=out_dt, capacity=batch.capacity).to_device()

    if isinstance(expr, Like):
        child = expr.child
        assert isinstance(child, Col), "host LIKE only over direct columns"
        col = batch.column(child.name)
        vals = strings_to_list(col.to_host(), batch.num_rows)
        rx = re.compile(
            "^" + "".join(".*" if ch == "%" else "." if ch == "_" else re.escape(ch) for ch in expr.pattern) + "$",
            re.DOTALL,
        )
        out = np.zeros(batch.capacity, np.bool_)
        validity = np.zeros(batch.capacity, np.bool_)
        for i, v in enumerate(vals):
            if v is None:
                continue
            validity[i] = True
            m = bool(rx.match(v))
            out[i] = (not m) if expr.negated else m
        return column_from_numpy(DataType.bool_(), out, validity, batch.capacity).to_device()
    raise NotImplementedError(f"host eval of {type(expr).__name__}")


# ------------------------------------------------------------ public API

@dataclass
class CompiledExpr:
    dtype: DataType
    expr: Expr
    schema: Schema

    def __call__(self, cols: Dict[str, Column], n: int) -> Column:
        return lower(self.expr, self.schema, cols, n)


def compile_expr(expr: Expr, schema: Schema) -> CompiledExpr:
    return CompiledExpr(infer_dtype(expr, schema), expr, schema)


def compile_exprs(exprs: List[Expr], schema: Schema) -> List[CompiledExpr]:
    return [compile_expr(e, schema) for e in exprs]
