"""Decimal128 (p > 18) expression kernels over int64 limb-plane columns.

Ref: the reference computes decimals as Decimal128 end-to-end (arrow-rs
i128 arrays; NativeConverters.scala:599-676 supplies the result
precision/scale Spark planned). Narrow decimals (p <= 18) stay on the
engine's compact int64 representation; these kernels cover operations
whose operands or result are wide, storing values as StructData
[hi int64, lo int64-as-unsigned] (columnar/int128.py).

Supported here — and enforced at plan time by the convert strategy's
wide-decimal walk (spark/converters.py) so anything else falls back:
add/sub, mul while p1+p2 <= 38 (the product fits 128 bits), division
via bit-serial 128-bit long division (int128.divmod_full) with HALF_UP
at the planned result scale while the scale-alignment upscale provably
fits 128 bits, all comparisons, negate, casts int/narrow/wide -> wide,
wide -> narrow / float64, and CheckOverflow (null outside 10^p, Spark
non-ANSI). Mod remains plan-time rejected.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from blaze_tpu.columnar import int128 as i128
from blaze_tpu.columnar.batch import Column, StructData
from blaze_tpu.columnar.types import FLOAT64, INT64, DataType, TypeKind
from blaze_tpu.exprs import ir

Array = jax.Array


def is_wide(dtype: DataType) -> bool:
    return dtype.wide_decimal


def planes(col: Column) -> Tuple[Array, Array]:
    """(hi, lo) planes of a decimal column, widening narrow storage."""
    if col.dtype.wide_decimal:
        return col.data.children[0].data, col.data.children[1].data
    return i128.from_i64(col.data.astype(jnp.int64))


def build(dtype: DataType, hi: Array, lo: Array,
          validity: Optional[Array]) -> Column:
    return Column(dtype, StructData(
        [Column(INT64, hi, None), Column(INT64, lo, None)]), validity)


def _rescale_to(col: Column, out_scale: int
                ) -> Tuple[Array, Array, Array]:
    """(hi, lo, ok): ok=False rows wrapped during an upscale (their true
    magnitude exceeds 2^127 post-scale) and must go null/saturate."""
    h, l = planes(col)
    return i128.rescale_checked(h, l, out_scale - col.dtype.scale)


def arith(lc: Column, rc: Column, op: ir.BinOp,
          result_type: DataType, validity: Optional[Array]) -> Column:
    """ADD/SUB/MUL with a wide operand or result (plan-checked bounds).
    Rows whose operands wrap during scale alignment come out null —
    Spark's own result there is the post-CheckOverflow null."""
    out_s = result_type.scale
    if op in (ir.BinOp.ADD, ir.BinOp.SUB):
        lh, ll, lok = _rescale_to(lc, out_s)
        rh, rl, rok = _rescale_to(rc, out_s)
        h, l = (i128.add(lh, ll, rh, rl) if op == ir.BinOp.ADD
                else i128.sub(lh, ll, rh, rl))
        return shape(result_type, h, l, _and_ok(validity, lok & rok))
    if op == ir.BinOp.MUL:
        ls, rs = lc.dtype.scale, rc.dtype.scale
        h, l = _mul(lc, rc)
        h, l, ok = i128.rescale_checked(h, l, out_s - (ls + rs))
        return shape(result_type, h, l, _and_ok(validity, ok))
    if op == ir.BinOp.DIV:
        return _div(lc, rc, result_type, validity)
    raise NotImplementedError(f"wide decimal op {op}")


def _div(lc: Column, rc: Column, result_type: DataType,
         validity: Optional[Array]) -> Column:
    """Spark decimal division: HALF_UP at the planner's result scale.

    value = round(a * 10^delta / b) with delta = out_s - a.s + b.s; a
    negative delta instead scales the DIVISOR up (both checked for
    128-bit wrap). Divide-by-zero and out-of-precision quotients go null
    (Spark non-ANSI). Ref: datafusion-ext-commons cast.rs decimal paths /
    Spark Decimal.divide (java BigDecimal HALF_UP)."""
    out_s = result_type.scale
    delta = out_s - lc.dtype.scale + rc.dtype.scale
    ah, al = planes(lc)
    bh, bl = planes(rc)
    ok = jnp.ones(ah.shape, jnp.bool_)
    if delta >= 0:
        ah, al, ok1 = i128.rescale_checked(ah, al, delta, half_up=False)
        ok = ok & ok1
    else:
        bh, bl, ok1 = i128.rescale_checked(bh, bl, -delta, half_up=False)
        ok = ok & ok1
    nonzero = (bh != 0) | (bl != 0)
    sign = i128.is_neg(ah, al) ^ i128.is_neg(bh, bl)
    qh, ql, rh, rl = i128.divmod_full(ah, al, bh, bl)
    # HALF_UP: bump |q| when 2*rem >= |b| (128-bit unsigned compare;
    # rem < |b| < 2^127 so the doubled value's carry bit decides alone
    # when set)
    dbh, dbl = bh, bl
    abh, abl = i128.abs_(dbh, dbl)
    carry = (rh >> 63) & jnp.int64(1)
    r2h = (rh << 1) | ((rl >> 63) & jnp.int64(1))
    r2l = rl << 1
    ge = (carry == 1) | ~(i128._u_lt(r2h, abh)
                          | ((r2h == abh) & i128._u_lt(r2l, abl)))
    qh, ql = i128.add(qh, ql,
                      jnp.zeros_like(qh), ge.astype(jnp.int64))
    nh, nl = i128.neg(qh, ql)
    h = jnp.where(sign, nh, qh)
    l = jnp.where(sign, nl, ql)
    ok = ok & nonzero & i128.in_precision(h, l, result_type.precision)
    return shape(result_type, h, l, _and_ok(validity, ok))


def _and_ok(validity: Optional[Array], ok: Array) -> Array:
    return ok if validity is None else (validity & ok)


def _mul(lc: Column, rc: Column) -> Tuple[Array, Array]:
    lw, rw = lc.dtype.wide_decimal, rc.dtype.wide_decimal
    if not lw and not rw:
        return i128.mul_i64(lc.data.astype(jnp.int64),
                            rc.data.astype(jnp.int64))
    # one side wide: |product| < 10^38 < 2^127 (plan bound p1+p2 <= 38),
    # so sign-magnitude schoolbook with the low 128 bits is exact
    ah, al = planes(lc)
    bh, bl = planes(rc)
    sign = i128.is_neg(ah, al) ^ i128.is_neg(bh, bl)
    ah, al = i128.abs_(ah, al)
    bh, bl = i128.abs_(bh, bl)
    ph, pl = i128._mul_u64(al, bl)
    ph = ph + al * bh + ah * bl          # low-64 wraps of the cross terms
    nh, nl = i128.neg(ph, pl)
    return (jnp.where(sign, nh, ph), jnp.where(sign, nl, pl))


def shape(result_type: DataType, h: Array, l: Array,
           validity: Optional[Array]) -> Column:
    """Wide results stay limb-shaped; a narrow result type (possible when
    Spark planned p<=18 for a wide-operand expression) compacts back."""
    if result_type.wide_decimal:
        return build(result_type, h, l, validity)
    v64, fits = i128.to_i64_checked(h, l)
    validity = fits if validity is None else (validity & fits)
    return Column(result_type, v64, validity)


def compare(lc: Column, rc: Column) -> Tuple[Array, Array, Array]:
    """(lt, eq, gt) with scales aligned (Catalyst normally equalizes
    types; unequal scales upscale the smaller side). A side that would
    wrap during the upscale saturates to +/-max128 — its true magnitude
    dominates anything representable, so the order is preserved."""
    s = max(lc.dtype.scale, rc.dtype.scale)
    lh, ll, lok = _rescale_to(lc, s)
    rh, rl, rok = _rescale_to(rc, s)
    lh, ll = _saturate(lh, ll, lok, *planes(lc))
    rh, rl = _saturate(rh, rl, rok, *planes(rc))
    c = i128.cmp(lh, ll, rh, rl)
    return c < 0, c == 0, c > 0


def _saturate(h: Array, l: Array, ok: Array, oh: Array, ol: Array
              ) -> Tuple[Array, Array]:
    neg = i128.is_neg(oh, ol)
    sat_h = jnp.where(neg, np.int64(-0x8000000000000000),
                      np.int64(0x7FFFFFFFFFFFFFFF))
    sat_l = jnp.where(neg, np.int64(0), np.int64(-1))
    return jnp.where(ok, h, sat_h), jnp.where(ok, l, sat_l)


def negate(col: Column) -> Column:
    h, l = planes(col)
    nh, nl = i128.neg(h, l)
    return build(col.dtype, nh, nl, col.validity)


def check_overflow(col: Column, precision: int, scale: int,
                   result_type: DataType) -> Column:
    """Spark CheckOverflow (non-ANSI): rescale then null outside 10^p."""
    h, l, rok = _rescale_to(col, scale)
    ok = rok & i128.in_precision(h, l, precision)
    return shape(result_type, h, l, _and_ok(col.validity, ok))


def cast_to_wide(col: Column, target: DataType) -> Column:
    """int / narrow decimal / wide decimal -> wide decimal."""
    src = col.dtype
    if src.is_decimal:
        h, l, rok = _rescale_to(col, target.scale)
    elif src.kind in (TypeKind.INT8, TypeKind.INT16, TypeKind.INT32,
                      TypeKind.INT64, TypeKind.BOOLEAN):
        h, l = i128.from_i64(col.data.astype(jnp.int64))
        h, l, rok = i128.rescale_checked(h, l, target.scale)
    else:
        raise NotImplementedError(f"cast {src} -> {target}")
    ok = rok & i128.in_precision(h, l, target.precision)
    return build(target, h, l, _and_ok(col.validity, ok))


# -- segmented aggregation kernels (ops/agg.py wide branches) --------------

_M32 = np.int64(0xFFFFFFFF)
# numpy scalars: a module-level jnp constant is a concrete device array
# — creating it at import initializes a backend (on the chip, takes the
# chip), and jit LIFTS it into a scalar buffer argument in some flows;
# np scalars always fold into program literals
_I64_MIN = np.int64(-0x8000000000000000)
# any |sum| past this is already beyond every valid decimal precision
# (10^38 < 1.5e38 < 2^127), so flagging it cannot null a representable
# result; it catches true 128-bit wraps exactly where CheckOverflow's
# in-range test cannot see them
_OVERFLOW_BOUND = 1.5e38


def seg_sum_wide(h: Array, l: Array, valid: Array, layout, seg
                 ) -> Tuple[Array, Array, Array]:
    """Per-group 128-bit sums via four signed 32-bit limb plane sums
    (each limb sum is int64-exact: < 2^21 rows * 2^32). Returns
    (hi, lo, ok) per group slot; ok=False marks magnitude overflow
    (detected on an f64 shadow — sums beyond 2^127 wrap mod 2^128)."""
    neg = h < 0
    nh, nl = i128.neg(h, l)
    ah = jnp.where(neg, nh, h)
    al = jnp.where(neg, nl, l)
    sgn = jnp.where(neg, jnp.int64(-1), jnp.int64(1))
    limbs = [al & _M32, (al >> 32) & _M32, ah & _M32, (ah >> 32) & _M32]
    sums = [seg.seg_sum(limb * sgn, layout, valid) for limb in limbs]
    s0, s1, s2, s3 = sums
    # low 128 bits: s0 + s1*2^32 + (s2 + s3*2^32)*2^64  (mod 2^128)
    h1, l1 = i128.mul_small(*i128.from_i64(s1), 1 << 32)
    acc_h, acc_l = i128.add(*i128.from_i64(s0), h1, l1)
    acc_h = acc_h + s2 + (s3 << 32)
    # f64 shadow for wrap detection (exact magnitude, ~2^-50 relative)
    approx = (s0.astype(jnp.float64)
              + s1.astype(jnp.float64) * (2.0 ** 32)
              + s2.astype(jnp.float64) * (2.0 ** 64)
              + s3.astype(jnp.float64) * (2.0 ** 96))
    ok = jnp.abs(approx) < _OVERFLOW_BOUND
    return acc_h, acc_l, ok


def seg_minmax_wide(h: Array, l: Array, valid: Array, layout, seg,
                    is_min: bool) -> Tuple[Array, Array, Array]:
    """Per-group 128-bit min/max: reduce the signed hi plane, then the
    lo plane among rows at the winning hi (lo compared unsigned via the
    sign-flip trick)."""
    red = seg.seg_min if is_min else seg.seg_max
    mh, has = red(h, layout, valid)
    at_extreme = valid & (h == mh[layout.gid])
    ls = l ^ _I64_MIN
    ml_s, _ = red(ls, layout, at_extreme)
    return mh, ml_s ^ _I64_MIN, has


def div_by_count(h: Array, l: Array, cnt: Array, result: DataType,
                 extra_scale: int) -> Tuple[Array, Array, Array]:
    """(sum * 10^extra_scale) / cnt with HALF_UP (ties away from zero) —
    the avg finalize. Returns (hi, lo, ok); ok=False where the scale-up
    wrapped or the quotient leaves the result precision. The division is
    int128.divmod_full's bit-serial loop: any positive int64 count
    divides, and it compiles as one small loop where a limb-wise long
    division by a per-row divisor costs eight emulated 64-bit divisions
    (on a v5e 17 s of compile each, 211 s for the four limbs)."""
    rok = jnp.ones(h.shape, jnp.bool_)
    if extra_scale:
        h, l, rok = i128.rescale_checked(h, l, extra_scale)
    sign = h < 0
    dd = jnp.maximum(cnt, 1).astype(jnp.int64)
    qh, ql, _, rem = i128.divmod_full(h, l, jnp.zeros_like(dd), dd)
    # rem < dd < 2^63: 2 * rem >= dd without the doubling that could wrap
    bump = (rem >= dd - rem).astype(jnp.int64)
    qh, ql = i128.add(qh, ql, jnp.zeros_like(qh), bump)
    nh, nl = i128.neg(qh, ql)
    ok = rok & i128.in_precision(qh, ql, result.precision)
    return jnp.where(sign, nh, qh), jnp.where(sign, nl, ql), ok


def cast_from_wide(col: Column, target: DataType) -> Column:
    """wide decimal -> narrow decimal / integral / float64."""
    h, l = planes(col)
    if target.is_decimal and not target.wide_decimal:
        h, l = i128.rescale(h, l, target.scale - col.dtype.scale)
        v64, fits = i128.to_i64_checked(h, l)
        inp = i128.in_precision(h, l, target.precision)
        ok = fits & inp
        validity = ok if col.validity is None else (col.validity & ok)
        return Column(target, v64, validity)
    if target.kind == TypeKind.FLOAT64:
        # convert the MAGNITUDE (negative values as hi*2^64 + lo would
        # cancel catastrophically: -2^64 + u64(lo) loses the low bits)
        neg = i128.is_neg(h, l)
        ah, al = i128.abs_(h, l)
        lo_u = jnp.where(al < 0, al.astype(jnp.float64)
                         + jnp.float64(2.0**64), al.astype(jnp.float64))
        v = ah.astype(jnp.float64) * jnp.float64(2.0**64) + lo_u
        v = jnp.where(neg, -v, v)
        return Column(FLOAT64, v / jnp.float64(10.0**col.dtype.scale),
                      col.validity)
    if target.kind in (TypeKind.INT32, TypeKind.INT64):
        # truncate the fraction, then narrow with overflow -> null
        h, l = i128.rescale(h, l, -col.dtype.scale, half_up=False)
        v64, fits = i128.to_i64_checked(h, l)
        if target.kind == TypeKind.INT32:
            in32 = (v64 >= jnp.int64(-2**31)) & (v64 < jnp.int64(2**31))
            fits = fits & in32
            out = v64.astype(jnp.int32)
        else:
            out = v64
        validity = fits if col.validity is None else (col.validity & fits)
        return Column(target, out, validity)
    raise NotImplementedError(f"cast {col.dtype} -> {target}")
