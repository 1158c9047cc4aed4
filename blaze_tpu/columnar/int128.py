"""128-bit signed integer limb arithmetic for wide decimals (p > 18).

Ref: the reference's type algebra is Decimal128 throughout (blaze-serde
scalar handling, datafusion-ext-commons cast.rs); arrow-rs stores the
unscaled value as a 128-bit little-endian integer. Here a wide decimal
column is two int64 planes — `hi` (signed, carries the sign) and `lo`
(the low 64 bits, INTERPRETED AS UNSIGNED) — so value = hi * 2^64 + u64(lo).
All kernels below are elementwise jnp on those planes; on TPU int64 is
itself emulated (32-bit pairs) but the arithmetic stays exact.

Unsigned comparisons on int64 planes use the sign-flip trick
(x ^ INT64_MIN monotonically maps u64 order onto i64 order).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# numpy scalars: a module-level jnp constant is a concrete device array
# — creating it at import initializes a backend (on the chip, takes the
# chip), and jit LIFTS it into a scalar buffer argument in some flows;
# np scalars always fold into program literals
_I64_MIN = np.int64(-0x8000000000000000)
_MASK32 = np.int64(0xFFFFFFFF)


def _u_lt(a: Array, b: Array) -> Array:
    """unsigned(a) < unsigned(b) on int64 planes."""
    return (a ^ _I64_MIN) < (b ^ _I64_MIN)


def from_parts(hi, lo) -> Tuple[Array, Array]:
    return jnp.asarray(hi, jnp.int64), jnp.asarray(lo, jnp.int64)


def from_i64(x: Array) -> Tuple[Array, Array]:
    """Sign-extend an int64 to 128 bits."""
    x = jnp.asarray(x, jnp.int64)
    return jnp.where(x < 0, jnp.int64(-1), jnp.int64(0)), x


def add(ah: Array, al: Array, bh: Array, bl: Array
        ) -> Tuple[Array, Array]:
    lo = al + bl
    carry = _u_lt(lo, al).astype(jnp.int64)
    return ah + bh + carry, lo


def neg(h: Array, l: Array) -> Tuple[Array, Array]:
    nl = -l
    nh = ~h + (l == 0).astype(jnp.int64)
    return nh, nl


def sub(ah: Array, al: Array, bh: Array, bl: Array
        ) -> Tuple[Array, Array]:
    nh, nl = neg(bh, bl)
    return add(ah, al, nh, nl)


def is_neg(h: Array, l: Array) -> Array:
    return h < 0


def abs_(h: Array, l: Array) -> Tuple[Array, Array]:
    nh, nl = neg(h, l)
    n = h < 0
    return jnp.where(n, nh, h), jnp.where(n, nl, l)


def cmp(ah: Array, al: Array, bh: Array, bl: Array) -> Array:
    """-1 / 0 / +1 (signed 128-bit order)."""
    hi_lt = ah < bh
    hi_gt = ah > bh
    lo_lt = _u_lt(al, bl)
    lo_gt = _u_lt(bl, al)
    lt = hi_lt | ((ah == bh) & lo_lt)
    gt = hi_gt | ((ah == bh) & lo_gt)
    return jnp.where(lt, jnp.int32(-1), jnp.where(gt, jnp.int32(1),
                                                  jnp.int32(0)))


def eq(ah: Array, al: Array, bh: Array, bl: Array) -> Array:
    return (ah == bh) & (al == bl)


def _mul_u64(a: Array, b: Array) -> Tuple[Array, Array]:
    """Full 64x64 -> 128 product of UNSIGNED operands (int64 planes)."""
    a0 = a & _MASK32
    a1 = (a >> 32) & _MASK32
    b0 = b & _MASK32
    b1 = (b >> 32) & _MASK32
    p00 = a0 * b0                     # < 2^64, exact in u64 wrap
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    # logical (not arithmetic) high halves: arithmetic >> then mask
    # equals a logical shift's low 32 bits
    mid = ((p00 >> 32) & _MASK32) + (p01 & _MASK32) + (p10 & _MASK32)
    lo = (p00 & _MASK32) | ((mid & _MASK32) << 32)
    hi = p11 + ((p01 >> 32) & _MASK32) + ((p10 >> 32) & _MASK32) \
        + (mid >> 32)
    return hi, lo


def mul_i64(a: Array, b: Array) -> Tuple[Array, Array]:
    """Signed 64x64 -> exact 128-bit product."""
    sign = (a < 0) ^ (b < 0)
    ua = jnp.abs(a)  # |INT64_MIN| wraps to itself; treated unsigned below
    ub = jnp.abs(b)
    h, l = _mul_u64(ua, ub)
    nh, nl = neg(h, l)
    return jnp.where(sign, nh, h), jnp.where(sign, nl, l)


def mul_small(h: Array, l: Array, m: int) -> Tuple[Array, Array]:
    """(h, l) * m for a small positive python int (< 2^62): schoolbook on
    the magnitude, sign reapplied."""
    assert 0 < m < (1 << 62)
    sign = h < 0
    ah, al = abs_(h, l)
    mh, ml = _mul_u64(al, jnp.int64(m))
    hi = mh + ah * jnp.int64(m)
    nh, nl = neg(hi, ml)
    return jnp.where(sign, nh, hi), jnp.where(sign, nl, ml)


def divmod_small(h: Array, l: Array, d: int) -> Tuple[Array, Array, Array]:
    """magnitude divmod by a small positive python int (< 2^31):
    (qh, ql, rem) on the MAGNITUDE; caller handles sign/rounding.
    Long division over four 32-bit limbs. A per-row divisor goes to
    divmod_full: on the TPU's compiler each emulated 64-bit division by
    an array costs ~17 s of compile, and this would be eight of them."""
    assert 0 < d < (1 << 31)
    dd = jnp.asarray(d, jnp.int64)
    ah, al = abs_(h, l)
    limbs = [(ah >> 32) & _MASK32, ah & _MASK32,
             (al >> 32) & _MASK32, al & _MASK32]
    q = []
    rem = jnp.zeros_like(ah)
    for limb in limbs:
        cur = (rem << 32) | limb      # < d * 2^32 <= 2^63: fits signed
        q.append(cur // dd)
        rem = cur % dd
    qh = (q[0] << 32) | q[1]
    ql = (q[2] << 32) | q[3]
    return qh, ql, rem


def divmod_full(h: Array, l: Array, dh: Array, dl: Array
                ) -> Tuple[Array, Array, Array, Array]:
    """Full 128/128 magnitude divmod: (qh, ql, rh, rl) of |a| divmod |d|.

    Bit-serial restoring long division (128 fori_loop steps of
    shift/compare/subtract over the two int64 limb planes) — branch-free
    per row, static trip count, so it jits to one compact TPU loop.
    Caller handles signs and rounding. d == 0 produces q = all-ones
    (the caller must null those rows — Spark's divide-by-zero is null).
    Exact for |a|, |d| < 2^127 (decimals are < 10^38 < 2^127)."""
    from jax import lax

    ah, al = abs_(h, l)
    bh, bl = abs_(dh, dl)

    def uge(xh, xl, yh, yl):
        return ~(_u_lt(xh, yh) | ((xh == yh) & _u_lt(xl, yl)))

    def step(i, st):
        qh, ql, rh, rl = st
        idx = jnp.int64(127) - i
        hi_bit = (ah >> jnp.clip(idx - 64, 0, 63)) & jnp.int64(1)
        lo_bit = (al >> jnp.clip(idx, 0, 63)) & jnp.int64(1)
        bit = jnp.where(idx >= 64, hi_bit, lo_bit)
        rh = (rh << 1) | ((rl >> 63) & jnp.int64(1))
        rl = (rl << 1) | bit
        g = uge(rh, rl, bh, bl)
        sh, sl = sub(rh, rl, bh, bl)
        rh = jnp.where(g, sh, rh)
        rl = jnp.where(g, sl, rl)
        qh = jnp.where(g & (idx >= 64),
                       qh | (jnp.int64(1) << jnp.clip(idx - 64, 0, 63)), qh)
        ql = jnp.where(g & (idx < 64),
                       ql | (jnp.int64(1) << jnp.clip(idx, 0, 63)), ql)
        return (qh, ql, rh, rl)

    z = jnp.zeros_like(ah)
    qh, ql, rh, rl = lax.fori_loop(0, 128, step, (z, z, z, z))
    return qh, ql, rh, rl


def rescale_checked(h: Array, l: Array, delta: int, half_up: bool = True
                    ) -> Tuple[Array, Array, Array]:
    """rescale plus a per-row ok flag: upscaling by 10^delta WRAPS mod
    2^128 when |v| >= 2^127 / 10^delta — wrapped residues can alias back
    into valid ranges and defeat downstream in_precision checks, so
    callers must null (or saturate) rows with ok=False. Downscaling
    cannot overflow (ok all-true)."""
    if delta > 0:
        # |v| < 10^(38-delta) guarantees |v * 10^delta| < 10^38 < 2^127
        ok = in_precision(h, l, max(38 - delta, 0))
    else:
        ok = jnp.ones(h.shape, jnp.bool_)
    hh, ll = rescale(h, l, delta, half_up)
    return hh, ll, ok


def rescale(h: Array, l: Array, delta: int, half_up: bool = True
            ) -> Tuple[Array, Array]:
    """Multiply by 10^delta (delta>0) or divide by 10^-delta with HALF_UP
    rounding (Spark decimal rescale)."""
    if delta == 0:
        return h, l
    if delta > 0:
        for step in _pow10_steps(delta):
            h, l = mul_small(h, l, step)
        return h, l
    sign = h < 0
    rem_scale = -delta
    rh, rl = abs_(h, l)
    last_rem = None
    last_div = 1
    for step in _pow10_steps(rem_scale):
        rh, rl, last_rem = divmod_small(rh, rl, step)
        last_div = step
    if half_up:
        bump = (2 * last_rem >= last_div).astype(jnp.int64)
        rh, rl = add(rh, rl, jnp.zeros_like(rh), bump)
    nh, nl = neg(rh, rl)
    return jnp.where(sign, nh, rh), jnp.where(sign, nl, rl)


def _pow10_steps(k: int):
    """10^k as factors each < 2^31 (divmod_small's bound)."""
    out = []
    while k > 0:
        s = min(k, 9)
        out.append(10 ** s)
        k -= s
    return out


def to_i64_checked(h: Array, l: Array) -> Tuple[Array, Array]:
    """(value as int64, fits) — fits when the 128-bit value is a
    sign-extension of its low 64 bits."""
    fits = h == jnp.where(l < 0, jnp.int64(-1), jnp.int64(0))
    return l, fits


def in_precision(h: Array, l: Array, precision: int) -> Array:
    """|value| < 10^precision (Spark CheckOverflow bound)."""
    bh, bl = _pow10_128(precision)
    ah, al = abs_(h, l)
    # note: abs(min128) wraps negative; treat via unsigned compare on
    # (h, l) magnitude planes — compare as unsigned 128
    lt = (_u_lt(ah, bh)) | ((ah == bh) & _u_lt(al, bl))
    return lt


def _pow10_128(k: int) -> Tuple[Array, Array]:
    v = 10 ** k
    return (jnp.int64((v >> 64) & 0xFFFFFFFFFFFFFFFF
                      ) if (v >> 64) < (1 << 63)
            else jnp.int64((v >> 64) - (1 << 64)),
            jnp.int64(v & 0xFFFFFFFFFFFFFFFF) if (v & 0xFFFFFFFFFFFFFFFF
                                                  ) < (1 << 63)
            else jnp.int64((v & 0xFFFFFFFFFFFFFFFF) - (1 << 64)))


# -- host-side helpers (construction / extraction) -------------------------


def np_from_ints(values) -> Tuple["jnp.ndarray", "jnp.ndarray"]:
    """Python ints -> (hi, lo) numpy int64 planes."""
    import numpy as np

    hi = np.empty(len(values), np.int64)
    lo = np.empty(len(values), np.int64)
    for i, v in enumerate(values):
        v = int(v)
        u = v & ((1 << 128) - 1)
        lo_u = u & 0xFFFFFFFFFFFFFFFF
        hi_u = (u >> 64) & 0xFFFFFFFFFFFFFFFF
        lo[i] = lo_u - (1 << 64) if lo_u >= (1 << 63) else lo_u
        hi[i] = hi_u - (1 << 64) if hi_u >= (1 << 63) else hi_u
    return hi, lo


def ints_from_np(hi, lo) -> list:
    """(hi, lo) numpy planes -> Python ints."""
    out = []
    for h, l in zip(hi.tolist(), lo.tolist()):
        u = ((h & ((1 << 64) - 1)) << 64) | (l & ((1 << 64) - 1))
        out.append(u - (1 << 128) if u >= (1 << 127) else u)
    return out
