"""Bit-exact Spark Murmur3 (x86_32) in jax — the partitioning/hash-agg hash.

Ref: datafusion-ext-commons spark_hash.rs:27-90 (itself a port of Spark's
Murmur3_x86_32), and the shuffle partition computation hash(seed=42) then
pmod (datafusion-ext-plans shuffle/mod.rs:94-119). Semantics replicated:

  * int8/16/32/date, and boolean (as 1/0): hashInt(v) — sign-extended
  * int64/timestamp/decimal(p<=18 unscaled): hashLong(v) — two 32-bit halves
  * float32: hashInt(bits(f)), with -0.0 normalized to 0.0; float64 likewise
    via hashLong(bits(d))
  * string/binary: 4-byte little-endian chunks, then per-byte (signed) tail
  * null: leaves the running hash unchanged (multi-column hash chains seeds)

All arithmetic in uint32 with wrapping multiply; vectorized over rows.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from blaze_tpu.columnar import bits64
from blaze_tpu.columnar.batch import Column, StringData
from blaze_tpu.columnar.types import TypeKind

Array = jax.Array

# numpy scalars, NOT jnp: a module-level jnp constant is a concrete device
# array — creating it at import initializes a backend, and jit lifts it
# into a scalar buffer argument in some trace contexts, where the varying
# lifted-const count corrupts cached-executable reuse
# (runtime/jit_cache._with_stale_exec_retry is the backstop)
_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
_M5 = np.uint32(0xE6546B64)

SPARK_SHUFFLE_SEED = 42


def _rotl(x: Array, r: int) -> Array:
    return (x << r) | (x >> (32 - r))


def _mix_k1(k1: Array) -> Array:
    k1 = (k1 * _C1).astype(jnp.uint32)
    k1 = _rotl(k1, 15)
    return (k1 * _C2).astype(jnp.uint32)


def _mix_h1(h1: Array, k1: Array) -> Array:
    h1 = h1 ^ k1
    h1 = _rotl(h1, 13)
    return (h1 * jnp.uint32(5) + _M5).astype(jnp.uint32)


def _fmix(h1: Array, length: Array) -> Array:
    h1 = h1 ^ length.astype(jnp.uint32)
    h1 = h1 ^ (h1 >> 16)
    h1 = (h1 * jnp.uint32(0x85EBCA6B)).astype(jnp.uint32)
    h1 = h1 ^ (h1 >> 13)
    h1 = (h1 * jnp.uint32(0xC2B2AE35)).astype(jnp.uint32)
    return h1 ^ (h1 >> 16)


def hash_int32(v: Array, seed: Array) -> Array:
    """Spark hashInt: v int32 (already sign-extended for narrower types)."""
    h1 = _mix_h1(seed.astype(jnp.uint32), _mix_k1(v.astype(jnp.int32).view(jnp.uint32)))
    return _fmix(h1, jnp.uint32(4))


def hash_int64(v: Array, seed: Array) -> Array:
    high, low = bits64.i64_halves(v.astype(jnp.int64))
    return hash_u32_halves(high, low, seed)


def hash_u32_halves(high: Array, low: Array, seed: Array) -> Array:
    """hashLong over pre-split 64-bit words (low mixed first, like Spark)."""
    h1 = _mix_h1(seed.astype(jnp.uint32), _mix_k1(low))
    h1 = _mix_h1(h1, _mix_k1(high))
    return _fmix(h1, jnp.uint32(8))


def hash_bytes(s: StringData, seed: Array) -> Array:
    """Spark hashUnsafeBytes over the fixed-width matrix, masked by length."""
    cap, w = s.bytes.shape
    nwords = w // 4
    b = s.bytes.reshape(cap, nwords, 4).astype(jnp.uint32)
    words = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)  # LE
    lens = s.lengths
    nfull = lens // 4  # number of full 4-byte words

    h = jnp.broadcast_to(seed.astype(jnp.uint32), (cap,))
    # Under shard_map the loop body's output is varying over the manual
    # mesh axes (it reads the sharded batch data) while `h` derives only
    # from the replicated seed — fori_loop then rejects the carry type.
    # XOR-with-zero of batch data promotes h to the same varying type
    # without changing its value (fused away by XLA).
    h = h ^ (lens.astype(jnp.uint32) & jnp.uint32(0))

    def word_step(j, h):
        wj = jax.lax.dynamic_index_in_dim(words, j, axis=1, keepdims=False)
        return jnp.where(j < nfull, _mix_h1(h, _mix_k1(wj)), h)

    h = jax.lax.fori_loop(0, nwords, word_step, h)

    # tail: remaining 0-3 bytes, each as a SIGNED byte, mixed individually
    aligned = nfull * 4
    for t in range(3):
        pos = aligned + t
        byte = jnp.take_along_axis(
            s.bytes, jnp.clip(pos, 0, w - 1)[:, None], axis=1)[:, 0]
        sbyte = byte.astype(jnp.int8).astype(jnp.int32).view(jnp.uint32)
        h = jnp.where(pos < lens, _mix_h1(h, _mix_k1(sbyte)), h)
    return _fmix(h, lens.astype(jnp.uint32))


def _hash_wide_decimal(col: Column, seed: Array) -> Array:
    """Spark hash of a decimal with precision > 18: murmur3 over the
    MINIMAL big-endian two's-complement byte array of the unscaled
    BigInteger (java BigInteger.toByteArray), i.e. leading sign-filler
    bytes are stripped while one sign bit stays. Built as a (cap, 16)
    byte matrix + per-row length and fed to the string hasher."""
    hi = col.data.children[0].data
    lo = col.data.children[1].data
    # big-endian 16-byte representation
    parts = []
    for word in (hi, lo):
        for b in range(7, -1, -1):
            parts.append(((word >> (8 * b)) & jnp.int64(0xFF)
                          ).astype(jnp.uint8))
    be = jnp.stack(parts, axis=1)                      # (cap, 16)
    filler = jnp.where(hi < 0, jnp.uint8(0xFF), jnp.uint8(0))
    # count leading bytes droppable: byte == filler AND the NEXT byte's
    # sign bit matches (so the retained prefix still encodes the sign)
    nxt = jnp.concatenate([be[:, 1:], be[:, -1:]], axis=1)
    next_sign_ok = (nxt >> 7) == (filler[:, None] >> 7)
    droppable = (be == filler[:, None]) & next_sign_ok
    # prefix-run length of droppable (stop at first non-droppable),
    # capped at 15 so at least one byte remains
    run = jnp.cumprod(droppable.astype(jnp.int32), axis=1)
    strip = jnp.minimum(jnp.sum(run, axis=1), 15).astype(jnp.int32)
    length = jnp.int32(16) - strip
    # left-align: shift each row left by `strip` bytes
    idx = (jnp.arange(16, dtype=jnp.int32)[None, :] + strip[:, None])
    aligned = jnp.take_along_axis(be, jnp.minimum(idx, 15), axis=1)
    return hash_bytes(StringData(aligned, length), seed)


def hash_column(col: Column, seed: Array, row_mask: Optional[Array] = None) -> Array:
    """Chainable per-column hash: null (or padding) rows keep `seed`."""
    k = col.dtype.kind
    if col.is_string:
        h = hash_bytes(col.data, seed)
    elif k in (TypeKind.INT8, TypeKind.INT16, TypeKind.INT32, TypeKind.DATE):
        h = hash_int32(col.data.astype(jnp.int32), seed)
    elif k == TypeKind.BOOLEAN:
        h = hash_int32(col.data.astype(jnp.int32), seed)
    elif k == TypeKind.DECIMAL and col.dtype.wide_decimal:
        h = _hash_wide_decimal(col, seed)
    elif k in (TypeKind.INT64, TypeKind.TIMESTAMP, TypeKind.DECIMAL):
        h = hash_int64(col.data, seed)
    elif k == TypeKind.FLOAT32:
        f = col.data
        f = jnp.where(f == 0.0, jnp.float32(0.0), f)  # -0.0 -> 0.0
        h = hash_int32(f.view(jnp.int32), seed)
    elif k == TypeKind.FLOAT64:
        hi32, lo32 = bits64.f64_hash_halves(col.data)
        h = hash_u32_halves(hi32, lo32, seed)
    elif k == TypeKind.NULL:
        h = jnp.broadcast_to(seed.astype(jnp.uint32), (col.capacity,))
    else:
        raise TypeError(f"hash of {col.dtype} not supported on device")
    valid = col.valid_mask()
    if row_mask is not None:
        valid = valid & row_mask
    return jnp.where(valid, h, jnp.broadcast_to(seed.astype(jnp.uint32), h.shape))


def hash_columns(cols: Sequence[Column], seed: int = SPARK_SHUFFLE_SEED,
                 row_mask: Optional[Array] = None) -> Array:
    """Multi-column Spark hash: h = hash_col_n(...hash_col_1(seed))."""
    cap = cols[0].capacity
    h = jnp.full((cap,), jnp.uint32(seed))
    for c in cols:
        h = hash_column(c, h, row_mask)
    return h.view(jnp.int32)


def pmod(hash_i32: Array, num_partitions: int) -> Array:
    """Spark non-negative modulo: partition id in [0, P)."""
    p = jnp.int32(num_partitions)
    r = hash_i32 % p
    return jnp.where(r < 0, r + p, r)
