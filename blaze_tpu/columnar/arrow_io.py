"""Arrow <-> device batch conversion.

Ref analog: the JVM<->native Arrow boundary — ArrowFFIStreamImportIterator /
ArrowFFIExportIterator (spark-extension arrowio) and the FFI stream export in
blaze/src/rt.rs:76-80. Our native engine lives in-process with pyarrow, so the
C-data-interface crossing is pyarrow's; this module does the host-side layout
transform (variable-length Arrow -> fixed-width padded device arrays) with
vectorized numpy, then one host->device transfer per column.
"""

from __future__ import annotations

from typing import List, Optional

import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from blaze_tpu.columnar.batch import (
    Column, ColumnBatch, StringData, bucket_capacity, bucket_width, _pad_validity,
    pull_rows,
)
from blaze_tpu.columnar import types as T


_ARROW_TO_KIND = {
    pa.types.is_boolean: T.BOOLEAN,
    pa.types.is_int8: T.INT8,
    pa.types.is_int16: T.INT16,
    pa.types.is_int32: T.INT32,
    pa.types.is_int64: T.INT64,
    pa.types.is_float32: T.FLOAT32,
    pa.types.is_float64: T.FLOAT64,
    pa.types.is_date32: T.DATE,
    pa.types.is_null: T.NULL,
}


def dtype_from_arrow(at: pa.DataType) -> T.DataType:
    for pred, dt in _ARROW_TO_KIND.items():
        if pred(at):
            return dt
    if pa.types.is_string(at) or pa.types.is_large_string(at):
        return T.STRING
    if pa.types.is_binary(at) or pa.types.is_large_binary(at):
        return T.BINARY
    if pa.types.is_timestamp(at):
        return T.TIMESTAMP
    if pa.types.is_decimal(at):
        return T.decimal(at.precision, at.scale)
    if pa.types.is_list(at) or pa.types.is_large_list(at):
        return T.list_of(dtype_from_arrow(at.value_type))
    if pa.types.is_map(at):
        return T.map_of(dtype_from_arrow(at.key_type), dtype_from_arrow(at.item_type))
    if pa.types.is_struct(at):
        return T.struct_of(T.Field(f.name, dtype_from_arrow(f.type), f.nullable) for f in at)
    if pa.types.is_dictionary(at):
        return dtype_from_arrow(at.value_type)
    raise TypeError(f"unsupported arrow type {at}")


def dtype_to_arrow(dt: T.DataType) -> pa.DataType:
    k = T.TypeKind
    m = {
        k.NULL: pa.null(), k.BOOLEAN: pa.bool_(), k.INT8: pa.int8(),
        k.INT16: pa.int16(), k.INT32: pa.int32(), k.INT64: pa.int64(),
        k.FLOAT32: pa.float32(), k.FLOAT64: pa.float64(), k.STRING: pa.string(),
        k.BINARY: pa.binary(), k.DATE: pa.date32(), k.TIMESTAMP: pa.timestamp("us"),
    }
    if dt.kind in m:
        return m[dt.kind]
    if dt.kind == k.DECIMAL:
        return pa.decimal128(dt.precision, dt.scale)
    if dt.kind == k.LIST:
        return pa.list_(dtype_to_arrow(dt.element))
    if dt.kind == k.MAP:
        return pa.map_(dtype_to_arrow(dt.key), dtype_to_arrow(dt.element))
    if dt.kind == k.STRUCT:
        return pa.struct([pa.field(f.name, dtype_to_arrow(f.dtype), f.nullable) for f in dt.fields])
    raise TypeError(f"unsupported dtype {dt}")


def schema_from_arrow(s: pa.Schema) -> T.Schema:
    return T.Schema([T.Field(f.name, dtype_from_arrow(f.type), f.nullable) for f in s])


def schema_to_arrow(s: T.Schema) -> pa.Schema:
    return pa.schema([pa.field(f.name, dtype_to_arrow(f.dtype), f.nullable) for f in s])


def _validity_np(arr: pa.Array) -> Optional[np.ndarray]:
    if arr.null_count == 0:
        return None
    return np.asarray(arr.is_valid())


def _pad1d(arr: np.ndarray, cap: int, np_dtype) -> np.ndarray:
    out = np.zeros((cap,), np_dtype)
    out[: arr.shape[0]] = arr
    return out


def _varbin_to_fixed(arr: pa.Array, cap: int, min_width: int = 0):
    """Variable-length binary arrow array -> (cap, W) uint8 matrix + lengths.

    Vectorized: gathers data[offset[i] + j] for j < len[i] with clipping.
    """
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    arr = arr.cast(pa.large_binary())
    n = len(arr)
    buf_off = arr.buffers()[1]
    offsets = np.frombuffer(buf_off, np.int64, count=n + 1, offset=arr.offset * 8)
    databuf = arr.buffers()[2]
    data = np.frombuffer(databuf, np.uint8) if databuf is not None else np.zeros(0, np.uint8)
    lengths = (offsets[1:] - offsets[:-1]).astype(np.int32)
    max_len = int(lengths.max()) if n else 0
    width = bucket_width(max(max_len, min_width, 1))
    j = np.arange(width, dtype=np.int64)
    gather_idx = np.clip(offsets[:-1, None] + j[None, :], 0, max(len(data) - 1, 0))
    mat = (data[gather_idx] if len(data) else np.zeros((n, width), np.uint8)) * (
        j[None, :] < lengths[:, None]
    ).astype(np.uint8)
    out_mat = np.zeros((cap, width), np.uint8)
    out_mat[:n] = mat
    return out_mat, _pad1d(lengths, cap, np.int32)


_ZC_KINDS = {
    T.TypeKind.INT8: pa.int8(), T.TypeKind.INT16: pa.int16(),
    T.TypeKind.INT32: pa.int32(), T.TypeKind.INT64: pa.int64(),
    T.TypeKind.FLOAT32: pa.float32(), T.TypeKind.FLOAT64: pa.float64(),
    T.TypeKind.DATE: pa.date32(),
}


def _numeric_zero_copy(arr, dtype: T.DataType, cap: int) -> Optional[Column]:
    """No-host-copy ingest for null-free fixed-width columns (north-star
    item, SURVEY.md §7 step 1): the Arrow data buffer is viewed in place
    (np.frombuffer), devices-put in ONE DMA, and padded to the capacity
    bucket ON DEVICE. The general path below pays fill_null + astype +
    pad — three host copies — before the same DMA."""
    at = _ZC_KINDS.get(dtype.kind)
    if at is None or arr.type != at or arr.null_count != 0:
        return None
    n = len(arr)
    buf = arr.buffers()[1]
    if buf is None:
        return None
    itemsize = dtype.np_dtype().itemsize
    view = np.frombuffer(buf, dtype.np_dtype(), count=n,
                         offset=arr.offset * itemsize)
    if cap > n:
        # pad on HOST: one upload DMA total. Padding on device costs an
        # eager scatter dispatch per column; the host memcpy is cheaper.
        full = np.zeros((cap,), dtype.np_dtype())
        full[:n] = view
        view = full
    return Column(dtype, jnp.asarray(view), None)


def column_from_arrow(arr, dtype: T.DataType, cap: int) -> Column:
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if pa.types.is_dictionary(arr.type):
        arr = arr.cast(arr.type.value_type)
    fast = _numeric_zero_copy(arr, dtype, cap)
    if fast is not None:
        return fast
    n = len(arr)
    validity = _validity_np(arr)
    if dtype.kind == T.TypeKind.LIST:
        from blaze_tpu.columnar.batch import ListData

        la = arr.cast(pa.large_list(dtype_to_arrow(dtype.element)))
        offs_raw = np.frombuffer(la.buffers()[1], np.int64,
                                 count=n + 1, offset=la.offset * 8)
        base = offs_raw[0]
        lens = (offs_raw[1:] - offs_raw[:-1]).astype(np.int32)
        offsets = np.zeros((cap + 1,), np.int32)
        offsets[1:n + 1] = np.cumsum(lens)
        offsets[n + 1:] = offsets[n]
        flat = la.values.slice(base, offs_raw[-1] - base)
        ecap = bucket_capacity(len(flat))
        elems = column_from_arrow(flat, dtype.element, ecap)
        return Column(dtype, ListData(jnp.asarray(offsets), elems),
                      _pad_validity(validity, n, cap))
    if dtype.is_string_like:
        mat, lens = _varbin_to_fixed(arr, cap)
        col = Column(dtype, StringData(jnp.asarray(mat), jnp.asarray(lens)),
                     _pad_validity(validity, n, cap))
        return col.normalized()
    if dtype.kind == T.TypeKind.NULL:
        from blaze_tpu.columnar.batch import _zero_column

        return _zero_column(dtype, cap)
    if dtype.is_decimal:
        from blaze_tpu.runtime import trace

        # the decimal128 -> int64 plane step of this column, as a span of
        # its own (benchmark metric decimal_decode_s). The buffer holds
        # 16-byte little-endian two's-complement values: the low word is
        # the unscaled value for p <= 18, and (lo, hi) are exactly the
        # engine's wide limb planes. Null slots keep what the buffer
        # holds; normalized() zeroes them.
        with trace.span("decimal_decode", rows=n,
                        wide=dtype.wide_decimal):
            want = pa.decimal128(dtype.precision, dtype.scale)
            d = arr if arr.type == want else arr.cast(want)
            words = np.frombuffer(d.buffers()[1], np.int64, count=2 * n,
                                  offset=d.offset * 16)
            np_vals = words[0::2].copy()
            hi = words[1::2].copy() if dtype.wide_decimal else None
        if dtype.wide_decimal:
            from blaze_tpu.columnar.batch import StructData

            return Column(dtype, StructData(
                [Column(T.INT64, jnp.asarray(_pad1d(hi, cap, np.int64)),
                        None),
                 Column(T.INT64, jnp.asarray(_pad1d(np_vals, cap, np.int64)),
                        None)]),
                _pad_validity(validity, n, cap)).normalized()
    elif dtype.kind == T.TypeKind.TIMESTAMP:
        np_vals = np.asarray(arr.cast(pa.timestamp("us")).fill_null(0), np.int64)
    elif dtype.kind == T.TypeKind.BOOLEAN:
        np_vals = np.asarray(arr.fill_null(False))
    else:
        np_vals = np.asarray(arr.fill_null(0)).astype(dtype.np_dtype())
    col = Column(dtype, jnp.asarray(_pad1d(np_vals, cap, dtype.np_dtype())),
                 _pad_validity(validity, n, cap))
    return col.normalized()


def batch_from_arrow(rb: pa.RecordBatch, capacity: Optional[int] = None,
                     schema: Optional[T.Schema] = None) -> ColumnBatch:
    schema = schema or schema_from_arrow(rb.schema)
    cap = capacity or bucket_capacity(rb.num_rows)
    cols = [column_from_arrow(rb.column(i), f.dtype, cap) for i, f in enumerate(schema)]
    return ColumnBatch(schema, cols, jnp.asarray(rb.num_rows, jnp.int32), cap)


def batch_to_arrow(batch: ColumnBatch) -> pa.RecordBatch:
    n = pull_rows(batch, "d2h.arrow_rows")
    arrays: List[pa.Array] = []
    for f, c in zip(batch.schema, batch.columns):
        valid = np.asarray(c.valid_mask())[:n]
        if c.is_list:
            sub = ColumnBatch(T.Schema([T.Field(f.name, f.dtype)]), [c],
                              batch.num_rows, batch.capacity)
            vals = sub.to_numpy()[f.name]
            arrays.append(pa.array(vals, dtype_to_arrow(f.dtype)))
            continue
        if c.is_string:
            b = np.asarray(c.data.bytes)[:n]
            l = np.asarray(c.data.lengths)[:n]
            vals = [b[i, : l[i]].tobytes() for i in range(n)]
            if f.dtype.kind == T.TypeKind.STRING:
                py = [v.decode("utf-8", "replace") if valid[i] else None for i, v in enumerate(vals)]
                arrays.append(pa.array(py, pa.string()))
            else:
                py = [v if valid[i] else None for i, v in enumerate(vals)]
                arrays.append(pa.array(py, pa.binary()))
            continue
        if f.dtype.wide_decimal:
            from decimal import Decimal

            from blaze_tpu.columnar import int128 as i128

            hi = np.asarray(c.data.children[0].data)[:n]
            lo = np.asarray(c.data.children[1].data)[:n]
            ints = i128.ints_from_np(hi, lo)
            py = [Decimal(ints[i]).scaleb(-f.dtype.scale) if valid[i]
                  else None for i in range(n)]
            arrays.append(pa.array(py, dtype_to_arrow(f.dtype)))
            continue
        d = np.asarray(c.data)[:n]
        at = dtype_to_arrow(f.dtype)
        if f.dtype.is_decimal:
            from decimal import Decimal

            py = [Decimal(int(v)).scaleb(-f.dtype.scale) if valid[i] else None
                  for i, v in enumerate(d)]
            arrays.append(pa.array(py, at))
        elif f.dtype.kind == T.TypeKind.NULL:
            arrays.append(pa.nulls(n))
        else:
            arrays.append(pa.array(d, type=at, mask=None if valid.all() else ~valid))
    return pa.RecordBatch.from_arrays(arrays, schema=schema_to_arrow(batch.schema))
