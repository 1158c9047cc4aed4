"""Compact columnar batch serialization with zstd framing.

Ref: datafusion-ext-commons io/batch_serde.rs (custom column-wise format +
zstd level-1 frames, bit-packed validity :257-302) — the wire format used for
shuffle segments, spills and broadcast payloads. Same role here; the layout
is schema-driven (the decoder is handed the plan schema, like the
reference's read_batch) and numpy-vectorized on the host side. A C++
implementation of the same format lives in native/ for the JNI path.

Row-range serialization (`HostBatch.serialize(lo, hi)`) exists because the
shuffle writer serializes per-partition slices of one partition-id-sorted
batch — one device->host pull, many frames (ref sort_repartitioner.rs).

Frame layout (little-endian):
  u32 magic "BTB1" | u32 raw_len | u32 comp_len | zstd(payload)
Payload:
  u32 num_rows | u16 num_cols | colblock*
Colblock:
  u8 has_validity | [ceil(n/8) bytes packed validity (LSB-first)]
  numeric/bool: n * itemsize raw LE values
  string/binary: u32 total | n x u32 lengths | concatenated bytes
  string/binary (dict): u32 0xFFFFFFFF | u32 K | u32 dict_total |
                        K x u32 dict_lengths | dict bytes | n x u32 codes
  null column: nothing

The dict form (conf.dict_encode_strings) writes each distinct string once
plus per-row int32 codes; 0xFFFFFFFF is an impossible plain `total` (a
frame is capped well below 4 GiB) so old frames decode unchanged. Code 0
is ALWAYS the empty string (the DictData invariant). A slice whose
cardinality exceeds conf.dict_max_cardinality, or where the dict form is
not smaller, falls back to the plain layout per column.
"""

from __future__ import annotations

import dataclasses
import io
import struct
import time
from typing import BinaryIO, Iterator, List, Optional

import numpy as np
import zstandard

from blaze_tpu.columnar.batch import (
    ColumnBatch, bucket_capacity, pull_rows,
)
from blaze_tpu.columnar.types import Schema, TypeKind
from blaze_tpu.config import conf
from blaze_tpu.runtime import faults, monitor, trace

MAGIC = b"BTB1"
DICT_SENTINEL = 0xFFFFFFFF  # impossible plain string `total` (frames < 2 GiB)


@dataclasses.dataclass
class _HostCol:
    kind: str   # "num" | "str" | "dict" | "list" | "struct" | "null"
    data: Optional[np.ndarray]     # (n,) values | (n, W) bytes | None;
                                   # dict: (K, W) dictionary bytes
    lengths: Optional[np.ndarray]  # strings/lists: per-row lengths;
                                   # dict: (K,) dictionary entry lengths
    validity: Optional[np.ndarray]
    child: Optional["_HostCol"] = None        # lists: element column
    child_offsets: Optional[np.ndarray] = None  # lists: (n+1,) elem offsets
    children: Optional[List["_HostCol"]] = None  # structs: field columns
    codes: Optional[np.ndarray] = None  # dict: (n,) int32 codes


@dataclasses.dataclass
class HostBatch:
    """Live rows of a batch pulled to host once, sliceable for serde."""
    schema: Schema
    cols: List[_HostCol]
    num_rows: int

    def serialize(self, lo: int = 0, hi: Optional[int] = None,
                  level: Optional[int] = None) -> bytes:
        # timing window opens before fault injection: an injected encode
        # stall is real wall time and must land in serde_encode_ms
        t0 = time.perf_counter_ns()
        if conf.fault_injection_spec:
            faults.inject("serde.encode")
        hi = self.num_rows if hi is None else hi
        n = max(hi - lo, 0)
        out = io.BytesIO()
        out.write(struct.pack("<IH", n, len(self.cols)))
        for c in self.cols:
            _write_col(out, c, lo, hi)
        raw = out.getvalue()
        comp = zstandard.ZstdCompressor(
            level=level if level is not None else conf.zstd_level,
        ).compress(raw)
        frame = MAGIC + struct.pack("<II", len(raw), len(comp)) + comp
        if conf.monitor_enabled:
            # copied: the raw payload rebuilt row-by-row into the frame;
            # moved: the compressed frame that actually crosses
            monitor.count_copy("serde", len(raw), moved=len(frame))
            monitor.count_time("serde_encode", time.perf_counter_ns() - t0)
        return frame


def _dict_encode_slice(b: np.ndarray, lens: np.ndarray):
    """Distinct strings of a slice -> (dict (K, W), dict_lens (K,),
    codes (n,)) with entry 0 == empty string, or None past the
    cardinality cap. Length is part of the uniqueness key: b"a\\x00"
    and b"a" share canonical bytes but are different strings."""
    n = int(lens.shape[0])
    w = int(b.shape[1]) if b.ndim == 2 else 0
    pos = np.arange(w)[None, :] < lens[:, None]
    canon = np.where(pos, b, 0).astype(np.uint8, copy=False)
    key = np.concatenate(
        [canon, lens.astype("<u4")[:, None].view(np.uint8)], axis=1)
    # prepend an all-zero row: it sorts first, pinning code 0 to the
    # empty string (the DictData invariant normalized()/padding rely on)
    key = np.vstack([np.zeros((1, w + 4), np.uint8), key])
    uniq, inv = np.unique(key, axis=0, return_inverse=True)
    if uniq.shape[0] - 1 > conf.dict_max_cardinality:
        return None
    dmat = np.ascontiguousarray(uniq[:, :w])
    dlens = np.ascontiguousarray(uniq[:, w:]).view("<u4").reshape(-1)
    return dmat, dlens, inv.reshape(-1)[1:].astype(np.uint32)


def _write_dict_block(out, dmat: np.ndarray, dlens: np.ndarray,
                      codes: np.ndarray) -> None:
    dlens = dlens.astype(np.uint32)
    out.write(struct.pack("<III", DICT_SENTINEL, dlens.shape[0],
                          int(dlens.sum())))
    out.write(dlens.tobytes())
    if dmat.size:
        pos = np.arange(dmat.shape[1])[None, :] < dlens[:, None]
        out.write(np.ascontiguousarray(dmat)[pos].tobytes())
    out.write(codes.astype(np.uint32).tobytes())
    if conf.monitor_enabled:
        monitor.count_zerocopy("dict_cols_encoded")


def _write_col(out, c: _HostCol, lo: int, hi: int) -> None:
    has_v = c.validity is not None
    out.write(struct.pack("<B", 1 if has_v else 0))
    if has_v:
        out.write(np.packbits(c.validity[lo:hi].astype(np.uint8),
                              bitorder="little").tobytes())
    if c.kind == "null":
        return
    if c.kind == "dict":
        # already encoded: ship the dictionary + the slice's codes —
        # never re-concatenate payload bytes per hop
        _write_dict_block(out, c.data, c.lengths, c.codes[lo:hi])
        return
    if c.kind == "str":
        lens = c.lengths[lo:hi].astype(np.uint32)
        total = int(lens.sum())
        n = int(lens.shape[0])
        if conf.dict_encode_strings and n:
            enc = _dict_encode_slice(c.data[lo:hi], lens)
            if enc is not None:
                dmat, dlens, codes = enc
                dict_sz = 12 + 4 * dlens.shape[0] + int(dlens.sum()) + 4 * n
                if dict_sz < 4 + 4 * n + total:
                    if conf.trace_enabled:
                        from blaze_tpu.runtime import trace
                        trace.event("dict_encode", rows=n,
                                    entries=int(dlens.shape[0]))
                    _write_dict_block(out, dmat, dlens, codes)
                    return
        out.write(struct.pack("<I", total) + lens.tobytes())
        if total:
            b = c.data[lo:hi]
            pos = np.arange(b.shape[1])[None, :] < lens[:, None]
            out.write(b[pos].tobytes())
        return
    if c.kind == "list":
        lens = c.lengths[lo:hi].astype(np.uint32)
        elo, ehi = int(c.child_offsets[lo]), int(c.child_offsets[hi])
        out.write(struct.pack("<I", ehi - elo) + lens.tobytes())
        _write_col(out, c.child, elo, ehi)
        return
    if c.kind == "struct":
        for ch in c.children:
            _write_col(out, ch, lo, hi)
        return
    out.write(np.ascontiguousarray(c.data[lo:hi]).tobytes())


def _host_col(col, n: int) -> _HostCol:
    validity = (np.asarray(col.validity)[:n].astype(bool)
                if col.validity is not None else None)
    if col.dtype.kind == TypeKind.NULL:
        return _HostCol("null", None, None, validity)
    if col.is_list:
        offs = np.asarray(col.data.offsets)[:n + 1].astype(np.int64)
        n_elems = int(offs[n]) if n else 0
        child = _host_col(col.data.elements, n_elems)
        lens = (offs[1:] - offs[:-1]).astype(np.int32)
        return _HostCol("list", None, lens, validity, child, offs)
    if col.is_struct:
        return _HostCol("struct", None, None, validity,
                        children=[_host_col(ch, n)
                                  for ch in col.data.children])
    if col.is_dict:
        # keep the encoded form: pull codes + the small dictionary only
        # (the expanded matrix is never materialized on either side)
        dd = col.data
        return _HostCol("dict", np.asarray(dd.dict_bytes),
                        np.asarray(dd.dict_lengths).astype(np.int32),
                        validity,
                        codes=np.asarray(dd.codes)[:n].astype(np.int32))
    if col.is_string:
        return _HostCol("str", np.asarray(col.data.bytes)[:n],
                        np.asarray(col.data.lengths)[:n], validity)
    d = np.asarray(col.data)[:n]
    if d.dtype == np.bool_:
        d = d.astype(np.uint8)
    return _HostCol("num", d, None, validity)


def _col_nbytes(c: _HostCol) -> int:
    n = 0
    for arr in (c.data, c.lengths, c.validity, c.child_offsets, c.codes):
        if arr is not None:
            n += arr.nbytes
    if c.child is not None:
        n += _col_nbytes(c.child)
    if c.children:
        n += sum(_col_nbytes(ch) for ch in c.children)
    return n


def host_batch_nbytes(hb: HostBatch) -> int:
    """Host-side footprint of a pulled batch — the unit the monitor's
    "ffi" boundary accounts for device->host pulls and host->device
    uploads."""
    return sum(_col_nbytes(c) for c in hb.cols)


def to_host(batch: ColumnBatch) -> HostBatch:
    if conf.fault_injection_spec:
        faults.inject("device.get")
    # the d2h span starts before the row-count pull: that pull is where
    # the host waits for the device to finish the batch
    with trace.span("d2h", what="to_host") as sp:
        n = pull_rows(batch, "d2h.rows")
        hb = HostBatch(batch.schema,
                       [_host_col(c, n) for c in batch.columns], n)
        if conf.monitor_enabled or conf.trace_enabled:
            nbytes = host_batch_nbytes(hb)
            sp.set(rows=n, bytes=nbytes)
            if conf.monitor_enabled:
                monitor.count_copy("ffi", nbytes)
    return hb


def serialize_batch(batch: ColumnBatch, level: Optional[int] = None) -> bytes:
    return to_host(batch).serialize(level=level)


def serialize_slice(hb: HostBatch, lo: int, hi: int) -> bytes:
    """Row-range frame, preferring the C++ encoder (native/) when loaded —
    identical payload bytes, one fewer python loop on the shuffle path."""
    from blaze_tpu import native

    # the C++ encoder predates the dict colblock: route string columns
    # through the python encoder while dict encoding is on so they ship
    # (dict, codes) instead of plain payload bytes
    dict_strings = conf.dict_encode_strings and any(
        c.kind in ("str", "dict") for c in hb.cols)
    if native.available() and not dict_strings and \
            all(c.kind in ("num", "str", "null") for c in hb.cols):
        t0 = time.perf_counter_ns()
        if conf.fault_injection_spec:
            faults.inject("serde.encode")
        frame = native.serialize_host_batch(hb, lo, hi, conf.zstd_level)
        if conf.monitor_enabled:
            (raw_len,) = struct.unpack_from("<I", frame, 4)
            monitor.count_copy("serde", raw_len, moved=len(frame))
            monitor.count_time("serde_encode", time.perf_counter_ns() - t0)
        return frame
    return hb.serialize(lo, hi)


def write_batch(fp: BinaryIO, batch: ColumnBatch) -> int:
    buf = serialize_batch(batch)
    fp.write(buf)
    return len(buf)


def _read_exact(fp: BinaryIO, n: int) -> bytes:
    b = fp.read(n)
    if len(b) != n:
        raise EOFError("truncated batch frame")
    return b


def deserialize_batch(buf: bytes, schema: Schema,
                      capacity: Optional[int] = None,
                      dctx=None) -> ColumnBatch:
    t0 = time.perf_counter_ns()
    if conf.fault_injection_spec:
        faults.inject("serde.decode")
    if buf[:4] != MAGIC:
        raise ValueError("bad batch frame magic")
    raw_len, comp_len = struct.unpack("<II", buf[4:12])
    raw = (dctx or zstandard.ZstdDecompressor()).decompress(
        buf[12:12 + comp_len], max_output_size=raw_len)
    if conf.monitor_enabled:
        monitor.count_copy("serde", raw_len, moved=12 + comp_len)
    b = _decode(io.BytesIO(raw), schema, capacity)
    if conf.monitor_enabled:
        monitor.count_time("serde_decode", time.perf_counter_ns() - t0)
    return b


def read_batch(fp: BinaryIO, schema: Schema,
               capacity: Optional[int] = None,
               dctx=None) -> Optional[ColumnBatch]:
    """Read one frame; None at clean EOF. `dctx` lets stream readers
    reuse one decompressor across frames (context setup dominates small
    frames); per-frame construction remains the one-shot default."""
    t0 = time.perf_counter_ns()
    if conf.fault_injection_spec:
        faults.inject("serde.decode")
    head = fp.read(12)
    if not head:
        return None
    if len(head) != 12 or head[:4] != MAGIC:
        raise ValueError("bad batch frame header")
    raw_len, comp_len = struct.unpack("<II", head[4:])
    comp = _read_exact(fp, comp_len)
    raw = (dctx or zstandard.ZstdDecompressor()).decompress(
        comp, max_output_size=raw_len)
    if conf.monitor_enabled:
        monitor.count_copy("serde", raw_len, moved=12 + comp_len)
    b = _decode(io.BytesIO(raw), schema, capacity)
    if conf.monitor_enabled:
        # window covers the file read + decompress + decode: read-side
        # shuffle/spill file I/O is deliberately billed to serde_decode
        monitor.count_time("serde_decode", time.perf_counter_ns() - t0)
    return b


def read_batches(fp: BinaryIO, schema: Schema) -> Iterator[ColumnBatch]:
    dctx = zstandard.ZstdDecompressor()
    while True:
        b = read_batch(fp, schema, dctx=dctx)
        if b is None:
            return
        yield b


def read_batch_host(fp: BinaryIO, schema: Schema,
                    dctx=None) -> Optional[HostBatch]:
    """Decode one frame to host numpy columns (no device upload) — the
    spill-merge and host-coalescing paths (ops/host_sort.py) stay entirely
    on the host until one bulk upload."""
    t0 = time.perf_counter_ns()
    if conf.fault_injection_spec:
        faults.inject("serde.decode")
    head = fp.read(12)
    if not head:
        return None
    if len(head) != 12 or head[:4] != MAGIC:
        raise ValueError("bad batch frame header")
    raw_len, comp_len = struct.unpack("<II", head[4:])
    comp = _read_exact(fp, comp_len)
    raw = (dctx or zstandard.ZstdDecompressor()).decompress(
        comp, max_output_size=raw_len)
    if conf.monitor_enabled:
        monitor.count_copy("serde", raw_len, moved=12 + comp_len)
    bio = io.BytesIO(raw)
    n, ncols = struct.unpack("<IH", _read_exact(bio, 6))
    assert ncols == len(schema.fields), (ncols, len(schema.fields))
    hb = HostBatch(schema, [_decode_col_host(bio, f.dtype, n)
                            for f in schema], n)
    if conf.monitor_enabled:
        monitor.count_time("serde_decode", time.perf_counter_ns() - t0)
    return hb


def deserialize_batch_host(buf, schema: Schema) -> HostBatch:
    """Decode one frame held in memory. Accepts bytes OR a zero-copy
    memoryview (the mmap shuffle fast path): decompression reads
    straight from the caller's buffer, so a mapped frame is never
    duplicated host-side before the (inherent) decompress."""
    t0 = time.perf_counter_ns()
    if conf.fault_injection_spec:
        faults.inject("serde.decode")
    mv = memoryview(buf)
    if len(mv) == 0:
        raise ValueError("empty batch frame")
    if len(mv) < 12 or mv[:4] != MAGIC:
        raise ValueError("bad batch frame header")
    raw_len, comp_len = struct.unpack("<II", mv[4:12])
    raw = zstandard.ZstdDecompressor().decompress(
        mv[12:12 + comp_len], max_output_size=raw_len)
    if conf.monitor_enabled:
        monitor.count_copy("serde", raw_len, moved=12 + comp_len)
    bio = io.BytesIO(raw)
    n, ncols = struct.unpack("<IH", _read_exact(bio, 6))
    assert ncols == len(schema.fields), (ncols, len(schema.fields))
    hb = HostBatch(schema, [_decode_col_host(bio, f.dtype, n)
                            for f in schema], n)
    if conf.monitor_enabled:
        monitor.count_time("serde_decode", time.perf_counter_ns() - t0)
    return hb


def read_batches_host(fp: BinaryIO, schema: Schema) -> Iterator[HostBatch]:
    dctx = zstandard.ZstdDecompressor()
    while True:
        hb = read_batch_host(fp, schema, dctx=dctx)
        if hb is None:
            return
        yield hb


def _read_dict_block(fp: BinaryIO, n: int):
    """Read a dict colblock body (after the sentinel) -> host-form
    (dict (K, w), dict_lens int32 (K,), codes int32 (n,))."""
    K, dict_total = struct.unpack("<II", _read_exact(fp, 8))
    dlens = np.frombuffer(_read_exact(fp, 4 * K), np.uint32)
    payload = np.frombuffer(_read_exact(fp, dict_total), np.uint8)
    w = max(int(dlens.max()) if K else 1, 1)
    dmat = np.zeros((K, w), np.uint8)
    if K:
        pos = np.arange(w)[None, :] < dlens[:, None]
        dmat[pos] = payload
    codes = np.frombuffer(_read_exact(fp, 4 * n), np.uint32).astype(np.int32)
    if conf.trace_enabled:
        from blaze_tpu.runtime import trace
        trace.event("dict_decode", rows=n, entries=K)
    return dmat, dlens.astype(np.int32), codes


def _decode_col_host(fp: BinaryIO, dtype, n: int) -> _HostCol:
    from blaze_tpu.columnar.types import wide_decimal_storage

    (hasv,) = struct.unpack("<B", _read_exact(fp, 1))
    validity = None
    if hasv:
        vb = _read_exact(fp, (n + 7) // 8)
        validity = np.unpackbits(np.frombuffer(vb, np.uint8), count=n,
                                 bitorder="little").astype(bool)
    if dtype.kind == TypeKind.NULL:
        return _HostCol("null", None, None,
                        validity if validity is not None
                        else np.zeros((n,), bool))
    if dtype.kind in (TypeKind.LIST, TypeKind.MAP):
        raise ValueError("host decode does not support list storage")
    if dtype.kind == TypeKind.STRUCT or dtype.wide_decimal:
        fields = (wide_decimal_storage(dtype).fields
                  if dtype.wide_decimal else dtype.fields)
        children = [_decode_col_host(fp, f.dtype, n) for f in fields]
        return _HostCol("struct", None, None, validity, children=children)
    if dtype.is_string_like:
        (total,) = struct.unpack("<I", _read_exact(fp, 4))
        if total == DICT_SENTINEL:
            dmat, dlens, codes = _read_dict_block(fp, n)
            return _HostCol("dict", dmat, dlens, validity, codes=codes)
        lens = np.frombuffer(_read_exact(fp, 4 * n), np.uint32)
        payload = np.frombuffer(_read_exact(fp, total), np.uint8)
        w = max(int(lens.max()) if n else 1, 1)
        mat = np.zeros((n, w), np.uint8)
        if n:
            pos = np.arange(w)[None, :] < lens[:, None]
            mat[pos] = payload
        return _HostCol("str", mat, lens.astype(np.int32), validity)
    if dtype.kind == TypeKind.BOOLEAN:
        raw = np.frombuffer(_read_exact(fp, n), np.uint8).astype(bool)
        return _HostCol("num", raw, None, validity)
    npdt = np.dtype(dtype.np_dtype())
    raw = np.frombuffer(_read_exact(fp, npdt.itemsize * n), npdt)
    return _HostCol("num", raw.astype(npdt), None, validity)


def _decode_col(fp: BinaryIO, dtype, n: int, cap: int):
    import jax.numpy as jnp

    from blaze_tpu.columnar.batch import (
        Column, ListData, StringData, bucket_width, _pad_validity,
    )

    (hasv,) = struct.unpack("<B", _read_exact(fp, 1))
    validity_np = None
    if hasv:
        vb = _read_exact(fp, (n + 7) // 8)
        validity_np = np.unpackbits(
            np.frombuffer(vb, np.uint8), count=n,
            bitorder="little").astype(bool)
    if dtype.kind == TypeKind.NULL:
        return Column(dtype, jnp.zeros((cap,), jnp.int8),
                      jnp.zeros((cap,), jnp.bool_))
    if dtype.kind in (TypeKind.LIST, TypeKind.MAP):
        from blaze_tpu.columnar.types import storage_element

        (total,) = struct.unpack("<I", _read_exact(fp, 4))
        lens = np.frombuffer(_read_exact(fp, 4 * n), np.uint32)
        ecap = bucket_capacity(total)
        elems = _decode_col(fp, storage_element(dtype), total, ecap)
        offsets = np.zeros((cap + 1,), np.int32)
        offsets[1:n + 1] = np.cumsum(lens.astype(np.int32))
        offsets[n + 1:] = offsets[n]
        return Column(dtype, ListData(jnp.asarray(offsets), elems),
                      _pad_validity(validity_np, n, cap))
    if dtype.kind == TypeKind.STRUCT or dtype.wide_decimal:
        from blaze_tpu.columnar.batch import StructData
        from blaze_tpu.columnar.types import wide_decimal_storage

        fields = (wide_decimal_storage(dtype).fields
                  if dtype.wide_decimal else dtype.fields)
        children = [_decode_col(fp, f.dtype, n, cap) for f in fields]
        return Column(dtype, StructData(children),
                      _pad_validity(validity_np, n, cap))
    if dtype.is_string_like:
        (total,) = struct.unpack("<I", _read_exact(fp, 4))
        if total == DICT_SENTINEL:
            from blaze_tpu.columnar.batch import DictData, bucket_dict_rows

            dmat, dlens, codes_np = _read_dict_block(fp, n)
            K = dmat.shape[0]
            w = bucket_width(int(dlens.max()) if K else 1)
            kcap = bucket_dict_rows(max(K, 1))
            dict_b = np.zeros((kcap, w), np.uint8)
            dict_l = np.zeros((kcap,), np.int32)
            dict_b[:K, :dmat.shape[1]] = dmat
            dict_l[:K] = dlens
            # padding codes stay 0 -> empty string (the invariant)
            codes = np.zeros((cap,), np.int32)
            codes[:n] = codes_np
            col = Column(dtype, DictData(jnp.asarray(codes),
                                         jnp.asarray(dict_b),
                                         jnp.asarray(dict_l)),
                         _pad_validity(validity_np, n, cap))
            return col.normalized() if validity_np is not None else col
        lens = np.frombuffer(_read_exact(fp, 4 * n), np.uint32)
        payload = np.frombuffer(_read_exact(fp, total), np.uint8)
        w = bucket_width(int(lens.max()) if n else 1)
        mat = np.zeros((cap, w), np.uint8)
        if n:
            pos = np.arange(w)[None, :] < lens[:, None]
            mat[:n][pos] = payload
        col = Column(dtype,
                     StringData(jnp.asarray(mat),
                                jnp.asarray(np.pad(lens.astype(np.int32),
                                                   (0, cap - n)))),
                     _pad_validity(validity_np, n, cap))
        return col.normalized() if validity_np is not None else col
    if dtype.kind == TypeKind.BOOLEAN:
        raw = np.frombuffer(_read_exact(fp, n), np.uint8)
    else:
        npdt = np.dtype(dtype.np_dtype())
        raw = np.frombuffer(_read_exact(fp, npdt.itemsize * n), npdt)
    npdt = dtype.np_dtype()
    full = np.zeros((cap,), npdt)
    full[:n] = raw.astype(npdt)
    col = Column(dtype, jnp.asarray(full), _pad_validity(validity_np, n, cap))
    return col.normalized() if validity_np is not None else col


def _decode(fp: BinaryIO, schema: Schema,
            capacity: Optional[int]) -> ColumnBatch:
    import jax.numpy as jnp

    n, ncols = struct.unpack("<IH", _read_exact(fp, 6))
    assert ncols == len(schema.fields), (ncols, len(schema.fields))
    cap = capacity or bucket_capacity(n)
    cols = [_decode_col(fp, f.dtype, n, cap) for f in schema]
    return ColumnBatch(schema, cols, jnp.asarray(n, jnp.int32), cap)
