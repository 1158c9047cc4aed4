"""Host-side (numpy) row-encoded sort keys, run merge, and ordered collect.

The device engine sorts with a variadic ``lax.sort`` over unsigned key
arrays (ops/sort_keys.py). Two places must order rows where the data is
already host-resident and a device round trip costs more than the work:

  * merging spilled sort runs — frames live in host spill files, and a
    device-dispatch merge pays an upload and a dependent dispatch-and-pull
    (a host round trip) per pooled frame. The reference's merge is
    likewise host-side: a
    LoserTree over spilled cursors (datafusion-ext-commons
    loser_tree.rs:1-118, sort_exec.rs:419-475).
  * the driver collect of a root ORDER BY — the result is pulled to host
    anyway; ordering it during materialization is one numpy argsort
    instead of a multi-minute 2M-row ``lax.sort`` compile+dispatch.

Both build ONE memcmp-comparable key per row — the reference's design
(sort_exec.rs converts rows to Arrow ``Rows`` for byte comparison): each
sort column contributes big-endian bytes whose unsigned byte order equals
the requested (asc, nulls_first) Spark order; the concatenation is viewed
as a fixed-width ``S`` column that numpy compares with memcmp.

Order equivalence with the device encoder is exact for ints, dates,
timestamps, bools, strings (same 8-word prefix + length tiebreak) and
decimals. float64 needs care on TPU: the device orders by the
double-double (f32 hi, f32 lo) decomposition, which is COARSER than IEEE
total order — distinct f64s whose dd images coincide (|value| relative
differences below ~2^-46, e.g. long decimal fractions differing past the
dd mantissa) form one device TIE CLASS in arbitrary relative order
inside each device-sorted run. Host keys must therefore compare at the
SAME dd resolution when merging device-sorted runs: a finer (exact
IEEE) host key would consider such runs *unsorted* and the k-way merge
would emit rows out of order (observed as cross-frame inversions of dd
ties). `encode_keys` canonicalizes f64 planes to the dd image of the
device encoder (bits64.f64_total_order_keys) whenever the backend sorts
f64 at dd resolution; dd ties then merge in stable run order. On
backends with native 64-bit bitcast (CPU) both sides use exact IEEE
total order (NaN-above-inf and -0.0 == 0.0 match Spark either way).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import numpy as np

from blaze_tpu.columnar.serde import HostBatch, _HostCol
from blaze_tpu.columnar.types import Schema, TypeKind
from blaze_tpu.ops.sort_keys import DEFAULT_MAX_STRING_WORDS, SortSpec

_I64_MIN = np.int64(-(1 << 63))
_I32_MIN = np.uint32(1 << 31)


def _be(a: np.ndarray) -> np.ndarray:
    """(n,) unsigned -> (n, itemsize) uint8, big-endian."""
    k = a.dtype.itemsize
    return np.ascontiguousarray(
        a.astype(a.dtype.newbyteorder(">"))).view(np.uint8).reshape(-1, k)


def _f64_total_order(x: np.ndarray) -> np.ndarray:
    x = np.where(np.isnan(x), np.float64(np.nan), x)
    x = np.where(x == 0.0, np.float64(0.0), x)
    u = x.view(np.uint64)
    neg = (u >> np.uint64(63)) != 0
    return np.where(neg, ~u, u ^ np.uint64(1 << 63))


def _f32_total_order(x: np.ndarray) -> np.ndarray:
    x = np.where(np.isnan(x), np.float32(np.nan), x)
    x = np.where(x == np.float32(0.0), np.float32(0.0), x)
    u = x.view(np.uint32)
    neg = (u >> np.uint32(31)) != 0
    return np.where(neg, ~u, u ^ _I32_MIN)


_F64_EXACT: Optional[bool] = None


def _device_sorts_f64_exact() -> bool:
    """Whether the device encoder orders f64 by exact IEEE total order
    (64-bit bitcast available) or by the double-double decomposition.
    Cached: the answer is a property of the resolved backend."""
    global _F64_EXACT
    if _F64_EXACT is None:
        from blaze_tpu.columnar.bits64 import backend_has_bitcast64

        _F64_EXACT = bool(backend_has_bitcast64())
    return _F64_EXACT


def _f64_dd_parts(x: np.ndarray) -> List[np.ndarray]:
    """Numpy mirror of bits64._dd_split + per-limb f32 total order: the
    host key for merging DEVICE-sorted runs must compare at the device's
    dd resolution (see module docstring — a finer key would see dd tie
    classes as inversions and merge out of order)."""
    hi = x.astype(np.float32)
    with np.errstate(invalid="ignore"):
        lo = (x - hi.astype(np.float64)).astype(np.float32)
    lo = np.where(np.isfinite(hi), lo, np.float32(0.0))
    lo = np.where(np.isnan(x), np.float32(np.nan), lo)
    return [_be(_f32_total_order(hi)), _be(_f32_total_order(lo))]


def _value_parts(c: _HostCol, kind: TypeKind, wide: bool,
                 n: int) -> List[np.ndarray]:
    """Big-endian byte planes whose concatenated order is the ascending
    value order (mirrors ops/sort_keys.encode_column case by case)."""
    if kind == TypeKind.NULL:
        return []
    if wide:
        hi = c.children[0].data.astype(np.int64)
        lo = c.children[1].data.astype(np.int64)
        return [_be((hi ^ _I64_MIN).view(np.uint64)),
                _be(lo.view(np.uint64))]
    if kind in (TypeKind.STRING, TypeKind.BINARY):
        w = DEFAULT_MAX_STRING_WORDS * 8
        if c.kind == "dict":
            # build the prefix plane on the K dictionary entries, then
            # gather per-row by code — O(K) byte work instead of O(n)
            K, dw = c.data.shape
            dp = np.zeros((K, w), np.uint8)
            dp[:, :min(w, dw)] = c.data[:, :w]
            return [dp[c.codes],
                    _be(c.lengths[c.codes].astype(np.uint32))]
        b = c.data
        if b.shape[1] >= w:
            prefix = np.ascontiguousarray(b[:, :w])
        else:
            prefix = np.zeros((n, w), np.uint8)
            prefix[:, :b.shape[1]] = b
        return [prefix, _be(c.lengths.astype(np.uint32))]
    if kind == TypeKind.BOOLEAN:
        return [c.data.astype(np.uint8).reshape(-1, 1)]
    if kind == TypeKind.FLOAT64:
        x = c.data.astype(np.float64)
        if not _device_sorts_f64_exact():
            return _f64_dd_parts(x)
        return [_be(_f64_total_order(x))]
    if kind == TypeKind.FLOAT32:
        return [_be(_f32_total_order(c.data.astype(np.float32)))]
    if kind in (TypeKind.INT64, TypeKind.TIMESTAMP, TypeKind.DECIMAL):
        x = c.data.astype(np.int64)
        return [_be((x ^ _I64_MIN).view(np.uint64))]
    # int8/16/32/date — device widens to 32-bit; any self-consistent
    # width gives the same order
    x = c.data.astype(np.int32)
    return [_be(x.view(np.uint32) ^ _I32_MIN)]


def encode_keys(hb: HostBatch, specs: Sequence[SortSpec]) -> np.ndarray:
    """(n,) ``S``-bytes array: memcmp order == the requested sort order.
    Frames/host batches hold live rows only, so no liveness plane."""
    n = hb.num_rows
    planes: List[np.ndarray] = []
    for spec in specs:
        c = hb.cols[spec.col]
        f = hb.schema.fields[spec.col]
        # the flag plane follows the FIELD's nullability, not whether this
        # particular frame happened to carry a validity array — keys from
        # different frames/runs of the same column must share one byte
        # width or the memcmp merge compares misaligned planes
        if f.nullable:
            valid = (c.validity if c.validity is not None
                     else np.ones((n,), bool))
            first = spec.nulls_first
            flag = np.where(valid, np.uint8(1 if first else 0),
                            np.uint8(0 if first else 1))
            planes.append(flag.reshape(-1, 1))
        else:
            valid = None
        for p in _value_parts(c, f.dtype.kind, f.dtype.wide_decimal, n):
            if valid is not None:
                p = np.where(valid[:, None], p, np.uint8(0))
            planes.append(p if spec.asc else ~p)
    if not planes:
        return np.zeros((n,), "S1")
    mat = np.ascontiguousarray(np.concatenate(planes, axis=1))
    w = mat.shape[1]
    return mat.view(f"S{w}").reshape(-1)


def sort_perm(hb: HostBatch, specs: Sequence[SortSpec]) -> np.ndarray:
    return np.argsort(encode_keys(hb, specs), kind="stable")


# ---------------------------------------------------------------------------
# host batch manipulation (take / concat / device upload)
# ---------------------------------------------------------------------------

def host_supported(schema: Schema) -> bool:
    """LIST/MAP storage (at any nesting depth) is not row-sliceable
    host-side; those schemas keep the device paths."""
    return not any(_contains_list(f.dtype) for f in schema.fields)


def _contains_list(dtype) -> bool:
    if dtype.kind in (TypeKind.LIST, TypeKind.MAP):
        return True
    if dtype.kind == TypeKind.STRUCT and not dtype.wide_decimal:
        return any(_contains_list(f.dtype) for f in dtype.fields)
    return False


def _col_take(c: _HostCol, idx: np.ndarray) -> _HostCol:
    v = c.validity[idx] if c.validity is not None else None
    if c.kind == "null":
        return _HostCol("null", None, None, v)
    if c.kind == "struct":
        return _HostCol("struct", None, None, v,
                        children=[_col_take(ch, idx) for ch in c.children])
    if c.kind == "dict":
        # gather codes only; the dictionary is shared untouched
        return _HostCol("dict", c.data, c.lengths, v, codes=c.codes[idx])
    if c.kind == "str":
        return _HostCol("str", c.data[idx], c.lengths[idx], v)
    return _HostCol("num", c.data[idx], None, v)


def host_take(hb: HostBatch, idx: np.ndarray) -> HostBatch:
    return HostBatch(hb.schema, [_col_take(c, idx) for c in hb.cols],
                     len(idx))


def _col_concat(parts: List[_HostCol], kind: str) -> _HostCol:
    if any(p.validity is not None for p in parts):
        v = np.concatenate([
            p.validity if p.validity is not None
            else np.ones((_host_len(p),), bool) for p in parts])
    else:
        v = None
    if kind == "null":
        return _HostCol("null", None, None, v)
    if kind == "struct":
        nch = len(parts[0].children)
        children = [_col_concat([p.children[i] for p in parts],
                                parts[0].children[i].kind)
                    for i in range(nch)]
        return _HostCol("struct", None, None, v, children=children)
    if kind in ("str", "dict"):
        tot_entries = sum(p.data.shape[0] for p in parts
                          if p.kind == "dict")
        tot_rows = sum(_host_len(p) for p in parts)
        if all(p.kind == "dict" for p in parts) and \
                tot_entries <= max(tot_rows, 8):
            # merge dictionaries by offsetting codes: part 0's entry 0
            # (the empty string) keeps the code-0 invariant for the
            # merged dict; cross-part duplicate entries are harmless.
            # Past tot_rows entries (many merge rounds accumulating
            # dupes) the dict stops paying — expand instead.
            w = max(p.data.shape[1] for p in parts)
            dicts, dlens, codes, base = [], [], [], 0
            for p in parts:
                m = p.data
                if m.shape[1] < w:
                    mm = np.zeros((m.shape[0], w), np.uint8)
                    mm[:, :m.shape[1]] = m
                    m = mm
                dicts.append(m)
                dlens.append(p.lengths)
                codes.append(p.codes + np.int32(base))
                base += m.shape[0]
            return _HostCol("dict", np.concatenate(dicts),
                            np.concatenate(dlens), v,
                            codes=np.concatenate(codes))
        parts = [_dict_expand(p) for p in parts]
        w = max(p.data.shape[1] for p in parts)
        mats = []
        for p in parts:
            if p.data.shape[1] < w:
                m = np.zeros((p.data.shape[0], w), np.uint8)
                m[:, :p.data.shape[1]] = p.data
                mats.append(m)
            else:
                mats.append(p.data)
        return _HostCol("str", np.concatenate(mats),
                        np.concatenate([p.lengths for p in parts]), v)
    return _HostCol("num", np.concatenate([p.data for p in parts]), None, v)


def _dict_expand(c: _HostCol) -> _HostCol:
    """Decode a dict host col to the plain (n, W) string layout."""
    if c.kind != "dict":
        return c
    return _HostCol("str", c.data[c.codes], c.lengths[c.codes], c.validity)


def _host_len(c: _HostCol) -> int:
    if c.kind == "dict":
        return len(c.codes)
    if c.kind == "str":
        return len(c.lengths)
    if c.kind == "struct":
        return _host_len(c.children[0])
    if c.kind == "null":
        return len(c.validity) if c.validity is not None else 0
    return len(c.data)


def host_concat(parts: List[HostBatch]) -> HostBatch:
    if len(parts) == 1:
        return parts[0]
    schema = parts[0].schema
    cols = [_col_concat([p.cols[i] for p in parts], parts[0].cols[i].kind)
            for i in range(len(schema.fields))]
    return HostBatch(schema, cols, sum(p.num_rows for p in parts))


def _upload_col(c: _HostCol, f, n: int, cap: int):
    import jax.numpy as jnp

    from blaze_tpu.columnar.batch import (
        Column, StringData, StructData, bucket_width, _pad_validity,
    )
    from blaze_tpu.columnar.types import wide_decimal_storage

    validity = _pad_validity(c.validity, n, cap) \
        if c.validity is not None else None
    dtype = f.dtype
    if c.kind == "null":
        return Column(dtype, jnp.zeros((cap,), jnp.int8),
                      jnp.zeros((cap,), jnp.bool_))
    if c.kind == "struct":
        fields = (wide_decimal_storage(dtype).fields
                  if dtype.wide_decimal else dtype.fields)
        children = [_upload_col(ch, sf, n, cap)
                    for ch, sf in zip(c.children, fields)]
        return Column(dtype, StructData(children), validity)
    if c.kind == "dict":
        from blaze_tpu.columnar.batch import DictData, bucket_dict_rows

        K = c.data.shape[0]
        w = bucket_width(max(int(c.lengths.max()) if K else 1, 1))
        kcap = bucket_dict_rows(max(K, 1))
        db = np.zeros((kcap, w), np.uint8)
        cw = min(w, c.data.shape[1])
        db[:K, :cw] = c.data[:, :cw]
        dl = np.zeros((kcap,), np.int32)
        dl[:K] = c.lengths
        codes = np.zeros((cap,), np.int32)
        codes[:n] = c.codes
        col = Column(dtype, DictData(jnp.asarray(codes), jnp.asarray(db),
                                     jnp.asarray(dl)), validity)
        return col.normalized() if validity is not None else col
    if c.kind == "str":
        w = bucket_width(max(int(c.lengths.max()) if n else 1, 1))
        mat = np.zeros((cap, w), np.uint8)
        mat[:n, :min(w, c.data.shape[1])] = c.data[:, :w]
        lens = np.zeros((cap,), np.int32)
        lens[:n] = c.lengths
        col = Column(dtype, StringData(jnp.asarray(mat), jnp.asarray(lens)),
                     validity)
        return col.normalized() if validity is not None else col
    npdt = dtype.np_dtype()
    full = np.zeros((cap,), npdt)
    full[:n] = c.data.astype(npdt)
    col = Column(dtype, jnp.asarray(full), validity)
    return col.normalized() if validity is not None else col


def host_to_device(hb: HostBatch, capacity: Optional[int] = None):
    import jax.numpy as jnp

    from blaze_tpu.columnar.batch import ColumnBatch, bucket_capacity
    from blaze_tpu.config import conf
    from blaze_tpu.runtime import faults, trace

    if conf.fault_injection_spec:
        faults.inject("device.put")
    n = hb.num_rows
    # an h2d span: the host's time in the call (padding to capacity +
    # enqueue of the transfers), not the transfer itself
    with trace.span("h2d", rows=n, what="host_to_device") as sp:
        if conf.monitor_enabled or conf.trace_enabled:
            from blaze_tpu.columnar.serde import host_batch_nbytes

            nbytes = host_batch_nbytes(hb)
            sp.set(bytes=nbytes)
            if conf.monitor_enabled:
                from blaze_tpu.runtime import monitor

                monitor.count_copy("ffi", nbytes)
        cap = capacity or bucket_capacity(n)
        cols = [_upload_col(c, f, n, cap)
                for c, f in zip(hb.cols, hb.schema.fields)]
        return ColumnBatch(hb.schema, cols, jnp.asarray(n, jnp.int32), cap)


# ---------------------------------------------------------------------------
# k-way merge of sorted spill runs
# ---------------------------------------------------------------------------


def host_nbytes(hb: HostBatch) -> int:
    total = 0
    for c in hb.cols:
        total += _col_nbytes_host(c)
    return total


def _col_nbytes_host(c: _HostCol) -> int:
    n = 0
    if c.kind == "dict":
        n += c.data.size + 4 * len(c.lengths) + 4 * len(c.codes)
    elif c.kind == "str":
        n += c.data.size + 4 * len(c.lengths)
    elif c.kind == "struct":
        n += sum(_col_nbytes_host(ch) for ch in c.children)
    elif c.kind == "num":
        n += c.data.nbytes
    if c.validity is not None:
        n += len(c.validity)
    return n


def merge_sorted_host(frame_iters: List[Iterator[HostBatch]],
                      specs: Sequence[SortSpec],
                      emit_bytes: int) -> Iterator[HostBatch]:
    """Merge k sorted runs of host frames into sorted HostBatches.

    Pool-and-sort rounds, all numpy (ref loser_tree.rs role): each round
    loads the next frame of every run whose loaded rows were consumed,
    sorts the pool (memcmp row keys, one argsort), and emits every row
    <= the smallest loaded-frontier among active runs — correctness:
    no unread row can sort below an active run's frontier. Emissions are
    ~(k x frame) rows per round, so the merge runs at numpy argsort
    speed; a head-vs-head scheme (tried first, like the round-4 device
    merge) degrades to ~1-row emissions on interleaved runs. Working
    set stays O(k x frame) rows (the spill writer sizes frames against
    the memory budget)."""
    k = len(frame_iters)
    iters = [iter(it) for it in frame_iters]
    need_load = [True] * k
    exhausted = [False] * k
    frontier: List[Optional[bytes]] = [None] * k
    carry_hb: Optional[HostBatch] = None
    carry_keys: Optional[np.ndarray] = None

    while True:
        pieces: List[HostBatch] = []
        piece_keys: List[np.ndarray] = []
        for r in range(k):
            if exhausted[r] or not need_load[r]:
                continue
            # pull until a NON-empty frame (or exhaustion): an empty
            # frame must not clear this run's frontier for the round —
            # the bound would stop protecting its unread keys and rows
            # could emit out of order
            while True:
                hb = next(iters[r], None)
                if hb is None:
                    exhausted[r] = True
                    frontier[r] = None
                    break
                if hb.num_rows:
                    keys = encode_keys(hb, specs)
                    pieces.append(hb)
                    piece_keys.append(keys)
                    frontier[r] = keys[-1]
                    need_load[r] = False
                    break
        hbs = ([carry_hb] if carry_hb is not None else []) + pieces
        if not hbs:
            if all(exhausted):
                return
            continue  # some runs yielded empty frames; keep pulling
        keys = np.concatenate(
            ([carry_keys] if carry_keys is not None else []) + piece_keys)
        pooled = host_concat(hbs) if len(hbs) > 1 else hbs[0]
        order = np.argsort(keys, kind="stable")
        keys_sorted = keys[order]
        active = [f for r, f in enumerate(frontier) if not exhausted[r]
                  and f is not None]
        if active:
            bound = min(active)
            cut = int(np.searchsorted(keys_sorted, bound, side="right"))
        else:
            cut = len(keys_sorted)
        if cut:
            # sub-chunk very large rounds so downstream uploads stay in
            # the byte class the caller asked for (typical rounds fit in
            # one chunk and take exactly one copy)
            row_b = max(host_nbytes(pooled) // max(pooled.num_rows, 1), 1)
            step = max(int(emit_bytes // row_b), 1)
            for lo in range(0, cut, step):
                yield host_take(pooled, order[lo:min(lo + step, cut)])
        if cut < len(keys_sorted):
            carry_hb = host_take(pooled, order[cut:])
            carry_keys = keys_sorted[cut:]
        else:
            carry_hb, carry_keys = None, None
        for r in range(k):
            if exhausted[r] or frontier[r] is None:
                continue
            if not active or frontier[r] <= bound:
                need_load[r] = True  # loaded rows fully emitted


def host_to_pylike(hb: HostBatch):
    """ColumnBatch.to_numpy()-shaped dict from a host batch (numerics as
    arrays / object-with-None, strings as bytes-or-None lists, wide
    decimals as python ints) — the ordered-collect path hands this to the
    driver without a second device pull."""
    out = {}
    for f, c in zip(hb.schema.fields, hb.cols):
        n = hb.num_rows
        valid = c.validity if c.validity is not None else np.ones((n,), bool)
        if f.dtype.wide_decimal:
            from blaze_tpu.columnar import int128 as i128

            hi = c.children[0].data.astype(np.int64)
            lo = c.children[1].data.astype(np.int64)
            ints = i128.ints_from_np(hi, lo)
            out[f.name] = [ints[i] if valid[i] else None for i in range(n)]
            continue
        if c.kind == "struct":
            subs = [host_to_pylike(HostBatch(
                Schema([sf]), [ch], n))[sf.name]
                for sf, ch in zip(f.dtype.fields, c.children)]
            out[f.name] = [tuple(s[i] for s in subs) if valid[i] else None
                           for i in range(n)]
            continue
        if c.kind == "dict":
            b, l, cd = c.data, c.lengths, c.codes
            out[f.name] = [bytes(b[cd[i], :l[cd[i]]]) if valid[i] else None
                           for i in range(n)]
            continue
        if c.kind == "str":
            b, l = c.data, c.lengths
            out[f.name] = [bytes(b[i, :l[i]]) if valid[i] else None
                           for i in range(n)]
            continue
        if c.kind == "null":
            out[f.name] = np.full((n,), None, object)
            continue
        d = c.data[:n]
        if valid.all():
            out[f.name] = d
        else:
            o = d.astype(object)
            o[~valid] = None
            out[f.name] = o
    return out
