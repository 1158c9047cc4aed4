"""ctypes loader for the C++ native layer (native/libblaze_tpu_native.so).

Ref role: the boundary the reference crosses with JNI (blaze-jni-bridge).
Exposes the C ABI of native/include/blaze_native.h; `available()` gates
callers so the pure-Python paths keep working without the build.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import List, Optional

import numpy as np

_LIB_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native",
    "libblaze_tpu_native.so")

_lib: Optional[ctypes.CDLL] = None


class _BnCol(ctypes.Structure):
    _fields_ = [
        ("kind", ctypes.c_uint8),
        ("item_size", ctypes.c_uint8),
        ("data", ctypes.c_void_p),
        ("width", ctypes.c_int32),
        ("lengths", ctypes.c_void_p),
        ("validity", ctypes.c_void_p),
    ]


def _load() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is not None:
        return _lib
    if not os.path.exists(_LIB_PATH):
        return None
    lib = ctypes.CDLL(_LIB_PATH)
    lib.bn_serialize_bound.restype = ctypes.c_int64
    lib.bn_serialize_bound.argtypes = [ctypes.POINTER(_BnCol),
                                       ctypes.c_int32, ctypes.c_int64,
                                       ctypes.c_int64]
    lib.bn_serialize.restype = ctypes.c_int64
    lib.bn_serialize.argtypes = [ctypes.POINTER(_BnCol), ctypes.c_int32,
                                 ctypes.c_int64, ctypes.c_int64,
                                 ctypes.c_int32,
                                 ctypes.c_char_p, ctypes.c_int64]
    lib.bn_shuffle_new.restype = ctypes.c_void_p
    lib.bn_shuffle_new.argtypes = [ctypes.c_int32, ctypes.c_char_p,
                                   ctypes.c_int64]
    lib.bn_shuffle_push.restype = ctypes.c_int
    lib.bn_shuffle_push.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                    ctypes.c_char_p, ctypes.c_int64]
    lib.bn_shuffle_commit.restype = ctypes.c_int
    lib.bn_shuffle_commit.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_char_p,
                                      ctypes.POINTER(ctypes.c_int64)]
    lib.bn_shuffle_free.argtypes = [ctypes.c_void_p]
    lib.bn_shuffle_mem_used.restype = ctypes.c_int64
    lib.bn_shuffle_mem_used.argtypes = [ctypes.c_void_p]
    lib.bn_shuffle_spill.restype = ctypes.c_int
    lib.bn_shuffle_spill.argtypes = [ctypes.c_void_p]
    lib.bn_call.restype = ctypes.c_int
    lib.bn_call.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                            ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
                            ctypes.POINTER(ctypes.c_int64)]
    lib.bn_call_arrow.restype = ctypes.c_int
    lib.bn_call_arrow.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                  ctypes.c_void_p]
    lib.bn_arrow_stream_from_payload.restype = ctypes.c_int
    lib.bn_arrow_stream_from_payload.argtypes = [ctypes.c_char_p,
                                                 ctypes.c_int64,
                                                 ctypes.c_void_p]
    lib.bn_init.restype = ctypes.c_int
    lib.bn_init.argtypes = [ctypes.c_int64]
    lib.bn_last_error.restype = ctypes.c_char_p
    for kname in ("bn_last_error_category", "bn_request_kill",
                  "bn_clear_kill", "bn_kill_requested"):
        kfn = getattr(lib, kname)
        kfn.restype = ctypes.c_int
        kfn.argtypes = []
    lib.bn_free_buffer.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
    for name, argtypes in [
        ("bn_hash_i32", [ctypes.c_void_p] * 2 + [ctypes.c_int64,
                                                 ctypes.c_void_p]),
        ("bn_hash_i64", [ctypes.c_void_p] * 2 + [ctypes.c_int64,
                                                 ctypes.c_void_p]),
        ("bn_hash_bytes", [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
                           ctypes.c_int32, ctypes.c_void_p,
                           ctypes.c_void_p]),
        ("bn_pmod", [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
                     ctypes.c_void_p]),
    ]:
        fn = getattr(lib, name)
        fn.restype = None
        fn.argtypes = argtypes
    _lib = lib
    return lib


def available() -> bool:
    return _load() is not None


def last_error_category() -> int:
    """bn_last_error_category wire code for this thread's last native
    failure."""
    return int(_load().bn_last_error_category())


def request_kill() -> None:
    """bn_request_kill: cooperatively cancel running native tasks (the
    C-ABI mirror of the supervisor's per-attempt kill flag)."""
    _load().bn_request_kill()


def clear_kill() -> None:
    """bn_clear_kill: re-arm after a kill so the next task may run."""
    _load().bn_clear_kill()


def kill_requested() -> bool:
    """bn_kill_requested: whether the native kill flag is set."""
    return int(_load().bn_kill_requested()) > 0


def _native_error(what: str, rc: int) -> Exception:
    """Map the C ABI error category onto the faults taxonomy so the
    executor's resilience ladder treats native failures (retry, degrade,
    abort) exactly like Python-side ones."""
    from blaze_tpu.runtime import faults

    lib = _load()
    msg = f"{what} failed ({rc}): {lib.bn_last_error().decode()}"
    cat = faults.NATIVE_CODE_CATEGORIES.get(last_error_category())
    if cat == "killed":
        from blaze_tpu.ops.base import TaskKilledError

        return TaskKilledError(msg)
    cls = faults.CATEGORY_CLASSES.get(cat)
    return cls(msg) if cls is not None else RuntimeError(msg)


def _ptr(a: Optional[np.ndarray]):
    if a is None:
        return None
    return a.ctypes.data_as(ctypes.c_void_p)


def hash_columns(cols, seed: int = 42) -> np.ndarray:
    """Spark murmur3 over host column dicts, mirroring exprs/hash.py.

    `cols`: list of dicts {kind: 'i32'|'i64'|'bytes', data, lengths?,
    width?, validity?} with numpy arrays.
    """
    lib = _load()
    n = len(cols[0]["data"])
    h = np.full(n, np.uint32(seed), np.uint32)
    for c in cols:
        v = c.get("validity")
        v8 = None if v is None else np.ascontiguousarray(v, np.uint8)
        if c["kind"] == "i32":
            lib.bn_hash_i32(_ptr(np.ascontiguousarray(c["data"], np.int32)),
                            _ptr(v8), n, _ptr(h))
        elif c["kind"] == "i64":
            lib.bn_hash_i64(_ptr(np.ascontiguousarray(c["data"], np.int64)),
                            _ptr(v8), n, _ptr(h))
        elif c["kind"] == "bytes":
            mat = np.ascontiguousarray(c["data"], np.uint8)
            lens = np.ascontiguousarray(c["lengths"], np.int32)
            lib.bn_hash_bytes(_ptr(mat), _ptr(lens), n, mat.shape[1],
                              _ptr(v8), _ptr(h))
        else:
            raise ValueError(c["kind"])
    return h.view(np.int32)


def pmod(h: np.ndarray, num_partitions: int) -> np.ndarray:
    lib = _load()
    out = np.zeros(len(h), np.int32)
    lib.bn_pmod(_ptr(h.view(np.uint32)), len(h), num_partitions, _ptr(out))
    return out


def serialize_host_batch(host_batch, lo: int, hi: int,
                         level: int = 1) -> bytes:
    """C++ encoder for a serde.HostBatch slice (byte-compatible with
    HostBatch.serialize). Columns with kinds the C ABI doesn't cover
    (lists) raise — callers fall back to the Python encoder."""
    lib = _load()
    cols = host_batch.cols
    carr = (_BnCol * len(cols))()
    keep = []  # keep contiguous arrays alive
    for i, c in enumerate(cols):
        if c.kind == "num":
            d = np.ascontiguousarray(c.data)
            keep.append(d)
            carr[i].kind = 0
            carr[i].item_size = d.dtype.itemsize
            carr[i].data = d.ctypes.data
            carr[i].width = 0
            carr[i].lengths = None
        elif c.kind == "str":
            d = np.ascontiguousarray(c.data, np.uint8)
            lens = np.ascontiguousarray(c.lengths, np.int32)
            keep += [d, lens]
            carr[i].kind = 1
            carr[i].item_size = 1
            carr[i].data = d.ctypes.data
            carr[i].width = d.shape[1]
            carr[i].lengths = lens.ctypes.data
        elif c.kind == "null":
            carr[i].kind = 2
            carr[i].item_size = 0
            carr[i].data = None
            carr[i].width = 0
            carr[i].lengths = None
        else:
            raise NotImplementedError(f"native serde: {c.kind} column")
        if c.validity is not None:
            v = np.ascontiguousarray(c.validity, np.uint8)
            keep.append(v)
            carr[i].validity = v.ctypes.data
        else:
            carr[i].validity = None
    bound = lib.bn_serialize_bound(carr, len(cols), lo, hi)
    out = ctypes.create_string_buffer(bound)
    n = lib.bn_serialize(carr, len(cols), lo, hi, level, out, bound)
    if n < 0:
        raise RuntimeError(f"bn_serialize failed: {n}")
    return out.raw[:n]


class _ArrowArrayStream(ctypes.Structure):
    """Arrow C stream interface struct (stable ABI): 4 fn pointers +
    private_data."""
    _fields_ = [("get_schema", ctypes.c_void_p),
                ("get_next", ctypes.c_void_p),
                ("get_last_error", ctypes.c_void_p),
                ("release", ctypes.c_void_p),
                ("private_data", ctypes.c_void_p)]


def call_arrow(task_def: bytes):
    """bn_call_arrow: run a TaskDefinition, import the result as a
    pyarrow.RecordBatchReader through the standard Arrow C stream —
    proving the boundary any Arrow host (JVM arrow-c-data, arrow-rs)
    consumes (ref blaze/src/rt.rs:76-80)."""
    import pyarrow as pa

    lib = _load()
    stream = _ArrowArrayStream()
    rc = lib.bn_call_arrow(task_def, len(task_def), ctypes.byref(stream))
    if rc != 0:
        raise _native_error("bn_call_arrow", rc)
    return pa.RecordBatchReader._import_from_c(ctypes.addressof(stream))


def arrow_stream_from_payload(payload: bytes):
    """Import a BTAS payload (schema header + BTB1 frames) as a pyarrow
    RecordBatchReader via bn_arrow_stream_from_payload."""
    import pyarrow as pa

    lib = _load()
    stream = _ArrowArrayStream()
    rc = lib.bn_arrow_stream_from_payload(payload, len(payload),
                                          ctypes.byref(stream))
    if rc != 0:
        raise RuntimeError("bn_arrow_stream_from_payload failed")
    return pa.RecordBatchReader._import_from_c(ctypes.addressof(stream))


def call_native(task_def: bytes) -> bytes:
    """The callNative entry: serialized TaskDefinition -> result frames."""
    lib = _load()
    out = ctypes.POINTER(ctypes.c_uint8)()
    out_len = ctypes.c_int64()
    rc = lib.bn_call(task_def, len(task_def), ctypes.byref(out),
                     ctypes.byref(out_len))
    if rc != 0:
        raise _native_error("bn_call", rc)
    try:
        return ctypes.string_at(out, out_len.value)
    finally:
        lib.bn_free_buffer(out)


class NativeShuffleWriter:
    """ctypes wrapper over bn_shuffle_* (the C++ map-output writer)."""

    def __init__(self, num_partitions: int, spill_dir: str = "/tmp",
                 mem_budget: int = 1 << 30) -> None:
        self._lib = _load()
        self.P = num_partitions
        self._w = self._lib.bn_shuffle_new(num_partitions,
                                           spill_dir.encode(), mem_budget)
        # MemManager.mem_used() walks a SNAPSHOT of every task's consumers
        # from whichever thread is growing: it can reach this writer while
        # its owner closes it — C must never see the freed/NULL handle
        self._handle_lock = threading.Lock()

    def push(self, partition: int, frame: bytes) -> None:
        with self._handle_lock:
            rc = self._lib.bn_shuffle_push(self._w, partition, frame,
                                           len(frame))
        if rc != 0:
            raise _native_error("bn_shuffle_push", rc)

    def mem_used(self) -> int:
        with self._handle_lock:
            return self._lib.bn_shuffle_mem_used(self._w) if self._w else 0

    def spill(self) -> None:
        # host-driven release() may call this from another task's thread
        with self._handle_lock:
            rc = self._lib.bn_shuffle_spill(self._w) if self._w else 0
        if rc != 0:
            raise _native_error("bn_shuffle_spill", rc)

    def commit(self, data_path: str, index_path: str) -> List[int]:
        lengths = (ctypes.c_int64 * self.P)()
        with self._handle_lock:
            w = self._w
        # owner-thread only, and long: not held across the file writes
        rc = self._lib.bn_shuffle_commit(w, data_path.encode(),
                                         index_path.encode(), lengths)
        if rc != 0:
            raise _native_error("bn_shuffle_commit", rc)
        return list(lengths)

    def close(self) -> None:
        with self._handle_lock:
            if self._w:
                self._lib.bn_shuffle_free(self._w)
                self._w = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
