"""Host-evaluated scalar kernels: crypto digests, CRC32, JSON path.

Ref: datafusion-ext-functions lib.rs:28-53 registers Md5/Sha*/Crc32 digests
and spark_get_json_object.rs (577 LoC) implements the Spark JSON path
evaluator with a parsed-JSON cache. These are bytewise-serial algorithms
with no vector/MXU formulation worth building — the TPU-native translation
is a `jax.pure_callback` host kernel inside the jit program, the same
boundary the engine already uses for Spark UDFs (exprs/compiler.py
_compile_udf_wrapper). Data crosses as the fixed-width byte matrices the
string columns already are, so there is no serialization step.

The JSON path evaluator supports the Spark/Hive subset: `$`, `.field`,
`['field']`, `[n]`, `[*]`. A small parsed-JSON LRU mirrors the reference's
GetParsedJsonObject/ParseJson caching pair (UserDefinedArray) without the
opaque-array machinery: parse results are memoized by content so a
projection evaluating several paths over one column parses each value once.
"""

from __future__ import annotations

import hashlib
import json
import zlib
from collections import OrderedDict
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from blaze_tpu.columnar.batch import Column, ColumnBatch, StringData
from blaze_tpu.columnar.types import INT64, STRING

# ---------------------------------------------------------------------------
# host crossing
# ---------------------------------------------------------------------------


def host_apply(callback: Callable, shapes, *args):
    """Run a host computation over device arrays.

    On concrete (non-traced) inputs — the normal path, because operators
    containing host expressions are executed UNJITTED (executor checks
    Operator.jit_safe) — this pulls to numpy, runs the callback, and pushes
    the results back: no jax callback machinery. Under a tracer (tests jit
    whole pipelines) it uses jax.pure_callback, which needs a CPU device
    to land on: "cpu" must be listed in jax_platforms beside the
    accelerator (chip_smoke.py's premises probe reads whether it runs)."""
    import jax.core as jcore

    if any(isinstance(a, jcore.Tracer) for a in args):
        return jax.pure_callback(callback, shapes, *args,
                                 vmap_method="sequential")
    outs = callback(*[np.asarray(a) for a in args])
    if isinstance(outs, tuple):
        return tuple(jnp.asarray(o) for o in outs)
    return jnp.asarray(outs)


def host_bytes_to_string(col: Column, batch: ColumnBatch, out_width: int,
                         row_fn: Callable[[bytes], Optional[bytes]]) -> Column:
    """Apply `row_fn` to each live, valid row's bytes on the host.

    row_fn returning None marks the row null; results longer than
    `out_width` are nulled too (never silently truncated)."""
    sd = col.data
    nrows = batch.num_rows
    valid = col.valid_mask() & batch.row_mask()

    def callback(b, lens, ok, n):
        b, lens, ok = np.asarray(b), np.asarray(lens), np.asarray(ok)
        n = int(n)
        cap = b.shape[0]
        out_b = np.zeros((cap, out_width), np.uint8)
        out_l = np.zeros((cap,), np.int32)
        out_ok = np.zeros((cap,), bool)
        for i in range(n):
            if not ok[i]:
                continue
            r = row_fn(b[i, :lens[i]].tobytes())
            if r is None or len(r) > out_width:
                continue
            out_b[i, :len(r)] = np.frombuffer(r, np.uint8)
            out_l[i] = len(r)
            out_ok[i] = True
        return out_b, out_l, out_ok

    cap = batch.capacity
    shapes = (jax.ShapeDtypeStruct((cap, out_width), np.uint8),
              jax.ShapeDtypeStruct((cap,), np.int32),
              jax.ShapeDtypeStruct((cap,), np.bool_))
    ob, ol, ook = host_apply(callback, shapes, sd.bytes, sd.lengths,
                             valid, nrows)
    return Column(STRING, StringData(ob, ol), ook)


def host_bytes_to_int64(col: Column, batch: ColumnBatch,
                        row_fn: Callable[[bytes], int]) -> Column:
    sd = col.data
    valid = col.valid_mask() & batch.row_mask()

    def callback(b, lens, ok, n):
        b, lens, ok = np.asarray(b), np.asarray(lens), np.asarray(ok)
        cap = b.shape[0]
        out = np.zeros((cap,), np.int64)
        for i in range(int(n)):
            if ok[i]:
                out[i] = row_fn(b[i, :lens[i]].tobytes())
        return out

    cap = batch.capacity
    out = host_apply(
        callback, jax.ShapeDtypeStruct((cap,), np.int64),
        sd.bytes, sd.lengths, valid, batch.num_rows)
    return Column(INT64, out, col.validity)


# ---------------------------------------------------------------------------
# digests (ref lib.rs digest registrations)
# ---------------------------------------------------------------------------

DIGESTS = {
    "md5": (32, lambda b: hashlib.md5(b).hexdigest().encode()),
    "sha224": (56, lambda b: hashlib.sha224(b).hexdigest().encode()),
    "sha256": (64, lambda b: hashlib.sha256(b).hexdigest().encode()),
    "sha384": (96, lambda b: hashlib.sha384(b).hexdigest().encode()),
    "sha512": (128, lambda b: hashlib.sha512(b).hexdigest().encode()),
}


def crc32_value(b: bytes) -> int:
    return zlib.crc32(b) & 0xFFFFFFFF


# ---------------------------------------------------------------------------
# JSON path (ref spark_get_json_object.rs)
# ---------------------------------------------------------------------------


def parse_json_path(path: str) -> Optional[List]:
    """'$.a.b[0][*]' -> [('key','a'), ('key','b'), ('idx',0), ('star',)].
    Returns None for malformed paths (spark: result is NULL)."""
    if not path.startswith("$"):
        return None
    steps: List[Tuple] = []
    i = 1
    n = len(path)
    while i < n:
        c = path[i]
        if c == ".":
            j = i + 1
            while j < n and path[j] not in ".[":
                j += 1
            name = path[i + 1:j]
            if not name:
                return None
            steps.append(("key", name))
            i = j
        elif c == "[":
            j = path.find("]", i)
            if j < 0:
                return None
            inner = path[i + 1:j].strip()
            if inner == "*":
                steps.append(("star",))
            elif (len(inner) >= 2 and inner[0] in "'\""
                  and inner[-1] == inner[0]):
                steps.append(("key", inner[1:-1]))
            else:
                try:
                    steps.append(("idx", int(inner)))
                except ValueError:
                    return None
            i = j + 1
        else:
            return None
    return steps


_PARSE_CACHE: "OrderedDict[bytes, object]" = OrderedDict()
_PARSE_CACHE_MAX = 4096
_INVALID = object()


def cached_parse(raw: bytes):
    """Parsed-JSON memo (ref: ParseJson + UserDefinedArray caching)."""
    hit = _PARSE_CACHE.get(raw)
    if hit is not None:
        _PARSE_CACHE.move_to_end(raw)
        return hit
    try:
        v = json.loads(raw)
        if v is None:
            v = _INVALID
    except Exception:
        v = _INVALID
    _PARSE_CACHE[raw] = v
    if len(_PARSE_CACHE) > _PARSE_CACHE_MAX:
        _PARSE_CACHE.popitem(last=False)
    return v


def eval_json_path(value, steps: List[Tuple]):
    """Returns (found, value). [*] fans out and collects matches."""
    cur = [value]
    for st in steps:
        nxt = []
        if st[0] == "key":
            for v in cur:
                if isinstance(v, dict) and st[1] in v:
                    nxt.append(v[st[1]])
        elif st[0] == "idx":
            for v in cur:
                if isinstance(v, list) and -len(v) <= st[1] < len(v):
                    nxt.append(v[st[1]])
        else:  # star
            for v in cur:
                if isinstance(v, list):
                    nxt.extend(v)
        cur = nxt
        if not cur:
            return False, None
    if len(cur) == 1:
        return True, cur[0]
    return True, cur


def render_json_value(v) -> Optional[bytes]:
    """Spark rendering: strings raw (unquoted), null -> NULL, containers as
    compact JSON."""
    if v is None:
        return None
    if isinstance(v, str):
        return v.encode()
    if isinstance(v, bool):
        return b"true" if v else b"false"
    if isinstance(v, (int, float)):
        return json.dumps(v).encode()
    return json.dumps(v, separators=(",", ":")).encode()


def get_json_object_row(raw: bytes, steps: List[Tuple]) -> Optional[bytes]:
    v = cached_parse(raw)
    if v is _INVALID:
        return None
    found, out = eval_json_path(v, steps)
    if not found:
        return None
    return render_json_value(out)


def validate_json_row(raw: bytes) -> Optional[bytes]:
    """parse_json: NULL for invalid documents, input text otherwise."""
    return raw if cached_parse(raw) is not _INVALID else None
