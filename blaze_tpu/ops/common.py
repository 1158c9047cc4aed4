"""Shared operator utilities: batch concatenation / re-chunking.

Ref: concat_batches in datafusion-ext-commons lib.rs:33-61 and the
CoalesceStream wrapper (streams/coalesce_stream.rs) that re-chunks every
operator's output to the configured batch size.
"""

from __future__ import annotations

from typing import List, Optional

import jax.numpy as jnp
import numpy as np

from blaze_tpu.columnar.batch import (
    Column, ColumnBatch, StringData, bucket_capacity, pull_rows,
)
from blaze_tpu.columnar.types import Schema, TypeKind
from blaze_tpu.exprs import strings as S


def schema_row_bytes(schema: Schema) -> int:
    """Rough per-row device bytes (validity + typical string width)."""
    total = 0
    for f in schema.fields:
        total += _field_row_bytes(f.dtype) + 1
    return max(total, 1)


def _field_row_bytes(dtype) -> int:
    k = dtype.kind
    if k in (TypeKind.STRING, TypeKind.BINARY):
        return 36  # 32-byte width bucket guess + lengths
    if k in (TypeKind.LIST, TypeKind.MAP):
        return 64
    if dtype.wide_decimal:
        return 16  # two int64 limb planes
    if k == TypeKind.STRUCT:
        return sum(_field_row_bytes(f.dtype) + 1 for f in dtype.fields)
    try:
        import numpy as np

        return np.dtype(dtype.np_dtype()).itemsize
    except Exception:  # noqa: BLE001
        return 8


def adaptive_target_bytes(manager=None) -> int:
    """Macro-batch byte target: conf.target_batch_bytes clamped so one
    batch stays well inside the (HBM-modeling) memory budget — a forced
    small budget (spill tests) gets small bounded batches back. A query
    session degraded by the resilience ladder (rung 1 halves the target)
    clamps further via its own override, so one query's degradation
    never shrinks another's batches."""
    from blaze_tpu.config import conf
    from blaze_tpu.runtime import memory as M
    from blaze_tpu.runtime import supervisor as sup_mod

    mgr = manager or M.get_manager()
    target = conf.target_batch_bytes
    sess = sup_mod.current_session()
    if sess is not None and sess.batch_target:
        target = min(target, sess.batch_target)
    return max(min(target, mgr.total // 8), 1 << 18)


def adaptive_batch_rows(schema: Schema, manager=None) -> int:
    """Source batch row target for macro-batching (power of two so jit
    shape buckets stay few)."""
    from blaze_tpu.config import conf

    rows = adaptive_target_bytes(manager) // schema_row_bytes(schema)
    rows = max(conf.batch_size, min(int(rows), conf.max_batch_rows))
    return 1 << (max(int(rows), 1).bit_length() - 1)


def concat_batches(batches: List[ColumnBatch], schema: Optional[Schema] = None,
                   capacity: Optional[int] = None) -> ColumnBatch:
    """Concatenate live rows of several batches into one.

    Materialization point: reads num_rows to host (this only happens at
    pipeline breakers — sort/agg/join build — mirroring where the reference
    materializes memory tables)."""
    assert batches, "concat_batches needs at least one batch"
    schema = schema or batches[0].schema
    counts = [pull_rows(b, "concat.rows") for b in batches]
    total = sum(counts)
    cap = capacity or bucket_capacity(total)

    # gather indices: position in the virtual concatenation of capacities
    idx_np = np.zeros((cap,), np.int64)
    pos = 0
    offset = 0
    for b, n in zip(batches, counts):
        idx_np[pos : pos + n] = np.arange(n) + offset
        pos += n
        offset += b.capacity

    idx = jnp.asarray(idx_np)
    # one jitted program per (schema, input shapes, cap): the eager
    # formulation paid one gather dispatch per column per call. List
    # storage concatenates eagerly — its element recursion reads child
    # counts, which have no host value inside a trace.
    if any(_has_list(f.dtype) for f in schema.fields):
        out_cols = []
        for ci, field in enumerate(schema):
            parts = [b.columns[ci] for b in batches]
            out_cols.append(_concat_one(parts, idx, field, cap))
        return ColumnBatch(schema, out_cols, jnp.asarray(total, jnp.int32),
                           cap)

    from blaze_tpu.runtime import jit_cache

    key = ("concat", cap, tuple(schema.fields),
           tuple(b.shape_key() for b in batches))

    def make():
        def run(idx, total, *bs):
            out_cols = []
            for ci, field in enumerate(schema):
                parts = [b.columns[ci] for b in bs]
                out_cols.append(_concat_one(parts, idx, field, cap))
            return ColumnBatch(schema, out_cols, total.astype(jnp.int32),
                               cap)

        return run

    fn = jit_cache.get_or_compile(key, make)
    return fn(idx, jnp.asarray(total, jnp.int64), *batches)


def _has_list(dtype) -> bool:
    if dtype.kind in (TypeKind.LIST, TypeKind.MAP):
        return True
    if dtype.kind == TypeKind.STRUCT and not dtype.wide_decimal:
        return any(_has_list(f.dtype) for f in dtype.fields)
    return False


def _concat_validity(parts, idx):
    vs = [p.valid_mask() if p.validity is not None else None for p in parts]
    if not any(v is not None for v in vs):
        return None
    big_v = jnp.concatenate(
        [v if v is not None else jnp.ones((p.capacity,), jnp.bool_)
         for v, p in zip(vs, parts)], axis=0)
    return big_v[idx]


def _concat_one(parts, idx, field, cap):
    """Concatenate one column across batches: every storage kind gathers
    live rows through the SAME parent `idx` (positions in the virtual
    concatenation of part capacities), so children stay row-aligned."""
    if parts[0].is_list:
        return _concat_list_columns(parts, idx, field, cap)
    if parts[0].is_struct:
        from blaze_tpu.columnar.batch import StructData
        from blaze_tpu.columnar.types import Field, wide_decimal_storage

        fields = (wide_decimal_storage(field.dtype).fields
                  if field.dtype.wide_decimal else field.dtype.fields)
        children = [
            _concat_one([p.data.children[fi] for p in parts], idx,
                        Field(f.name, f.dtype), cap)
            for fi, f in enumerate(fields)]
        return Column(field.dtype, StructData(children),
                      _concat_validity(parts, idx))
    if parts[0].is_string:
        w = max(p.data.width for p in parts)
        datas = [S.ensure_width(p.data, w) for p in parts]
        big_bytes = jnp.concatenate([d.bytes for d in datas], axis=0)
        big_lens = jnp.concatenate([d.lengths for d in datas], axis=0)
        data = StringData(big_bytes[idx], big_lens[idx])
    else:
        big = jnp.concatenate([p.data for p in parts], axis=0)
        data = big[idx]
    return Column(field.dtype, data, _concat_validity(parts, idx))


def _concat_list_columns(parts, idx, field, cap):
    """Concatenate list columns: element storages concatenate with bases,
    then rows gather through a _list_take-style compaction."""
    from blaze_tpu.columnar.batch import ListData, _list_take
    from blaze_tpu.columnar.types import Field, Schema

    bases = []
    total_elems = 0
    elem_parts = []
    for p in parts:
        bases.append(total_elems)
        total_elems += p.data.elements.capacity
        elem_parts.append(p.data.elements)
    from blaze_tpu.columnar.types import storage_element

    elem_schema = Schema([Field("e", storage_element(field.dtype))])
    elem_batches = [
        ColumnBatch(elem_schema, [e],
                    jnp.asarray(e.capacity, jnp.int32), e.capacity)
        for e in elem_parts]
    big_elems = concat_batches(elem_batches, elem_schema,
                               capacity=total_elems).columns[0]

    starts = jnp.concatenate([p.data.offsets[:-1] + b
                              for p, b in zip(parts, bases)])
    lens = jnp.concatenate([p.data.lengths() for p in parts])
    vs = [p.valid_mask() if p.validity is not None else None for p in parts]
    validity = None
    if any(v is not None for v in vs):
        validity = jnp.concatenate(
            [v if v is not None else jnp.ones((p.capacity,), jnp.bool_)
             for v, p in zip(vs, parts)])[idx]
    # gather rows: emulate _list_take over the concatenated layout
    from blaze_tpu.ops.segment import element_rows

    glens = lens[idx]
    new_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(glens, dtype=jnp.int32)])
    # starts are not contiguous in the concatenated storage, so gather via
    # the shared slot->row mapping then offset by each row's start
    ecap = big_elems.capacity
    out_rows = idx.shape[0]
    _, row, within, live = element_rows(new_off, out_rows, ecap)
    src = starts[idx[row]] + within
    elems = big_elems.take(jnp.where(live, src, 0))
    from blaze_tpu.columnar.batch import Column

    return Column(field.dtype, ListData(new_off, elems), validity)


def slice_batch(batch: ColumnBatch, start: int, count: int,
                cap: Optional[int] = None) -> ColumnBatch:
    """Slice of live rows [start, start+count) into a fresh batch of
    capacity `cap` (the rows' own bucket unless given).

    Jitted per (schema, input shape, output bucket) with start/count
    traced — per-partition slicing in the exchange paths calls this with
    many different offsets and must not compile (or eagerly dispatch) per
    column per call.

    The rows lie next to each other, so every plane that holds row r at
    index r is cut out by a contiguous copy (`ColumnBatch.slice_rows`); a
    batch with a list column, whose elements lie elsewhere, is gathered
    by `take`. The batch's own columns decide, nothing else."""
    cap = bucket_capacity(count) if cap is None else cap
    from blaze_tpu.runtime import compile_service, jit_cache

    copies = batch.row_aligned
    compile_service.note_slice(copies)
    key = ("slice", cap, tuple(batch.schema.fields), batch.shape_key())

    def make():
        def run(b, start, count):
            rows = jnp.minimum(jnp.maximum(b.num_rows - start, 0), count)
            if copies:
                return b.slice_rows(start, cap, rows)
            # `take` clips the indices that pass the capacity
            return b.take(jnp.arange(cap, dtype=jnp.int32) + start, rows)

        return run

    return jit_cache.get_or_compile(key, make)(
        batch, jnp.asarray(start, jnp.int32), jnp.asarray(count, jnp.int32))
