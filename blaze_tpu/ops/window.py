"""WindowExec — ranking + aggregate window functions over sorted partitions.

Ref: datafusion-ext-plans window_exec.rs + window/ (processors RowNumber/
Rank/DenseRank + agg-over-window, window/mod.rs:43-51; partition boundary
detection over sorted input, window_context.rs:24). TPU-first redesign: the
batch is sorted by (partition_by, order_by) in one variadic sort, partition
and peer-group boundaries are neighbor-equality flags, and every window
value is a segmented scan:

  row_number : position within partition run
  rank       : position of the peer group's first row (+1)
  dense_rank : running count of peer-group starts within the partition
  agg funcs  : running aggregate leveled to the peer group's last row
               (Spark's default RANGE UNBOUNDED PRECEDING..CURRENT ROW);
               without ORDER BY the whole partition shares one value
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import (
    Column, ColumnBatch, pull_array, pull_rows,
)
from blaze_tpu.columnar.types import DataType, Field, Schema
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.compiler import compile_expr
from blaze_tpu.ops import segment as seg
from blaze_tpu.ops.agg import _sum_state_dtype
from blaze_tpu.ops.base import BatchStream, ExecContext, Operator, count_stream
from blaze_tpu.ops.common import concat_batches
from blaze_tpu.ops.sort_keys import SortSpec
from blaze_tpu.runtime import jit_cache

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class WindowCall:
    """One window expression (ref pb.WindowExprNode)."""
    fn: str                 # row_number | rank | dense_rank | <agg fn>
    inputs: Tuple[ir.Expr, ...]   # agg window funcs only
    dtype: DataType
    name: str

    def key(self) -> tuple:
        return (self.fn, tuple(e.key() for e in self.inputs),
                repr(self.dtype), self.name)

    @property
    def is_rank_like(self) -> bool:
        return self.fn in ("row_number", "rank", "dense_rank")


class WindowExec(Operator):
    def __init__(self, child: Operator, calls: Sequence[WindowCall],
                 partition_exprs: Sequence[ir.Expr],
                 order_specs: Sequence[SortSpec]) -> None:
        super().__init__([child])
        self.calls = list(calls)
        self.partition_exprs = list(partition_exprs)
        self.order_specs = list(order_specs)
        child_schema = child.schema
        self._part_fns = [compile_expr(e, child_schema)
                          for e in self.partition_exprs]
        self._input_fns = [[compile_expr(e, child_schema)
                            for e in c.inputs] for c in self.calls]
        out = list(child_schema.fields)
        for c in self.calls:
            if c.is_rank_like:
                out.append(Field(c.name, T.INT32, nullable=False))
            elif c.fn == "count":
                out.append(Field(c.name, T.INT64, nullable=False))
            elif c.fn == "sum":
                out.append(Field(c.name, _sum_state_dtype(c.dtype)))
            elif c.fn == "avg":
                out.append(Field(c.name, T.FLOAT64))
            else:
                out.append(Field(c.name, c.dtype))
        self._schema = Schema(out)

    @property
    def schema(self) -> Schema:
        return self._schema

    def plan_key(self) -> tuple:
        return ("window", tuple(c.key() for c in self.calls),
                tuple(e.key() for e in self.partition_exprs),
                tuple(s.key() for s in self.order_specs),
                self.children[0].plan_key())

    def _work_layout(self):
        """(work schema, part col indices, per-call input col indices)."""
        child_schema = self.children[0].schema
        nin = len(child_schema.fields)
        fields = list(child_schema.fields)
        part_idx = []
        probe = ColumnBatch.empty(child_schema)
        for i, fn in enumerate(self._part_fns):
            shp = jax.eval_shape(fn, probe)
            part_idx.append(len(fields))
            fields.append(Field(f"#part{i}", shp.dtype))
        in_idx: List[List[int]] = []
        for ci, fns in zip(self.calls, self._input_fns):
            row = []
            for j, fn in enumerate(fns):
                shp = jax.eval_shape(fn, probe)
                row.append(len(fields))
                fields.append(Field(f"#in{ci.name}{j}", shp.dtype))
            in_idx.append(row)
        return Schema(fields), part_idx, in_idx, nin

    def _make_work(self, b: ColumnBatch, work_schema: Schema) -> ColumnBatch:
        from blaze_tpu.exprs.compiler import cse_scope

        with cse_scope():
            cols = list(b.columns)
            for fn in self._part_fns:
                cols.append(fn(b))
            for fns in self._input_fns:
                for fn in fns:
                    cols.append(fn(b))
        return b.with_columns(work_schema, cols)

    def execute(self, ctx: ExecContext) -> BatchStream:
        """Partition-bounded streaming (ref window_context.rs:24): input is
        externally sorted by (partition, order) — spilling under the
        MemManager budget like any sort — then completed partitions are
        computed and emitted chunk by chunk; only the OPEN partition's rows
        carry between chunks, so peak state is one sort pool + the largest
        single partition."""
        def gen():
            from blaze_tpu.ops.common import slice_batch
            from blaze_tpu.ops.sort import ExternalSorter
            from blaze_tpu.runtime import memory as M

            work_schema, part_idx, in_idx, nin = self._work_layout()
            self._part_idx, self._in_idx, self._nin = part_idx, in_idx, nin
            jit = not any(
                ir.contains_host_fn(e) for e in list(self.partition_exprs) +
                [x for c in self.calls for x in c.inputs])
            specs = [SortSpec(i) for i in part_idx] + [
                SortSpec(s.col, s.asc, s.nulls_first)
                for s in self.order_specs]
            sorter = ExternalSorter(work_schema, specs, M.get_manager(ctx),
                                    name="window")
            try:
                for b in self.children[0].execute(ctx):
                    ctx.check_running()
                    if pull_rows(b, "window.input_rows") == 0:
                        continue
                    wkey = ("window_work", jit, self.plan_key(),
                            b.shape_key())
                    work = jit_cache.get_or_compile(
                        wkey, lambda: (
                            lambda bb: self._make_work(bb, work_schema)),
                        jit=jit)(b)
                    sorter.add(work)

                def compute(chunk: ColumnBatch):
                    key = ("window_kernel", jit, self.plan_key(),
                           chunk.shape_key())
                    with self.metrics.timer():
                        return jit_cache.get_or_compile(
                            key, lambda: self._compute_sorted, jit=jit)(chunk)

                if not part_idx:
                    # global window: one partition spans everything —
                    # collect the sorted chunks ONCE (re-concatenating a
                    # growing carry per chunk would be O(n^2) in copies)
                    chunks = [sb for sb in sorter.finish()
                              if pull_rows(sb, "window.chunk_rows") > 0]
                    if chunks:
                        yield compute(
                            chunks[0] if len(chunks) == 1
                            else concat_batches(chunks, work_schema))
                    self.metrics.add("spill_count", sorter.spill_count)
                    return
                carry: Optional[ColumnBatch] = None
                for sb in sorter.finish():
                    ctx.check_running()
                    chunk = (sb if carry is None
                             else concat_batches([carry, sb], work_schema))
                    n = pull_rows(chunk, "window.chunk_rows")
                    split = self._last_partition_start(chunk, part_idx)
                    if split <= 0:
                        carry = chunk
                        continue
                    done = slice_batch(chunk, 0, split)
                    carry = slice_batch(chunk, split, n - split)
                    yield compute(done)
                if (carry is not None and
                        pull_rows(carry, "window.chunk_rows") > 0):
                    yield compute(carry)
                self.metrics.add("spill_count", sorter.spill_count)
            finally:
                sorter.abort()

        return count_stream(self, gen())

    def _last_partition_start(self, chunk: ColumnBatch,
                              part_idx: List[int]) -> int:
        """Row index where the final (possibly incomplete) partition begins
        — one host pull per merge chunk."""
        starts = seg.group_starts(chunk, part_idx)
        iota = jnp.arange(chunk.capacity, dtype=jnp.int32)
        last = jnp.max(jnp.where(starts, iota, -1))
        return int(pull_array(last, "window.last_start"))

    # ---- the fused kernel (input already in sorted work layout) ----
    def _compute_sorted(self, sb: ColumnBatch) -> ColumnBatch:
        nin = self._nin
        part_idx = self._part_idx
        in_idx = self._in_idx

        mask = sb.row_mask()
        cap = sb.capacity
        iota = jnp.arange(cap, dtype=jnp.int32)

        part_layout = seg.group_layout(sb, part_idx)
        # peer groups: partition AND order-key equality
        order_cols = [s.col for s in self.order_specs]
        peer_layout = seg.group_layout(sb, part_idx + order_cols)
        has_order = bool(self.order_specs)

        part_start_pos = part_layout.start_idx[
            jnp.clip(part_layout.gid, 0, cap - 1)]
        peer_start_pos = peer_layout.start_idx[
            jnp.clip(peer_layout.gid, 0, cap - 1)]
        peer_end_pos = peer_layout.end_idx[
            jnp.clip(peer_layout.gid, 0, cap - 1)]

        out_cols = list(sb.columns[:nin])
        for ci, (call, idxs) in enumerate(zip(self.calls, in_idx)):
            if call.fn == "row_number":
                v = (iota - part_start_pos + 1).astype(jnp.int32)
                out_cols.append(Column(T.INT32, jnp.where(mask, v, 0), None))
            elif call.fn == "rank":
                v = (peer_start_pos - part_start_pos + 1).astype(jnp.int32)
                out_cols.append(Column(T.INT32, jnp.where(mask, v, 0), None))
            elif call.fn == "dense_rank":
                # running count of peer starts within the partition
                dr = seg.segmented_scan(
                    peer_layout.starts.astype(jnp.int32),
                    part_layout.starts, lambda a, b: a + b)
                out_cols.append(Column(
                    T.INT32, jnp.where(mask, dr.astype(jnp.int32), 0), None))
            else:
                out_cols.append(self._agg_window(
                    call, sb.columns[idxs[0]], part_layout, peer_end_pos,
                    has_order, mask))
        return ColumnBatch(self._schema, out_cols, sb.num_rows, cap)

    def _agg_window(self, call: WindowCall, x: Column, part_layout,
                    peer_end_pos: Array, has_order: bool, mask: Array
                    ) -> Column:
        valid = x.valid_mask() & mask
        fn = call.fn
        if fn == "count":
            run = seg.segmented_scan(valid.astype(jnp.int64),
                                     part_layout.starts, lambda a, b: a + b)
            dtype, validity_from = T.INT64, None
        elif fn in ("sum", "avg"):
            sd = (_sum_state_dtype(call.dtype) if fn == "sum" else T.FLOAT64)
            v = jnp.where(valid, x.data.astype(sd.jnp_dtype()), 0)
            run = seg.segmented_scan(v, part_layout.starts, lambda a, b: a + b)
            cnt = seg.segmented_scan(valid.astype(jnp.int64),
                                     part_layout.starts, lambda a, b: a + b)
            if fn == "avg":
                run = run / jnp.maximum(cnt, 1).astype(jnp.float64)
            dtype, validity_from = sd if fn == "sum" else T.FLOAT64, cnt
        elif fn in ("min", "max"):
            is_float = jnp.issubdtype(x.data.dtype, jnp.floating)
            if fn == "min":
                ident = (jnp.inf if is_float
                         else jnp.iinfo(x.data.dtype).max)
                op = jnp.fmin
            else:
                ident = (-jnp.inf if is_float
                         else jnp.iinfo(x.data.dtype).min)
                op = jnp.maximum
            v = jnp.where(valid, x.data, jnp.asarray(ident, x.data.dtype))
            run = seg.segmented_scan(v, part_layout.starts, op)
            cnt = seg.segmented_scan(valid.astype(jnp.int64),
                                     part_layout.starts, lambda a, b: a + b)
            if fn == "min" and is_float:
                # all values so far NaN (fmin skipped them) -> NaN, Spark's
                # "NaN greatest" answer (matches segment.seg_min)
                nonnan = seg.segmented_scan(
                    (valid & ~jnp.isnan(x.data)).astype(jnp.int64),
                    part_layout.starts, lambda a, b: a + b)
                run = jnp.where((cnt > 0) & (nonnan == 0),
                                jnp.asarray(jnp.nan, run.dtype), run)
            dtype, validity_from = call.dtype, cnt
        else:
            raise NotImplementedError(f"window agg {fn}")

        if has_order:
            # RANGE frame: level the running value to the peer group end
            run = run[peer_end_pos]
            if validity_from is not None:
                validity_from = validity_from[peer_end_pos]
        else:
            # whole-partition frame: value at partition end
            cap = run.shape[0]
            part_end_pos = part_layout.end_idx[
                jnp.clip(part_layout.gid, 0, cap - 1)]
            run = run[part_end_pos]
            if validity_from is not None:
                validity_from = validity_from[part_end_pos]
        validity = None if validity_from is None else (validity_from > 0)
        return Column(dtype, run, validity)
