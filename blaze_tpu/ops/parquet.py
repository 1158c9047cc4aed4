"""ParquetScanExec / ParquetSinkExec — columnar file IO.

Ref: datafusion-ext-plans parquet_exec.rs (scan with row-group pruning via
pushed predicates, all file IO through a JVM Hadoop FileSystem resource,
ignoreCorruptFiles, schema adaption casts :66,250) and parquet_sink_exec.rs
(Arrow->parquet into a JVM output stream, Hive-compatible part files).

TPU-first shape: pyarrow does the parquet decode on host (the reference's
arrow-rs does the same on CPU — parquet decode is not a TPU workload), one
device transfer per column per batch, and everything downstream is jitted.
Row-group pruning evaluates the pushed predicates against row-group
statistics before any data pages are read. The `fs_resource_id` hook lets an
embedding layer substitute opened file objects (the Hadoop FS callback path,
hadoop_fs.rs) — local paths are opened directly when absent.
"""

from __future__ import annotations

import logging
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.arrow_io import (batch_from_arrow, batch_to_arrow,
    schema_to_arrow)
from blaze_tpu.columnar.batch import ColumnBatch, pull_rows
from blaze_tpu.columnar.types import Field, Schema
from blaze_tpu.config import conf
from blaze_tpu.exprs import ir
from blaze_tpu.ops.base import BatchStream, ExecContext, Operator, count_stream
from blaze_tpu.runtime import resources, trace

logger = logging.getLogger(__name__)

# A scan batch's column buffers are 16-32 MB each. Arrow's default pool on
# Linux (mimalloc) gives such a buffer back to the OS when it is freed and
# maps a fresh one for every second scan: 80 ms of page faults before a
# query's first upload, every other query, so q06-core's queries took 0.69
# and 0.80 s in turn and a window's median fell anywhere between (v5e host,
# transparent huge pages off; PERF.md section 6, PR 32). glibc's allocator
# keeps the pages: 0.73-0.77 s, every query. A deployment that names its
# pool (ARROW_DEFAULT_MEMORY_POOL) keeps it.
if "ARROW_DEFAULT_MEMORY_POOL" not in os.environ:
    pa.set_memory_pool(pa.system_memory_pool())


def _stat_prune(expr: ir.Expr, stats: Dict[str, Tuple]) -> bool:
    """True if the row group can be SKIPPED based on min/max stats.

    Conservative: only simple `col <op> literal` comparisons prune;
    everything else keeps the group (ref: row-group pruning via pushed
    predicates, parquet_exec.rs:218-239).
    """
    if isinstance(expr, ir.Binary):
        if expr.op == ir.BinOp.AND:
            return (_stat_prune(expr.left, stats) or
                    _stat_prune(expr.right, stats))
        l, r = expr.left, expr.right
        if isinstance(l, ir.Literal) and isinstance(r, ir.Col):
            flip = {ir.BinOp.LT: ir.BinOp.GT, ir.BinOp.LE: ir.BinOp.GE,
                    ir.BinOp.GT: ir.BinOp.LT, ir.BinOp.GE: ir.BinOp.LE,
                    ir.BinOp.EQ: ir.BinOp.EQ}
            if expr.op in flip:
                return _stat_prune(ir.Binary(flip[expr.op], r, l), stats)
            return False
        if not (isinstance(l, ir.Col) and isinstance(r, ir.Literal)):
            return False
        st = stats.get(l.name)
        if st is None or st[0] is None or st[1] is None or r.value is None:
            return False
        mn, mx = st
        v = r.value
        if r.dtype.is_decimal:
            # the literal holds its unscaled integer, the statistics a
            # decimal.Decimal: 50000 under decimal(7,2) is 500.00
            import decimal

            v = decimal.Decimal(int(v)).scaleb(-r.dtype.scale)
        try:
            if expr.op == ir.BinOp.EQ:
                return v < mn or v > mx
            if expr.op == ir.BinOp.LT:
                return mn >= v
            if expr.op == ir.BinOp.LE:
                return mn > v
            if expr.op == ir.BinOp.GT:
                return mx <= v
            if expr.op == ir.BinOp.GE:
                return mx < v
        except TypeError:
            return False
    return False


class ParquetScanExec(Operator):
    """One task partition's parquet files -> device batches."""

    def __init__(self, files: Sequence[Tuple[str, list]],
                 file_schema: Schema,
                 projection: Sequence[int],
                 partition_schema: Optional[Schema] = None,
                 pruning_predicates: Sequence[ir.Expr] = (),
                 fs_resource_id: Optional[str] = None,
                 batch_rows: Optional[int] = None,
                 raw_files: Optional[list] = None) -> None:
        super().__init__([])
        self.files = list(files)
        self.file_schema = file_schema
        self.projection = list(projection) or list(
            range(len(file_schema.fields)))
        self.partition_schema = partition_schema or Schema([])
        self.pruning_predicates = list(pruning_predicates)
        self.fs_resource_id = fs_resource_id
        self.batch_rows = batch_rows  # None -> adaptive (execute time)
        self.raw_files = raw_files

        read_fields = [file_schema.fields[i] for i in self.projection]
        self._schema = Schema(read_fields +
                              list(self.partition_schema.fields))

    @property
    def schema(self) -> Schema:
        return self._schema

    def plan_key(self) -> tuple:
        return ("parquet_scan", tuple(self._schema.names()))

    def _open(self, path: str):
        if self.fs_resource_id:
            fs = resources.get(self.fs_resource_id)
            return fs(path) if callable(fs) else fs.open(path)
        # default resolver: scheme:// URIs route through fsspec (the
        # Hadoop-FS-per-URI analog, hadoop_fs.rs:23-132); local paths
        # pass through for pyarrow to open directly
        from blaze_tpu.runtime import filesystem

        return filesystem.open_input(path)

    def execute(self, ctx: ExecContext) -> BatchStream:
        def gen():
            from blaze_tpu.ops.common import adaptive_batch_rows

            # macro-batching: every batch costs fixed dispatches and host
            # round trips, so source batch size is a throughput lever;
            # size to the byte target unless pinned
            batch_rows = self.batch_rows or adaptive_batch_rows(
                self._schema)
            names = [self.file_schema.fields[i].name
                     for i in self.projection]
            for path, part_values in self.files:
                ctx.check_running()
                try:
                    pf = pq.ParquetFile(self._open(path))
                except Exception:
                    if conf.ignore_corrupt_files:
                        logger.warning("ignoring corrupt file %s", path)
                        continue
                    raise
                with pf:  # closes the underlying (fs-provided) handle
                    groups = self._select_row_groups(pf)
                    self.metrics.add("row_groups_pruned",
                                     pf.num_row_groups - len(groups))
                    if not groups:
                        continue
                    batches = pf.iter_batches(batch_size=batch_rows,
                                              row_groups=groups,
                                              columns=names)
                    while True:
                        # read + decode happen in the reader's next():
                        # pull each record batch inside its span (the
                        # pull that finds the end is a span of 0 rows)
                        with trace.span("scan_decode", file=path) as sp:
                            rb = next(batches, None)
                            if rb is None:
                                break
                            sp.set(rows=rb.num_rows, bytes=rb.nbytes)
                        ctx.check_running()
                        # io_time_ns and the h2d span are the host's time
                        # in the call (arrow -> numpy staging + enqueue of
                        # the transfer), not the transfer itself
                        with self.metrics.timer("io_time_ns"), \
                                trace.span("h2d", rows=rb.num_rows,
                                           bytes=rb.nbytes, what="scan"):
                            batch = self._to_device(rb, part_values)
                        self.metrics.add("bytes_scanned", rb.nbytes)
                        yield batch

        from blaze_tpu.runtime import memory as M, pipeline

        # prefetch: parquet read+decode+upload of the next macro-batch
        # runs on the I/O pool while downstream computes on this one
        return count_stream(self, pipeline.prefetch(
            gen(), ctx=ctx, manager=M.get_manager(ctx), name="parquet_scan"))

    def _select_row_groups(self, pf) -> List[int]:
        if not self.pruning_predicates:
            return list(range(pf.num_row_groups))
        keep = []
        meta = pf.metadata
        for g in range(pf.num_row_groups):
            rg = meta.row_group(g)
            stats: Dict[str, Tuple] = {}
            for c in range(rg.num_columns):
                col = rg.column(c)
                st = col.statistics
                if st is not None and st.has_min_max:
                    stats[col.path_in_schema] = (st.min, st.max)
            skipped = any(_stat_prune(p, stats)
                          for p in self.pruning_predicates)
            if not skipped:
                keep.append(g)
        return keep

    def _to_device(self, rb: pa.RecordBatch, part_values: list
                   ) -> ColumnBatch:

        read_schema = Schema([self.file_schema.fields[i]
                              for i in self.projection])
        base = batch_from_arrow(rb, schema=read_schema)
        if not self.partition_schema.fields:
            return base
        # hive partition columns: per-file constant literals (ref
        # NativeParquetScanBase partition values as literals)
        from blaze_tpu.exprs.compiler import compile_expr

        cols = list(base.columns)
        for f, v in zip(self.partition_schema.fields, part_values):
            lit = v if isinstance(v, ir.Literal) else _scalar_to_literal(v, f)
            cols.append(compile_expr(lit, base.schema)(base))
        return base.with_columns(self._schema, cols)


def _scalar_to_literal(v, f: Field) -> ir.Literal:
    from blaze_tpu.plan.from_proto import decode_scalar

    if hasattr(v, "dtype"):  # pb.ScalarValue
        return decode_scalar(v)
    return ir.Literal(f.dtype, v)


class ParquetSinkExec(Operator):
    """Arrow->parquet writer (ref parquet_sink_exec.rs; used by the
    NativeParquetInsertIntoHiveTable path). Emits one part file; yields a
    single stats row (path, num_rows, num_bytes) like the reference's
    sink output."""

    STATS_SCHEMA = Schema([Field("path", T.STRING, nullable=False),
                           Field("num_rows", T.INT64, nullable=False),
                           Field("num_bytes", T.INT64, nullable=False)])

    def __init__(self, child: Operator, path: str,
                 fs_resource_id: Optional[str] = None,
                 row_group_rows: Optional[int] = None,
                 props: Optional[Dict[str, str]] = None) -> None:
        super().__init__([child])
        self.path = path
        self.fs_resource_id = fs_resource_id
        self.row_group_rows = row_group_rows or 1 << 20
        self.props = props or {}

    @property
    def schema(self) -> Schema:
        return self.STATS_SCHEMA

    def plan_key(self) -> tuple:
        return ("parquet_sink", self.path, self.children[0].plan_key())

    def is_remote(self) -> bool:
        from blaze_tpu.runtime import filesystem

        return bool(self.fs_resource_id) or (
            filesystem.path_scheme(self.path) is not None)

    @staticmethod
    def clear_stale_parts(path: str) -> None:
        """Overwrite semantics for a local multi-task write: re-running
        into the same path must not leave a previous run's
        higher-numbered parts behind. This MUST run before any task of
        the new run is dispatched (local_runner calls it driver-side) —
        clearing from inside a task races task scheduling and can
        delete parts the current run already committed. In deployment
        the embedding layer's output-commit protocol owns this — the
        reference leans on Hive temp+move semantics the same way
        (NativeParquetInsertIntoHiveTableBase)."""
        import glob as _glob
        import os as _os

        _os.makedirs(path, exist_ok=True)
        for stale in _glob.glob(_os.path.join(path, "part-*.parquet")):
            _os.remove(stale)

    def _task_path(self, ctx: ExecContext) -> str:
        """Per-task part file (ref: Hive-compatible part files,
        parquet_sink_exec.rs): a multi-task stage writing ONE path would
        have every task truncate the previous tasks' rows. With one task
        the path is used as-is unless it already IS a part directory."""
        import os as _os

        remote = self.is_remote()
        if ctx.num_partitions <= 1 and not (
                not remote and _os.path.isdir(self.path)):
            return self.path
        if not remote:
            _os.makedirs(self.path, exist_ok=True)
        return _os.path.join(self.path,
                             f"part-{ctx.partition:05d}.parquet")

    def execute(self, ctx: ExecContext) -> BatchStream:
        def gen():
            child = self.children[0]
            arrow_schema = schema_to_arrow(child.schema)
            out_path = self._task_path(ctx)
            sink = out_path
            if self.fs_resource_id:
                fs = resources.get(self.fs_resource_id)
                sink = fs(out_path) if callable(fs) else fs.open(out_path,
                                                                 "wb")
            else:
                from blaze_tpu.runtime import filesystem

                sink = filesystem.open_output(out_path)
            compression = self.props.get("compression", "zstd")
            writer = pq.ParquetWriter(sink, arrow_schema,
                                      compression=compression)
            rows = 0
            try:
                for batch in child.execute(ctx):
                    ctx.check_running()
                    n = pull_rows(batch, "parquet_sink.input_rows")
                    if n == 0:
                        continue
                    with self.metrics.timer("io_time_ns"):
                        writer.write_batch(batch_to_arrow(batch),
                                           row_group_size=self.row_group_rows)
                    rows += n
            finally:
                writer.close()
                if not isinstance(sink, str) and hasattr(sink, "close"):
                    sink.close()
            from blaze_tpu.runtime import filesystem

            nbytes = (0 if self.fs_resource_id
                      else filesystem.size(out_path))
            self.metrics.add("output_rows_written", rows)
            yield ColumnBatch.from_numpy(
                {"path": [out_path], "num_rows": np.array([rows], np.int64),
                 "num_bytes": np.array([nbytes], np.int64)},
                self.STATS_SCHEMA)

        return count_stream(self, gen())
