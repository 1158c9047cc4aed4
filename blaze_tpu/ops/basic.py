"""Map-like and utility operators: Project, Filter, Rename, Limit, Union,
CoalesceBatches, Empty, MemorySource, Debug.

Ref: datafusion-ext-plans project_exec.rs / filter_exec.rs /
rename_columns_exec.rs / limit_exec.rs / empty_partitions_exec.rs /
coalesce_batches_exec.rs / debug_exec.rs. Filter+Project fuse into one XLA
program via the executor (the reference fuses them inside
CachedExprsEvaluator instead, cached_exprs_evaluator.rs:38-60).
"""

from __future__ import annotations

import logging
from typing import Callable, List, Optional, Sequence

import jax.numpy as jnp

from blaze_tpu.columnar.batch import ColumnBatch, bucket_capacity, pull_rows
from blaze_tpu.columnar.types import Field, Schema
from blaze_tpu.config import conf
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.compiler import compile_expr
from blaze_tpu.ops.base import (
    BatchStream, ExecContext, MapLikeOp, Operator, batch_tap, count_stream,
)
from blaze_tpu.ops.common import concat_batches

logger = logging.getLogger(__name__)


class MemorySourceExec(Operator):
    """Test/ingest source from pre-built batches (ref: DataFusion MemoryExec,
    the fixture used throughout the reference's operator tests, SURVEY.md §4).
    """

    def __init__(self, batches: List[ColumnBatch], schema: Optional[Schema] = None) -> None:
        super().__init__([])
        self._batches = batches
        self._schema = schema or batches[0].schema

    @property
    def schema(self) -> Schema:
        return self._schema

    def plan_key(self) -> tuple:
        return ("mem", tuple(self._schema.names()))

    def execute(self, ctx: ExecContext) -> BatchStream:
        return count_stream(self, iter(self._batches))


class ProjectExec(MapLikeOp):
    """Ref: project_exec.rs; exprs compiled to jax, fused upstream/downstream."""

    def __init__(self, child: Operator, exprs: Sequence[ir.Expr],
                 names: Sequence[str], dtypes=None) -> None:
        super().__init__(child)
        self.exprs = list(exprs)
        self.names = list(names)
        self._fns = [compile_expr(e, child.schema) for e in self.exprs]
        if dtypes is None:
            dtypes = [self._infer_dtype(e, f) for e, f in zip(self.exprs, self._fns)]
        self._schema = Schema([Field(n, d) for n, d in zip(self.names, dtypes)])

    def _infer_dtype(self, expr, fn):
        probe = ColumnBatch.empty(self.child.schema, capacity=bucket_capacity(0))
        import jax

        out = jax.eval_shape(fn, probe)
        # eval_shape returns a Column pytree with dtype aux intact
        return out.dtype

    @property
    def schema(self) -> Schema:
        return self._schema

    def plan_key(self) -> tuple:
        return ("project", tuple(e.key() for e in self.exprs), tuple(self.names),
                self.child.plan_key())

    def jit_safe(self) -> bool:
        return not any(ir.contains_host_fn(e) for e in self.exprs)

    def make_batch_fn(self) -> Callable[[ColumnBatch], ColumnBatch]:
        fns, schema = self._fns, self._schema

        def run(batch: ColumnBatch) -> ColumnBatch:
            cols = [fn(batch) for fn in fns]
            return batch.with_columns(schema, cols)

        return run


class FilterExec(MapLikeOp):
    """Ref: filter_exec.rs. Predicate -> mask -> in-jit compaction."""

    def __init__(self, child: Operator, predicates: Sequence[ir.Expr]) -> None:
        super().__init__(child)
        self.predicates = list(predicates)
        self._fns = [compile_expr(p, child.schema) for p in self.predicates]

    @property
    def schema(self) -> Schema:
        return self.child.schema

    def plan_key(self) -> tuple:
        return ("filter", tuple(p.key() for p in self.predicates), self.child.plan_key())

    def jit_safe(self) -> bool:
        return not any(ir.contains_host_fn(p) for p in self.predicates)

    def make_keep_fn(self) -> Callable[[ColumnBatch], jnp.ndarray]:
        """The rows the predicates keep, as one boolean plane over the
        batch's slots (true AND valid, ANDed over the predicates; padding
        slots are the caller's to mask). `make_batch_fn` compacts by it; a
        partial aggregate fed directly by this filter carries it into its
        collapse instead (ops/agg)."""
        fns = self._fns

        def keep_of(batch: ColumnBatch):
            keep = None
            for fn in fns:
                c = fn(batch)
                m = c.data.astype(jnp.bool_) & c.valid_mask()
                keep = m if keep is None else (keep & m)
            return keep

        return keep_of

    def make_batch_fn(self) -> Callable[[ColumnBatch], ColumnBatch]:
        keep_of = self.make_keep_fn()

        def run(batch: ColumnBatch) -> ColumnBatch:
            return batch.compact(keep_of(batch))

        return run


class RenameColumnsExec(MapLikeOp):
    """Ref: rename_columns_exec.rs (the `#<exprId>` naming normalizer)."""

    def __init__(self, child: Operator, names: Sequence[str]) -> None:
        super().__init__(child)
        self.names = list(names)
        self._schema = Schema([Field(n, f.dtype, f.nullable)
                               for n, f in zip(self.names, child.schema)])

    @property
    def schema(self) -> Schema:
        return self._schema

    def plan_key(self) -> tuple:
        return ("rename", tuple(self.names), self.child.plan_key())

    def make_batch_fn(self):
        schema = self._schema

        def run(batch: ColumnBatch) -> ColumnBatch:
            return batch.with_columns(schema, batch.columns)

        return run


class LocalLimitExec(Operator):
    """Ref: limit_exec.rs LocalLimitExec — truncate the stream at k rows."""

    def __init__(self, child: Operator, limit: int) -> None:
        super().__init__([child])
        self.limit = limit

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def plan_key(self) -> tuple:
        return ("local_limit", self.limit, self.children[0].plan_key())

    def execute(self, ctx: ExecContext) -> BatchStream:
        def gen():
            note = batch_tap(self)  # fed here: a passed batch's rows are known
            remaining = self.limit
            for batch in self.children[0].execute(ctx):
                if remaining <= 0:
                    break
                n = pull_rows(batch, "limit.input_rows")
                if n <= remaining:
                    remaining -= n
                    note(batch, n)
                    yield batch
                else:
                    batch = batch.with_num_rows(remaining)
                    note(batch)
                    yield batch
                    remaining = 0

        return gen()


class GlobalLimitExec(LocalLimitExec):
    """Ref: limit_exec.rs GlobalLimitExec (plan guarantees 1 partition)."""

    def plan_key(self) -> tuple:
        return ("global_limit", self.limit, self.children[0].plan_key())


class UnionExec(Operator):
    """Ref: from_proto.rs :453 Union — concatenation of child streams."""

    def __init__(self, children: List[Operator]) -> None:
        super().__init__(children)

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, ctx: ExecContext) -> BatchStream:
        def gen():
            for child in self.children:
                yield from child.execute(ctx)

        return count_stream(self, gen())


class EmptyPartitionsExec(Operator):
    """Ref: empty_partitions_exec.rs — schema-only, zero rows."""

    def __init__(self, schema: Schema, num_partitions: int = 1) -> None:
        super().__init__([])
        self._schema = schema
        self.num_partitions = num_partitions

    @property
    def schema(self) -> Schema:
        return self._schema

    def plan_key(self) -> tuple:
        return ("empty", tuple(self._schema.names()))

    def execute(self, ctx: ExecContext) -> BatchStream:
        return iter(())


class CoalesceBatchesExec(Operator):
    """Ref: streams/coalesce_stream.rs — re-chunk to the configured batch
    size. Buffers small batches and concatenates them on device."""

    def __init__(self, child: Operator, batch_size: Optional[int] = None) -> None:
        super().__init__([child])
        self.batch_size = batch_size

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def plan_key(self) -> tuple:
        return ("coalesce", self.batch_size, self.children[0].plan_key())

    def execute(self, ctx: ExecContext) -> BatchStream:
        target = self.batch_size or ctx.batch_size or conf.batch_size

        def gen():
            note = batch_tap(self)  # fed here: a passed batch's rows are known
            pending: List[ColumnBatch] = []
            pending_rows = 0
            for batch in self.children[0].execute(ctx):
                n = pull_rows(batch, "coalesce.input_rows")
                if n == 0:
                    continue
                staged = False
                if n < target // 2 or pending:
                    pending.append(batch)
                    pending_rows += n
                    staged = True
                if pending_rows >= target:
                    out = concat_batches(pending, self.schema)
                    note(out)
                    yield out
                    pending, pending_rows = [], 0
                if not staged:
                    note(batch, n)
                    yield batch
            if pending:
                out = concat_batches(pending, self.schema)
                note(out)
                yield out

        return gen()


class DebugExec(Operator):
    """Ref: debug_exec.rs — log batches flowing through a tagged point."""

    def __init__(self, child: Operator, tag: str = "") -> None:
        super().__init__([child])
        self.tag = tag

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, ctx: ExecContext) -> BatchStream:
        def gen():
            for i, batch in enumerate(self.children[0].execute(ctx)):
                logger.info("[DEBUG %s] batch %d: %d rows\n%s", self.tag, i,
                            pull_rows(batch, "debug.rows"), batch.to_numpy())
                yield batch

        return count_stream(self, gen())
