"""ExpandExec (grouping sets) and GenerateExec (explode).

Ref: datafusion-ext-plans expand_exec.rs (projection-list expansion) and
generate/ (explode/pos_explode of list columns, generate/mod.rs:29-49).
TPU-first: Expand evaluates each projection list over the whole batch and
concatenates (row order within a partition is not contractual); Generate is
the same gather-expansion as the join (offsets -> repeat -> element gather)
with one host sync for the output row count.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import (
    Column, ColumnBatch, ListData, bucket_capacity, pull_array, pull_rows,
)
from blaze_tpu.columnar.types import Field, Schema
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.compiler import compile_expr
from blaze_tpu.ops.base import (
    BatchStream, ExecContext, Operator, batch_tap, count_stream,
)
from blaze_tpu.runtime import jit_cache

Array = jax.Array


class ExpandExec(Operator):
    """Each input row emits one row per projection list (grouping sets)."""

    def __init__(self, child: Operator, projections: Sequence[Sequence[ir.Expr]],
                 schema: Schema) -> None:
        super().__init__([child])
        self.projections = [list(p) for p in projections]
        self._schema = schema
        self._fns = [[compile_expr(e, child.schema) for e in p]
                     for p in self.projections]

    @property
    def schema(self) -> Schema:
        return self._schema

    def plan_key(self) -> tuple:
        return ("expand",
                tuple(tuple(e.key() for e in p) for p in self.projections),
                self.children[0].plan_key())

    def execute(self, ctx: ExecContext) -> BatchStream:
        def gen():
            for batch in self.children[0].execute(ctx):
                ctx.check_running()
                jit = not any(ir.contains_host_fn(e)
                              for p_ in self.projections for e in p_)
                for pi, fns in enumerate(self._fns):
                    key = ("expand_kernel", jit, self.plan_key(), pi,
                           batch.shape_key())

                    def make(fns=fns):
                        def run(b: ColumnBatch) -> ColumnBatch:
                            cols = [fn(b) for fn in fns]
                            return b.with_columns(self._schema, cols)
                        return run

                    with self.metrics.timer():
                        yield jit_cache.get_or_compile(key, make,
                                                       jit=jit)(batch)

        return count_stream(self, gen())


class GenerateExec(Operator):
    """explode / pos_explode of a list column (ref generate/explode.rs).

    Output = required input columns (repeated per element) + [pos] + element
    column. `outer=True` keeps zero-length/null-list rows with a null
    element (ref Spark GenerateExec outer).
    """

    def __init__(self, child: Operator, child_expr: ir.Expr,
                 required_cols: Sequence[int], output_names: Sequence[str],
                 pos: bool = False, outer: bool = False) -> None:
        super().__init__([child])
        self.child_expr = child_expr
        self.required_cols = list(required_cols)
        self.output_names = list(output_names)
        self.pos = pos
        self.outer = outer
        self._list_fn = compile_expr(child_expr, child.schema)

        import jax as _jax

        probe = ColumnBatch.empty(child.schema, bucket_capacity(0))
        lcol = _jax.eval_shape(self._list_fn, probe)
        if lcol.dtype.kind != T.TypeKind.LIST:
            raise NotImplementedError(
                f"generate over {lcol.dtype} (only list explode supported)")
        self._elem_dtype = lcol.dtype.element

        for i in self.required_cols:
            if child.schema.fields[i].dtype.kind == T.TypeKind.LIST:
                # repeating a list column through the fan-out gather would
                # overflow its element storage (_list_take) — fall back
                raise NotImplementedError(
                    "generate with list-typed required columns")
        fields = [Field(child.schema.fields[i].name,
                        child.schema.fields[i].dtype,
                        child.schema.fields[i].nullable)
                  for i in self.required_cols]
        gen_fields = []
        if pos:
            # posexplode_outer emits NULL pos for kept empty/null lists
            gen_fields.append(Field(self.output_names[0], T.INT32,
                                    nullable=outer))
        gen_fields.append(Field(self.output_names[-1], self._elem_dtype))
        self._schema = Schema(fields + gen_fields)

    @property
    def schema(self) -> Schema:
        return self._schema

    def plan_key(self) -> tuple:
        return ("generate", self.child_expr.key(),
                tuple(self.required_cols), self.pos, self.outer,
                self.children[0].plan_key())

    def execute(self, ctx: ExecContext) -> BatchStream:
        def gen():
            note = batch_tap(self)  # fed here: the rows are pulled here
            for batch in self.children[0].execute(ctx):
                ctx.check_running()
                if pull_rows(batch, "expand.input_rows") == 0:
                    continue
                out = self._explode(batch)
                rows = 0 if out is None else pull_rows(out, "expand.out_rows")
                if rows:
                    note(out, rows)
                    yield out

        return gen()

    def _explode(self, batch: ColumnBatch) -> Optional[ColumnBatch]:
        lcol: Column = self._list_fn(batch)
        ld: ListData = lcol.data
        mask = batch.row_mask()
        lens = jnp.where(mask & lcol.valid_mask(), ld.lengths(), 0)
        eff = jnp.maximum(lens, 1) if self.outer else lens
        eff = jnp.where(mask, eff, 0)
        total = int(pull_array(jnp.sum(eff), "expand.total"))
        if total == 0:
            return None
        out_cap = bucket_capacity(total)
        key = ("generate_kernel", self.plan_key(), out_cap,
               batch.shape_key())

        def make():
            def run(b: ColumnBatch):
                lc = self._list_fn(b)
                ldd: ListData = lc.data
                m = b.row_mask()
                lens = jnp.where(m & lc.valid_mask(), ldd.lengths(), 0)
                eff = jnp.maximum(lens, 1) if self.outer else lens
                eff = jnp.where(m, eff, 0)
                offs = jnp.concatenate([
                    jnp.zeros((1,), jnp.int32),
                    jnp.cumsum(eff, dtype=jnp.int32)])
                num = offs[-1]
                row = jnp.repeat(jnp.arange(b.capacity, dtype=jnp.int32),
                                 eff, total_repeat_length=out_cap)
                slot = jnp.arange(out_cap, dtype=jnp.int32)
                within = slot - offs[row]
                elem_ok = within < lens[row]
                src = ldd.offsets[row] + within
                live = slot < num
                row = jnp.where(live, row, 0)
                src = jnp.where(live & elem_ok, src, 0)

                cols = [b.columns[i].take(row) for i in self.required_cols]
                if self.pos:
                    pos_validity = (elem_ok & live) if self.outer else None
                    cols.append(Column(T.INT32,
                                       jnp.where(elem_ok, within, 0),
                                       pos_validity))
                elem = ldd.elements.take(src, index_valid=elem_ok & live)
                cols.append(elem)
                return ColumnBatch(self._schema, cols, num, out_cap)
            return run

        with self.metrics.timer():
            return jit_cache.get_or_compile(
                key, make, jit=not ir.contains_host_fn(self.child_expr))(batch)
