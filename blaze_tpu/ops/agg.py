"""AggExec — grouped aggregation, sort-based, partial/merge/final modes.

Ref: datafusion-ext-plans agg_exec.rs + agg/ (modes Partial/PartialMerge/
Final, agg/mod.rs:41-51; accumulators sum/avg/count/min/max/first/
first_ignores_null, agg/*.rs; in-memory hash tables with bucket-sorted spill,
agg_tables.rs). TPU-first redesign: there are no hash tables — rows are
sorted by the grouping key and every accumulator update becomes a segmented
scan/reduce (ops/segment.py), one fused XLA program per shape bucket.

State layout divergence from the reference: Blaze packs accumulator state
into ONE opaque binary column (AGG_BUF_COLUMN_NAME "#9223372036854775807",
agg/mod.rs:38, NativeAggBase.scala:126-134) because its buffers are
row-addressed byte blocks. Ours are columnar by construction, so partial
output carries *typed state columns* (e.g. sum + nonempty flag). The state
is engine-opaque either way (Spark never parses it); only the column naming
convention is kept (`#<MAX_LONG>.<i>` prefixes) so plan pairing logic maps.

Streaming: input batches fold into a bounded pending set; when pending rows
exceed the collapse threshold they are aggregated into a single state batch
(the sort-based analog of the reference's partial-skipping + table merge).
"""

from __future__ import annotations

import dataclasses
import enum
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import (
    Column, ColumnBatch, bucket_capacity, nonzero_i32, pull_array, pull_rows,
)
from blaze_tpu.columnar.types import DataType, Field, Schema, TypeKind
from blaze_tpu.config import conf
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.compiler import compile_expr
from blaze_tpu.ops import segment as seg
from blaze_tpu.ops.base import BatchStream, ExecContext, Operator, batch_tap
from blaze_tpu.ops.basic import FilterExec
from blaze_tpu.ops.common import concat_batches
from blaze_tpu.ops.sort import truncate
from blaze_tpu.ops.sort_keys import SortSpec, sort_batch
from blaze_tpu.runtime import compile_service, jit_cache

# agg_collapse cache key -> ops/segment.count_forms' tally of the program's
# trace: how many per-group reductions it has in each form (scan, scatter),
# how many of them add numbers (sums) and how many of those integers
# (int_sums)
_SEG_FORMS: dict = {}

AGG_BUF_PREFIX = "#9223372036854775807"  # ref agg/mod.rs:38
# the last plane of a raw work batch whose filter was not run on its own:
# the filter's verdict on each slot (AggExec._mask_filter)
KEEP_PLANE = "filter.keep"


class AggMode(enum.Enum):
    PARTIAL = "partial"
    PARTIAL_MERGE = "partial_merge"
    FINAL = "final"


@dataclasses.dataclass(frozen=True)
class AggCall:
    """One aggregate expression (ref pb.AggFunction, blaze.proto:123-133)."""
    fn: str  # sum|avg|count|min|max|first|first_ignores_null
    inputs: Tuple[ir.Expr, ...]
    dtype: DataType          # Spark result dtype (planner-provided)
    name: str

    def key(self) -> tuple:
        return (self.fn, tuple(e.key() for e in self.inputs),
                repr(self.dtype), self.name)


def _sum_state_dtype(d: DataType) -> DataType:
    # Spark sum: int family -> long, float family -> double, decimal widens
    if d.kind == TypeKind.DECIMAL:
        return d
    if d.kind in (TypeKind.FLOAT32, TypeKind.FLOAT64):
        return T.FLOAT64
    return T.INT64


# Spark's result types over a decimal(p, s) input, precisions bounded at 38
# (Sum.resultType, Average.resultType / sumDataType):
#   sum -> decimal(p + 10, s)
#   avg -> decimal(p + 4, s + 4) over a sum buffer decimal(p + 10, s)
_SUM_DIGITS, _AVG_DIGITS, _AVG_SCALE = 10, 4, 4


def _bounded(precision: int, scale: int) -> DataType:
    return T.decimal(min(precision, 38), min(scale, 38))


def avg_sum_dtype(result: DataType) -> DataType:
    """The type of an avg's sum state, from the avg's result type alone (a
    FINAL operator sees state columns, not the input): double unless the
    result is decimal(p + 4, s + 4), whose buffer is decimal(p + 10, s)."""
    if not result.is_decimal:
        return T.FLOAT64
    if result.scale < _AVG_SCALE:
        raise TypeError(
            f"avg planned as {result}: Spark types avg(decimal(p,s)) as "
            f"decimal(p+{_AVG_DIGITS},s+{_AVG_SCALE}), so its scale is at "
            f"least {_AVG_SCALE}")
    return _bounded(result.precision - _AVG_DIGITS + _SUM_DIGITS,
                    result.scale - _AVG_SCALE)


def check_decimal_call(call: "AggCall", in_dtype: DataType) -> None:
    """Plan time: a sum or avg that touches a decimal carries the result
    type Spark plans for its input's type. Anything else is a plan Spark
    never produces, and guessing a rescale for it would hide a mislabel
    (a scale-2 sum under a scale-6 label is wrong by 10^4, silently)."""
    if call.fn not in ("sum", "avg") or not (
            in_dtype.is_decimal or call.dtype.is_decimal):
        return
    if not in_dtype.is_decimal:
        raise TypeError(f"{call.fn}({in_dtype}) planned as {call.dtype}: a "
                        "decimal result needs a decimal input")
    p, sc = in_dtype.precision, in_dtype.scale
    want = (_bounded(p + _SUM_DIGITS, sc) if call.fn == "sum"
            else _bounded(p + _AVG_DIGITS, sc + _AVG_SCALE))
    if call.dtype != want:
        raise TypeError(f"{call.fn}({in_dtype}) planned as {call.dtype}; "
                        f"Spark plans it as {want}")


def collect_state_dtype(call: AggCall) -> DataType:
    """List dtype of a collect_list/collect_set state/result column."""
    return (call.dtype if call.dtype.kind == TypeKind.LIST
            else T.list_of(call.dtype))


def state_fields(call: AggCall, i: int) -> List[Field]:
    """Typed state columns for one agg (named with the agg-buf convention)."""
    p = f"{AGG_BUF_PREFIX}.{i}"
    if call.fn == "sum":
        sd = _sum_state_dtype(call.dtype)
        return [Field(f"{p}.sum", sd), Field(f"{p}.nonempty", T.BOOLEAN)]
    if call.fn == "avg":
        return [Field(f"{p}.sum", avg_sum_dtype(call.dtype)),
                Field(f"{p}.count", T.INT64)]
    if call.fn == "count":
        return [Field(f"{p}.count", T.INT64)]
    if call.fn in ("min", "max"):
        return [Field(f"{p}.val", call.dtype), Field(f"{p}.has", T.BOOLEAN)]
    if call.fn == "first":
        return [Field(f"{p}.val", call.dtype), Field(f"{p}.valid", T.BOOLEAN),
                Field(f"{p}.has", T.BOOLEAN)]
    if call.fn == "first_ignores_null":
        return [Field(f"{p}.val", call.dtype), Field(f"{p}.has", T.BOOLEAN)]
    if call.fn in ("collect_list", "collect_set"):
        return [Field(f"{p}.list", collect_state_dtype(call))]
    raise NotImplementedError(f"agg function {call.fn}")


def result_field(call: AggCall) -> Field:
    if call.fn == "count":
        return Field(call.name, T.INT64, nullable=False)
    if call.fn == "avg" and call.dtype.kind != TypeKind.DECIMAL:
        return Field(call.name, T.FLOAT64)
    if call.fn == "sum":
        return Field(call.name, _sum_state_dtype(call.dtype))
    return Field(call.name, call.dtype)


def _seg_any(flags, layout):
    return seg.seg_any(flags, layout)


def _first_by_index(values_cols: Sequence[Column], layout, has) -> Tuple[list, jax.Array]:
    """Gather several parallel state columns at each group's first row where
    `has` — returns gathered Columns (as raw (data, validity) pairs) + ok."""
    cap = has.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    idx, ok = seg.seg_first(iota, layout, has, ignores_null=True)
    idx = jnp.clip(idx, 0, cap - 1)
    out = []
    for c in values_cols:
        out.append(c.take(idx))
    return out, ok


def _first_occurrence(x: Column, gid_key: jax.Array) -> jax.Array:
    """True at the first row of each distinct (gid, value) pair.

    Sorts (gid, value-encoding, iota), marks run starts, scatters the marks
    back to original row positions. Rows whose gid_key is the out-of-range
    sentinel never mark. Used by collect_set dedup (ref collect_set.rs's
    per-group HashSet — sort-based here, SURVEY.md §7b)."""
    cap = x.capacity
    iota = jnp.arange(cap, dtype=jnp.int32)
    if x.is_list or x.is_struct:
        # nested value types have no sort encoding yet; the planner rejects
        # collect_set over them (converters._check_agg_call)
        raise NotImplementedError(
            "collect_set over nested value types is not supported")
    if x.is_string:
        from blaze_tpu.ops.sort_keys import string_words

        words = string_words(x.data)
        vals = tuple(words) + (x.data.lengths,)
    else:
        data = x.data
        if data.dtype == jnp.bool_:
            data = data.astype(jnp.int32)
            vals = (data,)
        elif jnp.issubdtype(data.dtype, jnp.floating):
            # total-order bit encoding: adjacent NaNs compare EQUAL so the
            # dedup collapses them (spark set semantics: NaN == NaN)
            from blaze_tpu.ops.sort_keys import _float_total_order

            vals = tuple(_float_total_order(data))
        else:
            vals = (data,)
    ops = (gid_key,) + vals + (iota,)
    sorted_ops = jax.lax.sort(ops, num_keys=len(ops) - 1, is_stable=True)
    sgid, svals, perm = sorted_ops[0], sorted_ops[1:-1], sorted_ops[-1]
    neq = sgid != jnp.roll(sgid, 1)
    for v in svals:
        neq = neq | (v != jnp.roll(v, 1))
    first = (neq.at[0].set(True)) & (sgid < 2 ** 30)
    return jnp.zeros((cap,), jnp.bool_).at[perm].set(first)


def finalize_sum(call: AggCall, s: Column, nonempty: jax.Array) -> Column:
    """A sum's state -> its result column: null where no value was summed
    and, for a decimal, where the sum left the result precision (Spark,
    ANSI off: CheckOverflow nulls |sum| >= 10^p). The streaming finalize
    and the whole-stage program (runtime/stage_compiler) both end here."""
    dt = s.dtype
    if not dt.is_decimal:
        return Column(dt, s.data, nonempty)
    if dt.wide_decimal:
        from blaze_tpu.columnar import int128 as i128
        from blaze_tpu.exprs import wide_decimal as W

        # the seg shadow only catches magnitudes past 1.5e38
        h, l = W.planes(s)
        ok = s.valid_mask() & i128.in_precision(h, l, call.dtype.precision)
        return Column(call.dtype, s.data, nonempty & ok)
    ok = jnp.abs(s.data) < np.int64(10 ** call.dtype.precision)
    return Column(call.dtype, s.data, nonempty & ok)


def finalize_avg(call: AggCall, s: Column, cnt: jax.Array) -> Column:
    """An avg's (sum, count) state -> its result column. A decimal avg is
    Spark's: the sum, held at the input's scale, is brought to the result's
    scale, divided by the count and rounded HALF_UP (ties away from zero),
    all in 128 bits (sum x 10^4 passes int64 from 9.2e14 unscaled), and is
    null where the count is 0 or the quotient leaves the result precision.
    The streaming finalize and the whole-stage program both end here."""
    ok = cnt > 0
    if not call.dtype.is_decimal:
        v = s.data.astype(jnp.float64) / jnp.maximum(cnt, 1).astype(
            jnp.float64)
        return Column(T.FLOAT64, jnp.where(ok, v, 0.0), ok)
    from blaze_tpu.exprs import wide_decimal as W

    with jax.named_scope("finalize.decimal_avg"):
        h, l = W.planes(s)
        qh, ql, ok_div = W.div_by_count(
            h, l, cnt, call.dtype, call.dtype.scale - s.dtype.scale)
        return W.shape(call.dtype, qh, ql,
                       ok & ok_div & s.valid_mask()).normalized()


class _AggState:
    """Spillable aggregation state (ref AggTables + its MemConsumer impl,
    agg_tables.rs:57-278: in-mem tables spill to bucket-sorted runs merged
    on output). Memory relief here is (1) collapse raw rows into aggregated
    state (the sort-based analog of table insertion), then (2) spill state
    batches to host files; finish merges disk + memory hierarchically."""

    name = "agg"

    def __init__(self, op: "AggExec", manager) -> None:
        from blaze_tpu.runtime import memory as M

        self.op = op
        self.manager = manager
        self._M = M
        self.raw: List[ColumnBatch] = []
        self.raw_rows = 0
        self.raw_bytes = 0
        self.states: List[ColumnBatch] = []
        self.state_bytes = 0
        # True while self.states holds externally-produced state batches
        # (shuffle-read partial states): those may carry several rows per
        # group even in a single batch, so they are never "already
        # collapsed" — unlike batches produced by our own _collapse.
        self.states_external = False
        self.spills: List = []
        self.collapses = 0
        self.spill_files_used = 0
        manager.register(self)

    def mem_used(self) -> int:
        return self.raw_bytes + self.state_bytes

    def spill(self) -> int:
        freed = self._collapse_all()
        if freed:
            return freed
        # already collapsed: push state batches to a host spill file
        if not self.states:
            return 0
        freed = self.state_bytes
        sf = self._M.SpillFile(self.op._state_schema, manager=self.manager)
        for s in self.states:
            sf.write(truncate(s, max(pull_rows(s, "agg.state_rows"), 1)))
        self.spills.append(sf)
        self.spill_files_used += 1
        self.states, self.state_bytes = [], 0
        return freed

    def _collapse_all(self) -> int:
        freed = 0
        if self.raw:
            before = self.raw_bytes
            s = self.op._collapse(self.raw, raw_input=True)
            self.raw, self.raw_rows, self.raw_bytes = [], 0, 0
            self._push_state(s)
            freed += max(before - self._M.batch_nbytes(s), 0)
            self.collapses += 1
        if len(self.states) > 1 or (self.states_external and self.states):
            before = self.state_bytes
            s = self.op._collapse(self.states, raw_input=False)
            self.states, self.state_bytes = [], 0
            self._push_state(s)
            self.states_external = False
            freed += max(before - self.state_bytes, 0)
            self.collapses += 1
        return freed

    def _push_state(self, s: ColumnBatch) -> None:
        self.states.append(s)
        self.state_bytes += self._M.batch_nbytes(s)

    def add_raw(self, work: ColumnBatch, rows: Optional[int] = None) -> None:
        """`rows`: the batch's rows that count, where the caller has them on
        the host already (a carried filter's kept count)."""
        # op_lock: serialize against host-driven release() (bn_spill)
        with self.manager.op_lock:
            self.raw.append(work)
            self.raw_rows += (pull_rows(work, "agg.raw_rows")
                              if rows is None else rows)
            self.raw_bytes += self._M.batch_nbytes(work)
            if self.raw_rows >= self.op.collapse_threshold:
                self._collapse_all()
            self.manager.update_mem_used(self)

    def add_state(self, batch: ColumnBatch) -> None:
        with self.manager.op_lock:
            self._push_state(batch)
            self.states_external = True
            if len(self.states) >= 16:
                self._collapse_all()
            self.manager.update_mem_used(self)

    def merged(self) -> ColumnBatch:
        self._collapse_all()
        acc = self.states[0] if self.states else None
        for sf in self.spills:
            for chunk in sf.read():
                if acc is None:
                    acc = chunk
                else:
                    acc = self.op._collapse([acc, chunk], raw_input=False)
        assert acc is not None
        return acc

    def close(self) -> None:
        """Double-fault-safe: called from the stream's finally during
        unwinding; one failing spill close must not mask the query error
        or leak the remaining files (runtime.memory.close_all_quietly)."""
        self.manager.unregister(self)
        spills, self.spills = self.spills, []
        self._M.close_all_quietly(spills, "agg spill")


class AggExec(Operator):
    def __init__(self, child: Operator, group_exprs: Sequence[ir.Expr],
                 group_names: Sequence[str], aggs: Sequence[AggCall],
                 mode: AggMode,
                 collapse_threshold: Optional[int] = None) -> None:
        super().__init__([child])
        self.group_exprs = list(group_exprs)
        self.group_names = list(group_names)
        self.aggs = list(aggs)
        self.mode = mode
        self.collapse_threshold = collapse_threshold or (conf.batch_size * 16)
        self._build_schema()

    # ---- schema plumbing ----
    def _build_schema(self) -> None:
        child_schema = self.children[0].schema
        ngroups = len(self.group_exprs)
        if self.mode == AggMode.PARTIAL:
            self._group_fns = [compile_expr(e, child_schema)
                               for e in self.group_exprs]
            self._input_fns = [[compile_expr(e, child_schema)
                                for e in call.inputs] for call in self.aggs]
            self._work_jit = not any(
                ir.contains_host_fn(e) for e in list(self.group_exprs) +
                [x for call in self.aggs for x in call.inputs])
            probe = ColumnBatch.empty(child_schema, bucket_capacity(0))
            gcols = [jax.eval_shape(fn, probe) for fn in self._group_fns]
            for call, fns in zip(self.aggs, self._input_fns):
                if call.fn in ("sum", "avg"):
                    check_decimal_call(
                        call, jax.eval_shape(fns[0], probe).dtype)
            group_fields = [Field(n, c.dtype)
                            for n, c in zip(self.group_names, gcols)]
        else:
            # input is group cols + state cols by position
            group_fields = [Field(n, child_schema.fields[i].dtype)
                            for i, n in enumerate(self.group_names)]
        state: List[Field] = []
        for i, call in enumerate(self.aggs):
            state.extend(state_fields(call, i))
        if self.mode != AggMode.PARTIAL:
            # the state arrives typed, by position: a decimal sum under
            # another type than this plan derives (cents under a scale-6
            # label) is refused here, not divided wrong later
            for want, got in zip(state, child_schema.fields[ngroups:]):
                if want.dtype != got.dtype and (want.dtype.is_decimal
                                                or got.dtype.is_decimal):
                    raise TypeError(
                        f"agg state {got.name} arrives as {got.dtype}; "
                        f"this plan's state is {want.dtype}")
        self._group_fields = group_fields
        self._state_fields = state
        if self.mode == AggMode.FINAL:
            out = group_fields + [result_field(c) for c in self.aggs]
        else:
            out = group_fields + state
        self._schema = Schema(out)
        self._state_schema = Schema(group_fields + state)

    @property
    def schema(self) -> Schema:
        return self._schema

    def plan_key(self) -> tuple:
        return ("agg", self.mode.value,
                tuple(e.key() for e in self.group_exprs),
                tuple(c.key() for c in self.aggs),
                self.children[0].plan_key())

    # ---- execution ----
    def execute(self, ctx: ExecContext) -> BatchStream:
        def gen():
            from blaze_tpu.runtime import memory as M

            manager = M.get_manager(ctx)
            state = _AggState(self, manager)
            seen = False
            filt = self._mask_filter()
            note = batch_tap(self)  # fed here: the output's rows are pulled here
            try:
                # (batch, None), or (work batch, rows kept) where the
                # child is a filter whose mask the collapse carries
                inputs = (self._masked_work(filt, ctx) if filt is not None
                          else ((b, None)
                                for b in self.children[0].execute(ctx)))
                for batch, kept in inputs:
                    ctx.check_running()
                    if (pull_rows(batch, "agg.input_rows") if kept is None
                            else kept) == 0:
                        continue
                    seen = True
                    with self.metrics.timer():
                        if self._is_state_input():
                            state.add_state(batch)
                        elif kept is None:
                            state.add_raw(self._to_work(batch))
                        else:
                            state.add_raw(batch, kept)
                if not seen:
                    if not self.group_exprs:
                        out = self._empty_global_result()
                        note(out)
                        yield out
                    return
                with self.metrics.timer():
                    merged = state.merged()
                    if self.mode == AggMode.FINAL:
                        out = self._finalize_jit(merged)
                    else:
                        out = merged
                self.metrics.add("collapses", state.collapses)
                self.metrics.add("spill_count", state.spill_files_used)
                rows = pull_rows(out, "agg.out_rows")
                out = truncate(out, max(rows, 1))
                note(out, rows)
                yield out
            finally:
                state.close()

        return gen()

    def _mask_filter(self):
        """The FilterExec whose mask this aggregate carries into its
        collapse instead of reading the filter's compacted batches, or None:
        a PARTIAL aggregate fed directly by a filter that stays on the
        device. A compaction never shrinks a capacity, and the collapse
        sorts whatever it is handed with the dead slots last, so at every
        selectivity the filter's gathers bought the collapse nothing."""
        child = self.children[0]
        if (self.mode == AggMode.PARTIAL and isinstance(child, FilterExec)
                and child.jit_safe()):
            return child
        return None

    def _masked_work(self, filt, ctx: ExecContext):
        """The absorbed filter's input, batch by batch, as (work batch with
        the filter's keep plane, rows it kept). The filter runs no program
        of its own, so its output boundary is kept here: counters, trace,
        history, progress and fault point see the kept count, which is
        pulled where `add_raw` would have pulled the work batch's rows."""
        note_filter = batch_tap(filt)
        for batch in filt.child.execute(ctx):
            with self.metrics.timer():
                work, kept = self._to_work(batch, filt)
                compile_service.note_filter_batches(carried=1)
                kept = int(pull_array(kept, "agg.kept_rows"))
            note_filter(batch, kept)
            yield work, kept

    def _to_work(self, batch: ColumnBatch, filt=None):
        """Project child rows into the (group cols + per-agg inputs | state)
        working layout. With `filt` (`_mask_filter`) the batch is the
        filter's INPUT: the same program evaluates the filter's predicates
        and the work batch takes their verdict as one more plane, KEEP_PLANE,
        its `num_rows` still the physical rows; returns (work, kept count)."""
        if self.mode != AggMode.PARTIAL:
            return batch  # already group+state layout
        key = ("agg_work", self._work_jit, self.plan_key(),
               batch.shape_key())
        return jit_cache.get_or_compile(key, lambda: self._work_fn(filt),
                                        jit=self._work_jit)(batch)

    def _work_fn(self, filt=None):
        """The program of `_to_work`, to be jitted."""
        from blaze_tpu.exprs.compiler import cse_scope

        gfns, ifns = self._group_fns, self._input_fns
        keep_of = None if filt is None else filt.make_keep_fn()

        def run(b: ColumnBatch):
            with cse_scope():
                cols = [fn(b) for fn in gfns]
                fields = list(self._group_fields)
                for call, fns in zip(self.aggs, ifns):
                    for j, fn in enumerate(fns):
                        c = fn(b)
                        cols.append(c)
                        fields.append(Field(f"in.{call.name}.{j}", c.dtype))
            if keep_of is None:
                return b.with_columns(Schema(fields), cols)
            with cse_scope(), jax.named_scope(filt.label()):
                keep = keep_of(b)
            cols.append(Column(T.BOOLEAN, keep, None))
            fields.append(Field(KEEP_PLANE, T.BOOLEAN, nullable=False))
            return (b.with_columns(Schema(fields), cols),
                    jnp.sum(keep & b.row_mask(), dtype=jnp.int32))

        return run

    def _collapse(self, batches: List[ColumnBatch], raw_input: bool
                  ) -> ColumnBatch:
        big = batches[0] if len(batches) == 1 else concat_batches(batches)
        big = compile_service.canonical_batch(big, "agg_collapse")
        key = ("agg_collapse", raw_input, self.plan_key(), big.shape_key())
        # raw work batches of a carried filter end in its keep plane
        masked = raw_input and self._mask_filter() is not None

        def make():
            def run(b: ColumnBatch) -> ColumnBatch:
                ngroups = len(self._group_fields)
                specs = [SortSpec(i) for i in range(ngroups)]
                live = None
                if masked:
                    # the rows the filter dropped are dead slots to the
                    # sort, as padding is: it sends them last, and the
                    # keep plane itself is not permuted
                    live = b.row_mask() & b.columns[-1].data
                    b = b.select(range(len(b.columns) - 1))
                # the phases of the collapse, as named scopes (the sort
                # brings its own: sort.encode_keys / sort / permute)
                with jax.named_scope("collapse.sort"):
                    sb = sort_batch(b, specs, live=live)
                with jax.named_scope("collapse.group_layout"):
                    layout = seg.group_layout(sb, list(range(ngroups)))
                with jax.named_scope("collapse.group_keys"):
                    gcols = [seg.group_first_rows(sb.columns[i], layout)
                             for i in range(ngroups)]
                with seg.count_forms() as forms:
                    if raw_input:
                        with jax.named_scope("collapse.accumulate_raw"):
                            scols = self._accumulate_raw(sb, layout, ngroups)
                    else:
                        with jax.named_scope("collapse.merge_state"):
                            scols = self._merge_state(sb, layout, ngroups)
                # traced once a program; _collapse adds it at every dispatch
                _SEG_FORMS[key] = dict(forms)
                return ColumnBatch(self._state_schema, gcols + scols,
                                   layout.num_groups, sb.capacity)

            return run

        out = jit_cache.get_or_compile(key, make)(big)
        compile_service.note_seg_reductions(**_SEG_FORMS.get(key, {}))
        return out

    def _is_state_input(self) -> bool:
        return self.mode in (AggMode.PARTIAL_MERGE, AggMode.FINAL)

    def _accumulate_raw(self, sb: ColumnBatch, layout, ngroups: int
                        ) -> List[Column]:
        """Partial: raw input columns -> state columns via segmented ops."""
        out: List[Column] = []
        ci = ngroups
        counted: dict = {}
        for call in self.aggs:
            ins = sb.columns[ci:ci + len(call.inputs)]
            ci += len(call.inputs)
            with jax.named_scope(call.fn):
                out.extend(self._acc_one(call, ins, layout, counted))
        return out

    def _acc_one(self, call: AggCall, ins: List[Column], layout,
                 counted: dict) -> List[Column]:
        def count(valid):
            # sum's non-empty flag, count(x) and avg's count over one input
            # expression count one mask: once per _accumulate_raw
            over = tuple(e.key() for e in call.inputs)
            if over not in counted:
                counted[over] = seg.seg_count(valid, layout)
            return counted[over]

        fn = call.fn
        if fn == "count":
            valid = None
            for c in ins:
                v = c.valid_mask()
                valid = v if valid is None else (valid & v)
            return [Column(T.INT64, count(valid), None)]
        (x,) = ins
        valid = x.valid_mask()
        if fn == "sum":
            sd = _sum_state_dtype(call.dtype)
            if sd.wide_decimal:
                from blaze_tpu.exprs import wide_decimal as W

                live = valid & layout.row_mask
                # the sum keeps its input's scale (check_decimal_call)
                h, l = W.planes(x)
                sh, sl, ok = W.seg_sum_wide(h, l, live, layout, seg)
                nonempty = count(valid) > 0
                return [W.build(sd, sh, sl, ok),
                        Column(T.BOOLEAN, nonempty, None)]
            data = x.data.astype(sd.jnp_dtype())
            s = seg.seg_sum(jnp.where(valid, data, 0), layout, valid)
            nonempty = count(valid) > 0
            return [Column(sd, s, None), Column(T.BOOLEAN, nonempty, None)]
        if fn == "avg":
            # a decimal avg sums at its input's scale under the SUM's type
            # (Spark's buffer, decimal(p+10, s)); finalize_avg rescales
            sd = avg_sum_dtype(call.dtype)
            cnt = count(valid)
            if sd.wide_decimal:
                from blaze_tpu.exprs import wide_decimal as W

                live = valid & layout.row_mask
                h, l = W.planes(x)
                sh, sl, ok = W.seg_sum_wide(h, l, live, layout, seg)
                return [W.build(sd, sh, sl, ok),
                        Column(T.INT64, cnt, None)]
            data = x.data.astype(sd.jnp_dtype())
            s = seg.seg_sum(jnp.where(valid, data, 0), layout, valid)
            return [Column(sd, s, None), Column(T.INT64, cnt, None)]
        if fn in ("min", "max"):
            red = seg.seg_min if fn == "min" else seg.seg_max
            if x.is_string:
                return self._minmax_string(call, x, layout, fn)
            if call.dtype.wide_decimal:
                from blaze_tpu.exprs import wide_decimal as W

                h, l = W.planes(x)
                mh, ml, has = W.seg_minmax_wide(
                    h, l, valid & layout.row_mask, layout, seg,
                    fn == "min")
                return [W.build(call.dtype, mh, ml, None),
                        Column(T.BOOLEAN, has, None)]
            val, has = red(x.data, layout, valid)
            return [Column(call.dtype, val, None),
                    Column(T.BOOLEAN, has, None)]
        if fn == "first":
            idx = jnp.clip(layout.start_idx, 0, x.capacity - 1)
            picked = x.take(idx)
            fvalid = (valid & layout.row_mask)[idx]
            has = layout.group_mask
            return [Column(call.dtype, picked.data, None),
                    Column(T.BOOLEAN, fvalid, None),
                    Column(T.BOOLEAN, has, None)]
        if fn == "first_ignores_null":
            if x.is_string:
                (vcol,), ok = _first_by_index([x], layout, valid)
                return [Column(call.dtype, vcol.data, None),
                        Column(T.BOOLEAN, ok, None)]
            val, has = seg.seg_first(x.data, layout, valid, ignores_null=True)
            return [Column(call.dtype, val, None),
                    Column(T.BOOLEAN, has, None)]
        if fn in ("collect_list", "collect_set"):
            return self._collect_raw(call, x, layout,
                                     dedup=(fn == "collect_set"))
        raise NotImplementedError(f"agg function {fn}")

    # ---- collect_list / collect_set (ref agg/collect_list.rs,
    # collect_set.rs — there per-group Vec/HashSet accumulators; here the
    # state is a ListData column whose group slices are built by segmented
    # counting + stable compaction over the group-sorted rows) ----

    def _list_dtype(self, call: AggCall) -> DataType:
        return collect_state_dtype(call)

    def _collect_raw(self, call: AggCall, x: Column, layout,
                     dedup: bool) -> List[Column]:
        from blaze_tpu.columnar.batch import ListData

        valid = x.valid_mask() & layout.row_mask  # spark: nulls are dropped
        keep = valid
        if dedup:
            gid_key = jnp.where(valid, layout.gid, jnp.int32(2 ** 30))
            keep = keep & _first_occurrence(x, gid_key)
        lens = seg.seg_sum(keep, layout, keep)
        goff = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(lens, dtype=jnp.int32)])
        # kept rows to the front, original (group-sorted) order preserved
        order = jnp.argsort(~keep, stable=True).astype(jnp.int32)
        elems = x.take(order)
        dt = self._list_dtype(call)
        return [Column(dt, ListData(goff, Column(dt.element, elems.data,
                                                 None)), None)]

    def _collect_merge(self, call: AggCall, lcol: Column, layout,
                       dedup: bool) -> List[Column]:
        from blaze_tpu.columnar.batch import ListData

        dt = self._list_dtype(call)
        ld = lcol.data
        cap = layout.row_mask.shape[0]
        ecap = ld.elements.capacity
        lens_r = jnp.where(layout.row_mask & lcol.valid_mask(),
                           ld.lengths(), 0).astype(jnp.int32)
        cum = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(lens_r, dtype=jnp.int32)])
        # explode rows (already gid-sorted) into one element stream
        _, row, within, live = seg.element_rows(cum, cap, ecap)
        src = jnp.clip(ld.offsets[row] + within, 0, ecap - 1)
        elems = ld.elements.take(jnp.where(live, src, 0))
        elems = Column(dt.element, elems.data, None)
        egid = jnp.where(live, layout.gid[row], jnp.int32(2 ** 30))
        if dedup:
            keep = live & _first_occurrence(elems, egid)
            order = jnp.argsort(~keep, stable=True).astype(jnp.int32)
            elems = Column(dt.element, elems.take(order).data, None)
            glens = jnp.zeros((cap,), jnp.int32).at[egid].add(
                keep.astype(jnp.int32), mode="drop")
        else:
            glens = seg.seg_sum(lens_r, layout, jnp.ones((cap,), jnp.bool_))
        goff = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                jnp.cumsum(glens, dtype=jnp.int32)])
        return [Column(dt, ListData(goff, elems), None)]

    def _minmax_string(self, call, x: Column, layout, fn: str) -> List[Column]:
        """String min/max: sort rows by (gid, encoded string) and pick each
        group's first row. Invalid/null strings are encoded to sort last in
        every direction, so each group's run keeps a row for every gid and
        compacted starts stay aligned with the group slots."""
        from blaze_tpu.ops.sort_keys import string_words

        cap = x.capacity
        valid = x.valid_mask() & layout.row_mask
        words = string_words(x.data)
        umax64 = jnp.uint64(0xFFFFFFFFFFFFFFFF)
        umax32 = jnp.uint32(0xFFFFFFFF)
        enc_words = [jnp.where(valid, w if fn == "min" else ~w, umax64)
                     for w in words]
        lkey = x.data.lengths.view(jnp.uint32)
        enc_len = jnp.where(valid, lkey if fn == "min" else ~lkey, umax32)
        # padding rows last (gid is garbage there)
        gid_key = jnp.where(layout.row_mask, layout.gid, jnp.int32(2**30))
        iota = jnp.arange(cap, dtype=jnp.int32)
        ops = (gid_key,) + tuple(enc_words) + (enc_len, iota)
        sorted_ops = jax.lax.sort(ops, num_keys=len(ops) - 1, is_stable=True)
        perm, sgid = sorted_ops[-1], sorted_ops[0]
        starts = jnp.concatenate([
            jnp.ones((1,), jnp.bool_), sgid[1:] != sgid[:-1]])
        gstart = nonzero_i32(starts & (sgid < 2**30), cap)
        row_idx = perm[jnp.clip(gstart, 0, cap - 1)]
        picked = x.take(jnp.clip(row_idx, 0, cap - 1))
        has = _seg_any(x.valid_mask() & layout.row_mask, layout)
        return [Column(call.dtype, picked.data, None),
                Column(T.BOOLEAN, has, None)]

    def _merge_state(self, sb: ColumnBatch, layout, ngroups: int
                     ) -> List[Column]:
        out: List[Column] = []
        ci = ngroups
        for call in self.aggs:
            nstate = len(state_fields(call, 0))
            cols = sb.columns[ci:ci + nstate]
            ci += nstate
            fn = call.fn
            ones = jnp.ones((sb.capacity,), jnp.bool_)
            if fn == "count":
                cnt = seg.seg_sum(cols[0].data, layout, ones)
                out.append(Column(T.INT64, cnt, None))
            elif fn == "sum":
                if cols[0].dtype.wide_decimal:
                    out += self._merge_sum_wide(cols, layout, ones)
                    continue
                s = seg.seg_sum(jnp.where(cols[1].data, cols[0].data, 0),
                                layout, ones)
                ne = _seg_any(cols[1].data, layout)
                out += [Column(cols[0].dtype, s, None),
                        Column(T.BOOLEAN, ne, None)]
            elif fn == "avg":
                if cols[0].dtype.wide_decimal:
                    scol, _ = self._merge_sum_wide(
                        [cols[0], Column(T.BOOLEAN,
                                         jnp.ones((sb.capacity,),
                                                  jnp.bool_), None)],
                        layout, ones)
                    cnt = seg.seg_sum(cols[1].data, layout, ones)
                    out += [scol, Column(T.INT64, cnt, None)]
                    continue
                s = seg.seg_sum(cols[0].data, layout, ones)
                cnt = seg.seg_sum(cols[1].data, layout, ones)
                out += [Column(cols[0].dtype, s, None),
                        Column(T.INT64, cnt, None)]
            elif fn in ("min", "max"):
                if cols[0].is_string:
                    masked = Column(cols[0].dtype, cols[0].data,
                                    cols[1].data)
                    out.extend(self._minmax_string(call, masked, layout, fn))
                elif cols[0].dtype.wide_decimal:
                    from blaze_tpu.exprs import wide_decimal as W

                    h, l = W.planes(cols[0])
                    mh, ml, has = W.seg_minmax_wide(
                        h, l, cols[1].data & layout.row_mask, layout, seg,
                        fn == "min")
                    out += [W.build(cols[0].dtype, mh, ml, None),
                            Column(T.BOOLEAN, has, None)]
                else:
                    red = seg.seg_min if fn == "min" else seg.seg_max
                    val, has = red(cols[0].data, layout, cols[1].data)
                    out += [Column(cols[0].dtype, val, None),
                            Column(T.BOOLEAN, has, None)]
            elif fn == "first":
                (v, vv), ok = _first_by_index([cols[0], cols[1]], layout,
                                              cols[2].data)
                out += [Column(cols[0].dtype, v.data, None),
                        Column(T.BOOLEAN, vv.data, None),
                        Column(T.BOOLEAN, ok, None)]
            elif fn == "first_ignores_null":
                (v,), ok = _first_by_index([cols[0]], layout, cols[1].data)
                out += [Column(cols[0].dtype, v.data, None),
                        Column(T.BOOLEAN, ok, None)]
            elif fn in ("collect_list", "collect_set"):
                out.extend(self._collect_merge(call, cols[0], layout,
                                               dedup=(fn == "collect_set")))
            else:
                raise NotImplementedError(fn)
        return out

    def _merge_sum_wide(self, cols, layout, ones):
        """Re-sum wide-decimal partial sums (limb planes); empty partials
        contribute nothing, an overflowed contributing partial poisons
        its group (validity False -> null result)."""
        from blaze_tpu.exprs import wide_decimal as W

        state, ne_col = cols[0], cols[1]
        ne = ne_col.data & layout.row_mask
        h, l = W.planes(state)
        h = jnp.where(ne, h, jnp.int64(0))
        l = jnp.where(ne, l, jnp.int64(0))
        sh, sl, ok = W.seg_sum_wide(h, l, ne, layout, seg)
        ok_in = state.valid_mask() | ~ne
        group_ok = ~_seg_any(~ok_in, layout)
        ne_out = _seg_any(ne, layout)
        return [W.build(state.dtype, sh, sl, ok & group_ok),
                Column(T.BOOLEAN, ne_out, None)]

    # ---- finalize ----
    def _finalize_jit(self, state: ColumnBatch) -> ColumnBatch:
        key = ("agg_final", self.plan_key(), state.shape_key())

        def make():
            def run(b: ColumnBatch) -> ColumnBatch:
                ngroups = len(self._group_fields)
                cols = list(b.columns[:ngroups])
                ci = ngroups
                for call in self.aggs:
                    nstate = len(state_fields(call, 0))
                    scols = b.columns[ci:ci + nstate]
                    ci += nstate
                    cols.append(self._finalize_one(call, scols))
                return b.with_columns(self._schema, cols)

            return run

        return jit_cache.get_or_compile(key, make)(state)

    def _finalize_one(self, call: AggCall, scols: List[Column]) -> Column:
        fn = call.fn
        if fn == "count":
            return scols[0]
        if fn == "sum":
            return finalize_sum(call, scols[0], scols[1].data)
        if fn == "avg":
            return finalize_avg(call, scols[0], scols[1].data)
        if fn in ("min", "max", "first_ignores_null"):
            return Column(call.dtype, scols[0].data, scols[1].data)
        if fn == "first":
            return Column(call.dtype, scols[0].data,
                          scols[1].data & scols[2].data)
        if fn in ("collect_list", "collect_set"):
            # spark: groups with no collected values get an EMPTY array,
            # not null
            return scols[0]
        raise NotImplementedError(fn)

    def _empty_global_result(self) -> ColumnBatch:
        """Global agg over zero rows: one row of initial state (count=0,
        sum=null, ...) — matches Spark's global-agg-on-empty semantics."""
        cap = bucket_capacity(1)
        state = ColumnBatch.empty(self._state_schema, cap).with_num_rows(1)
        state = ColumnBatch(self._state_schema,
                            [c.normalized() for c in state.columns],
                            state.num_rows, cap)
        if self.mode == AggMode.FINAL:
            return self._finalize_jit(state)
        return state
