"""SortExec / TakeOrderedExec — sort-based pipeline breakers.

Ref: datafusion-ext-plans sort_exec.rs (external merge-sort with loser-tree
spill merge, optional fetch limit) and take_ordered_exec (NativeTakeOrdered).
TPU-first redesign: in-memory runs are concatenated and sorted by ONE
variadic `lax.sort` program per shape bucket (no pairwise merge levels —
XLA's sort is the merge network); the fetch-limited path keeps a bounded
top-k state folded over the stream so unbounded inputs never materialize.
Host spill of sorted runs plugs in at the runtime.memory layer.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax.numpy as jnp

from blaze_tpu.columnar.batch import (
    ColumnBatch, bucket_capacity, pull_array, pull_rows,
)
from blaze_tpu.columnar.types import Schema
from blaze_tpu.config import conf
from blaze_tpu.ops.base import BatchStream, ExecContext, Operator, count_stream
from blaze_tpu.ops.common import concat_batches
from blaze_tpu.ops.sort_keys import SortSpec, sort_batch
from blaze_tpu.runtime import compile_service, jit_cache


def sorted_batch_jit(batch: ColumnBatch, specs: Sequence[SortSpec],
                     plan_key: tuple = ()) -> ColumnBatch:
    """Jit-cached whole-batch sort. The cache key deliberately omits the
    plan: the kernel depends only on specs + batch layout, so identical
    sorts across different plans share one compilation — and the shape is
    host-reconstructible, so the compile service records a replay payload
    for manifest-driven pre-warming."""
    batch = compile_service.canonical_batch(batch, "sort_kernel")
    key = ("sort_kernel", tuple(s.key() for s in specs), batch.shape_key())
    compile_service.record_sort_shape(key, batch, specs)
    fn = jit_cache.get_or_compile(
        key, lambda: (lambda b: sort_batch(b, specs)))
    return fn(batch)


def truncate(batch: ColumnBatch, limit: int) -> ColumnBatch:
    """Keep the first `limit` live rows (batch must be front-compact)."""
    cap = bucket_capacity(limit)
    if cap >= batch.capacity:
        return batch.with_num_rows(jnp.minimum(batch.num_rows, limit))
    cols = []
    from blaze_tpu.columnar.batch import Column, StringData

    iota = jnp.arange(cap, dtype=jnp.int32)
    for c in batch.columns:
        if c.is_string:
            data = StringData(c.data.bytes[:cap], c.data.lengths[:cap])
        elif c.is_list:
            from blaze_tpu.columnar.batch import ListData

            data = ListData(c.data.offsets[:cap + 1], c.data.elements)
        elif c.is_struct:
            cols.append(c.take(iota))
            continue
        else:
            data = c.data[:cap]
        v = c.validity[:cap] if c.validity is not None else None
        cols.append(Column(c.dtype, data, v))
    n = jnp.minimum(batch.num_rows, limit)
    return ColumnBatch(batch.schema, cols, n, cap)


class ExternalSorter:
    """Budgeted sort state: in-memory batches spill as sorted runs; the
    finish phase k-way merges runs with a bounded pool.

    Ref: sort_exec.rs — in-mem SortedBatches merged into levels, spills
    merged by a LoserTree over cursors (:307-475). TPU shape: a run is a
    sequence of sorted zstd frames in a SpillFile; the merge pools the
    front batch of the run with the smallest head key, emits every pooled
    row that is <= the smallest head key among the other runs (lexicographic
    compare on the encoded sort keys, device-side), and carries the rest.
    """

    def __init__(self, schema: Schema, specs: Sequence[SortSpec],
                 manager=None, name: str = "sort") -> None:
        from blaze_tpu.runtime import memory as M

        self.schema = schema
        self.specs = list(specs)
        self.manager = manager or M.get_manager()
        self.name = name
        self.pending: List[ColumnBatch] = []
        self.pending_bytes = 0
        self.runs: List = []
        # counters survive abort() — metrics read them after cleanup
        self.spill_count = 0
        self.spilled_bytes = 0
        self._M = M
        self.manager.register(self)

    # MemConsumer protocol
    def mem_used(self) -> int:
        return self.pending_bytes

    def spill(self) -> int:
        if not self.pending:
            return 0
        freed = self.pending_bytes
        run = self._M.SpillFile(self.schema, manager=self.manager)
        big = concat_batches(self.pending, self.schema)
        sb = sorted_batch_jit(big, self.specs)
        # frame granularity bounds the merge's iteration count (one
        # concat+sort+split dispatch trio per pooled frame, each costing
        # fixed per-dispatch overhead). Merge throughput on the CPU mesh
        # was k-INVARIANT (k=8 vs k=64), so the O(k) head-min scan the
        # reference's LoserTree would replace is not the cost driver;
        # iteration overhead is.
        # The frame is CLAMPED against the memory budget: the merge holds
        # one head frame per run (plus pool/carry) un-budgeted, so frames
        # sized ~budget/8 keep the merge's working set inside the budget
        # class that forced spilling in the first place.
        cap = max(int(big.capacity), 1)
        row_bytes = max(self._M.batch_nbytes(big) // cap, 1)
        budget_rows = max(self.manager.total // (8 * row_bytes), 1024)
        frame = int(min(int(conf.spill_frame_rows), budget_rows))
        for lo in range(0, max(pull_rows(sb, "sort.run_rows"), 1), frame):
            from blaze_tpu.ops.common import slice_batch

            chunk = slice_batch(sb, lo, frame)
            if pull_rows(chunk, "sort.run_rows") == 0:
                break
            run.write(chunk)
        self.runs.append(run)
        self.spill_count += 1
        self.spilled_bytes += run.bytes_written
        self.pending, self.pending_bytes = [], 0
        return freed

    def add(self, batch: ColumnBatch) -> None:
        # op_lock: a host-driven release() (bn_spill) must not run
        # spill() between the append and the accounting update
        with self.manager.op_lock:
            self.pending.append(batch)
            self.pending_bytes += self._M.batch_nbytes(batch)
            self.manager.update_mem_used(self)

    def finish(self):
        try:
            if not self.runs:
                if not self.pending:
                    return
                big = concat_batches(self.pending, self.schema)
                yield sorted_batch_jit(big, self.specs)
                return
            if self.pending:
                self.spill()
            yield from self._merge_runs()
        finally:
            self.abort()

    def abort(self) -> None:
        """Idempotent cleanup (also the error path: SortExec wraps its
        stream in try/finally so a cancelled query never leaks the
        MemManager registration or spill files).

        Double-fault contract (ref §5.3 failure detection): cleanup runs
        during exception unwinding, so a failing close must neither mask
        the original query error nor stop later runs from closing."""
        self.manager.unregister(self)
        self.pending, self.pending_bytes = [], 0
        runs, self.runs = self.runs, []
        self._M.close_all_quietly(runs, "sort spill-run")

    # -- k-way merge of sorted runs --
    # Spilled runs are HOST-resident (zstd frames in spill files), so the
    # merge happens on the host in numpy with memcmp row keys
    # (ops/host_sort.py — the reference's LoserTree-over-spill-cursors
    # role, loser_tree.rs:1-118 / sort_exec.rs:419-475) and uploads each
    # merged macro-batch once. A device-dispatch merge pays a host round
    # trip per pooled frame; the host merge is dispatch-free. Schemas
    # with list storage keep the device merge.
    def _head_key(self, batch: ColumnBatch, row: int) -> tuple:
        from blaze_tpu.ops.sort_keys import batch_sort_keys

        keys = batch_sort_keys(batch, self.specs)
        return tuple(int(pull_array(k[row], "sort.head_key")) for k in keys)

    def _split_leq(self, pool: ColumnBatch, bound: tuple):
        import jax.numpy as jnp

        from blaze_tpu.ops.sort_keys import batch_sort_keys

        keys = batch_sort_keys(pool, self.specs)
        le = jnp.zeros((pool.capacity,), jnp.bool_)
        eq = jnp.ones((pool.capacity,), jnp.bool_)
        for karr, bval in zip(keys, bound):
            b = jnp.asarray(bval, karr.dtype)
            le = le | (eq & (karr < b))
            eq = eq & (karr == b)
        mask = (le | eq) & pool.row_mask()
        return pool.compact(mask), pool.compact(~mask)

    def _merge_runs(self):
        from blaze_tpu.ops import host_sort

        if host_sort.host_supported(self.schema):
            # merged macro-batches go back to DEVICE memory downstream:
            # size them inside the budget class that forced the spill
            emit = int(max(self.manager.total // 4, 1 << 20))
            iters = [r.read_host() for r in self.runs]
            for hb in host_sort.merge_sorted_host(iters, self.specs, emit):
                yield host_sort.host_to_device(hb)
            return
        yield from self._merge_runs_device()

    def _merge_runs_device(self):
        streams = [iter(r.read()) for r in self.runs]

        def pull(i):
            """Next batch of run i with its head key computed ONCE (the
            encoded keys of a loaded batch never change across merge
            iterations, so recomputing per loop would be pure waste)."""
            b = next(streams[i], None)
            return None if b is None else (b, self._head_key(b, 0))

        current = [pull(i) for i in range(len(streams))]
        carry: Optional[ColumnBatch] = None
        while True:
            active = [i for i, c in enumerate(current) if c is not None]
            if not active:
                if (carry is not None and
                        pull_rows(carry, "sort.merge_rows") > 0):
                    yield carry
                return
            i_min = min(active, key=lambda i: current[i][1])
            head_batch = current[i_min][0]
            parts = ([carry] if carry is not None and
                     pull_rows(carry, "sort.merge_rows") > 0 else [])
            parts.append(head_batch)
            pool = (parts[0] if len(parts) == 1 else
                    concat_batches(parts, self.schema))
            pool = sorted_batch_jit(pool, self.specs)
            current[i_min] = pull(i_min)
            others = [i for i in active if i != i_min]
            if not others and current[i_min] is None:
                if pull_rows(pool, "sort.merge_rows") > 0:
                    yield pool
                carry = None
                continue
            bounds = [current[i][1] for i in others]
            if current[i_min] is not None:
                bounds.append(current[i_min][1])
            bound = min(bounds)
            emit, carry = self._split_leq(pool, bound)
            if pull_rows(emit, "sort.merge_rows") > 0:
                yield emit


class SortExec(Operator):
    """Full sort (optionally fetch-limited top-k), external when the
    memory budget forces spilling."""

    def __init__(self, child: Operator, specs: Sequence[SortSpec],
                 fetch: Optional[int] = None) -> None:
        super().__init__([child])
        self.specs = list(specs)
        self.fetch = fetch

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def plan_key(self) -> tuple:
        return ("sort", tuple(s.key() for s in self.specs), self.fetch,
            self.children[0].plan_key())

    def execute(self, ctx: ExecContext) -> BatchStream:
        def gen():
            child = self.children[0]
            if self.fetch is not None:
                out = self._topk(child.execute(ctx), ctx)
                if out is not None:
                    yield out
                return
            from blaze_tpu.runtime import memory as M

            sorter = ExternalSorter(self.schema, self.specs,
                                    M.get_manager(ctx))
            try:
                for batch in child.execute(ctx):
                    ctx.check_running()
                    if pull_rows(batch, "sort.input_rows"):
                        with self.metrics.timer():
                            sorter.add(batch)
                with self.metrics.timer():
                    yield from sorter.finish()
                # counters (not the runs list) — abort() empties the list
                self.metrics.add("spill_count", sorter.spill_count)
                self.metrics.add("spilled_bytes", sorter.spilled_bytes)
            finally:
                sorter.abort()

        return count_stream(self, gen())

    def _topk(self, stream: BatchStream, ctx: ExecContext
              ) -> Optional[ColumnBatch]:
        """Fold a bounded top-k over the stream (ref sort_exec.rs fetch)."""
        state: Optional[ColumnBatch] = None
        for batch in stream:
            ctx.check_running()
            with self.metrics.timer():
                part = truncate(
                    sorted_batch_jit(batch, self.specs, self.plan_key()),
                    self.fetch)
                if state is None:
                    state = part
                else:
                    both = concat_batches([state, part], self.schema)
                    state = truncate(
                        sorted_batch_jit(both, self.specs, self.plan_key()),
                        self.fetch)
        return state


class TakeOrderedExec(SortExec):
    """Ref: NativeTakeOrderedBase — limit + sort in one node."""

    def __init__(self, child: Operator, specs: Sequence[SortSpec],
                 limit: int) -> None:
        super().__init__(child, specs, fetch=limit)

    def plan_key(self) -> tuple:
        return ("take_ordered", tuple(s.key() for s in self.specs),
                self.fetch, self.children[0].plan_key())
