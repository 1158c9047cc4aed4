"""Operator protocol + execution context.

Ref: DataFusion's ExecutionPlan trait as used by every operator in
datafusion-ext-plans, and the per-task runtime in blaze/src/rt.rs. The
streaming model carries over (operators yield batches, bounded memory); the
TPU twist is the *fused pipeline*: consecutive map-like operators (filter/
project/rename/...) expose a pure `batch_fn` and the executor composes them
into ONE jit-compiled program per shape bucket, so a scan->filter->project
chain is a single XLA executable instead of three interpreted operators.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, List, Optional

from blaze_tpu.columnar.batch import ColumnBatch, pull_rows
from blaze_tpu.columnar.types import Schema
from blaze_tpu.runtime.metrics import MetricsSet

BatchStream = Iterator[ColumnBatch]


@dataclasses.dataclass
class ExecContext:
    """Per-task context (ref: TaskContext + SessionContext in exec.rs)."""

    partition: int = 0
    num_partitions: int = 1
    batch_size: Optional[int] = None
    # populated by runtime.memory when spilling is enabled
    mem_manager: Optional[object] = None
    # task-kill cooperation (ref JniBridge.isTaskRunning polling). The
    # supervisor wires each TaskAttempt's flag check here — every
    # check_running() call at a batch boundary doubles as the attempt's
    # HEARTBEAT (proof of cooperative liveness for hang detection).
    is_running: Callable[[], bool] = lambda: True
    # first-commit-wins gate shared by an attempt and its speculative
    # twin (runtime/supervisor.CommitGate); file-publishing operators
    # (the shuffle writer) claim it before os.replace so racing attempts
    # can never double-commit. None = uncontended (no speculation).
    commit_gate: Optional[object] = None

    def check_running(self) -> None:
        if not self.is_running():
            raise TaskKilledError("task killed")


class TaskKilledError(RuntimeError):
    pass


class SpeculationLostError(TaskKilledError):
    """This attempt lost the first-commit-wins race to its speculative
    twin. A TaskKilledError subclass: classified "killed", never retried,
    never counted as an engine error — the winner already produced the
    task's output."""


class Operator:
    """Base physical operator."""

    def __init__(self, children: List["Operator"]) -> None:
        self.children = children
        self.metrics = MetricsSet()

    @property
    def schema(self) -> Schema:
        raise NotImplementedError

    def execute(self, ctx: ExecContext) -> BatchStream:
        raise NotImplementedError

    # plan-structure key for the jit cache (must be stable across tasks)
    def plan_key(self) -> tuple:
        return (type(self).__name__,) + tuple(c.plan_key() for c in self.children)

    def name(self) -> str:
        return type(self).__name__

    def label(self) -> str:
        """`FilterExec` -> `filter`: the operator inside program names and
        named scopes (from the class, never from data)."""
        name = type(self).__name__
        return (name[:-4] if name.endswith("Exec") else name).lower()

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + self.name() + "\n"
        return s + "".join(c.tree_string(indent + 1) for c in self.children)


class MapLikeOp(Operator):
    """Operator expressible as a pure per-batch transform — fusable.

    Subclasses implement `make_batch_fn()` returning a jittable
    `fn(ColumnBatch) -> ColumnBatch`. `execute` exists for standalone use;
    the executor normally fuses chains of these into a single jit.
    """

    def __init__(self, child: Operator) -> None:
        super().__init__([child])

    @property
    def child(self) -> Operator:
        return self.children[0]

    def make_batch_fn(self) -> Callable[[ColumnBatch], ColumnBatch]:
        raise NotImplementedError

    def jit_safe(self) -> bool:
        """False when the batch fn crosses to the host (digests/JSON/UDF) —
        the fused chain then runs unjitted (hostfns.host_apply)."""
        return True

    def execute(self, ctx: ExecContext) -> BatchStream:
        from blaze_tpu.runtime.executor import execute_fused

        return execute_fused(self, ctx)


def add_compute_split(op: Operator, ns: int, device: bool) -> None:
    """Attribute one compute window to the op's device-vs-host split.

    `elapsed_compute_ns` (MetricsSet.timer's default) stays the combined
    number every existing report reads; these two siblings decompose it
    so metric_report and the query doctor can tell a jit-dispatched
    chain from a host-kernel chain (digests/JSON/UDF) without parsing
    plan shapes. The executor calls this once per fused batch — ops that
    never fuse simply have a zero split. `elapsed_device_ns` is host
    time round an asynchronous dispatch, not device time: device time
    comes from the profiler trace only."""
    op.metrics.add("elapsed_device_ns" if device else "elapsed_host_ns",
                   ns)


def batch_tap(op: Operator) -> Callable[..., None]:
    """The bookkeeping of one operator's output boundary, as a function
    to call once a batch: `note(batch)` reads the batch's rows,
    `note(batch, rows)` takes them from a caller that has them on the host.

    `count_stream` calls it for every batch of a stream. An operator whose
    consumer ran its work for it (a FilterExec whose mask a partial
    aggregate carried, ops/agg) has no output stream to wrap: the consumer
    calls it with the count the operator would have produced. An operator
    whose generator has just pulled an output batch's rows (a join dropping
    an empty batch) calls `note(batch, rows)` before each yield instead of
    being wrapped, so the batch is pulled once: streams hold batches only.

    With `conf.enable_input_batch_statistics` (the reference's
    batch_statisitcs module: per-exec input-batch stat metrics behind
    spark.blaze.enableInputBatchStatistics), every batch also records
    byte/row-size statistics — each operator's output stream IS its
    parent's input stream, so one output-side hook covers the plan.

    conf.trace_enabled reuses this same batch boundary for the engine
    trace's batch events + batch_rows histogram (runtime/trace.py): no
    new per-batch branch appears on the hot path when tracing is off —
    the truthiness checks below are the whole disabled-mode cost."""
    from blaze_tpu.config import conf
    from blaze_tpu.runtime import faults, trace

    stats = conf.enable_input_batch_statistics
    if stats:
        from blaze_tpu.runtime.memory import batch_nbytes
    # query-history row tap (runtime/history.py): per-operator output
    # rows keyed by plan fingerprint — the observed-cardinality signal
    # the statistics feed aggregates. Same posture as tracing: unset,
    # the per-stream cost is this one truthiness check.
    if conf.history_dir:
        from blaze_tpu.runtime import history
    else:
        history = None
    # live progress tap (runtime/progress.py): per-stage rows/batches for
    # the /queries debug endpoint, fed from this same batch boundary.
    # Same posture again — off, the cost is one truthiness check here.
    if conf.progress_enabled:
        from blaze_tpu.runtime import progress
    else:
        progress = None
    fault_point = "op." + op.name()  # chaos injection at the op boundary

    def note(batch: ColumnBatch, rows: Optional[int] = None) -> None:
        if conf.fault_injection_spec:
            faults.inject(fault_point)
        if rows is None:
            rows = pull_rows(batch, "op.output_rows")
        if conf.trace_enabled:
            trace.on_batch(op, rows)
        if history is not None:
            history.observe_rows(op, rows)
        if progress is not None:
            progress.on_batch(op, rows)
        op.metrics.add("output_batches", 1)
        op.metrics.add("output_rows", rows)
        if stats:
            op.metrics.add("stat_bytes", batch_nbytes(batch))
            op.metrics.set_max("stat_max_batch_rows", rows)

    return note


def count_stream(op: Operator, stream: BatchStream) -> BatchStream:
    """Wrap a stream updating the operator's baseline metrics, trace,
    history and progress taps and its fault point (`batch_tap`)."""
    note = batch_tap(op)
    try:
        for batch in stream:
            note(batch)
            yield batch
    finally:
        # deterministic teardown: when the consumer abandons the stream
        # (kill, speculation loss, downstream error) a pipelined source
        # (runtime/pipeline.PrefetchStream) must quiesce its producer and
        # release its memory reservations NOW, not at GC time
        close = getattr(stream, "close", None)
        if close is not None:
            close()
