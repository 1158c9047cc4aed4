"""Per-task execution runtime: pipeline fusion + streaming drive loop.

Ref: blaze/src/rt.rs NativeExecutionRuntime — there, `plan.execute(partition)`
wires a tokio stream pipeline and a producer loop polls batches across the
FFI boundary. Here the pipeline is *compiled*: maximal chains of map-like
operators become one jit-compiled function (cached globally by plan key, see
jit_cache.py), and the drive loop is a plain Python generator pulling from
the chain's root source.
"""

from __future__ import annotations

import time

from typing import Callable, Optional

import jax

from blaze_tpu.columnar.batch import ColumnBatch
from blaze_tpu.config import conf
from blaze_tpu.ops.base import (
    BatchStream, ExecContext, MapLikeOp, Operator, add_compute_split,
    count_stream,
)
from blaze_tpu.ops.basic import FilterExec
from blaze_tpu.runtime import (
    compile_service, faults, jit_cache, monitor, trace,
)
from blaze_tpu.runtime.metrics import MetricNode


def run_task_with_resilience(attempt: Callable[[], object], *,
                             what: str = "task",
                             run_info: Optional[dict] = None,
                             fallback: Optional[Callable[[], object]] = None,
                             ctx: Optional[ExecContext] = None,
                             deadline: Optional[float] = None,
                             on_error: Optional[Callable] = None,
                             session=None):
    """Drive one task attempt through the resilience ladder.

    `attempt` must be a FULL re-runnable unit of work (decode plan ->
    execute -> commit): every operator here rebuilds its state per
    attempt and artifact commits are crash-atomic (runtime/artifacts.py),
    so re-running after a failure is safe — the Spark task-retry model,
    executed in-engine.

    Policy by error category (faults.classify):
      retryable  bounded retries (conf.max_task_retries) with exponential
                 backoff + jitter (faults.backoff_ms)
      resource   the degradation ladder (conf.enable_degradation_ladder):
                 rung 1 halves conf.target_batch_bytes for the remaining
                 attempts, rung 2 forces a MemManager release (self-spill
                 of every consumer), rung 3 reroutes the task to
                 `fallback` (the CPU row interpreter in the local runner).
                 Ladder off => treated as plain retryable.
      plan/fatal relayed immediately (original exception type preserved)
      killed     relayed immediately, never counted as an engine error

    Rungs and retries are recorded in the process-global resilience
    telemetry and, when given, in `run_info` ("retries", "degradations",
    "degraded.<rung>", "ladder_rung", "errors.<category>").

    `deadline` (time.monotonic seconds, from the supervisor's
    task/query budgets): backoff sleeps are CLAMPED to the remaining
    budget, and a retryable failure with no budget left is reclassified
    to faults.DeadlineError instead of sleeping past the deadline.

    `on_error(exc, category)` is invoked for every classified failure
    except "killed" — the supervisor's per-operator circuit breaker
    counts failures through it.

    `session` (a service.QuerySession) scopes the ladder's degradations
    to ONE query: rung 1 halves the session's batch-target override
    instead of mutating the process-global conf.target_batch_bytes, and
    rung 2's forced spill sweeps only the session tenant's consumers —
    a degrading query cannot shrink another tenant's batches or evict
    its working set."""
    import time as _time

    from blaze_tpu.config import conf
    from blaze_tpu.runtime import memory

    retries = 0
    hang_relaunches = 0
    rung = 0
    saved_target = None
    try:
        while True:
            try:
                return attempt()
            except Exception as e:  # noqa: BLE001 — classify-and-decide
                cat = faults.classify(e)
                if cat == "killed":
                    raise
                faults.note_error(cat, run_info)
                trace.event("task_error", what=what, category=cat,
                            error=type(e).__name__)
                if on_error is not None:
                    try:
                        on_error(e, cat)
                    except Exception:  # noqa: BLE001 — observer only
                        pass
                ladder = cat == "resource" and conf.enable_degradation_ladder
                if ladder:
                    if rung == 0:
                        rung = 1
                        if session is not None:
                            saved_target = (session.batch_target
                                            or conf.target_batch_bytes)
                            session.batch_target = max(
                                saved_target // 2, 1 << 20)
                        else:
                            saved_target = conf.target_batch_bytes
                            conf.target_batch_bytes = max(
                                saved_target // 2, 1 << 20)
                        faults.note_degradation("halve_batch", run_info)
                        trace.event("ladder_rung", what=what, rung=1,
                                    action="halve_batch")
                        _note_rung(run_info, rung)
                        _note_progress("ladder_rung", "halve_batch")
                        continue
                    if rung == 1:
                        rung = 2
                        memory.get_manager(ctx).release(
                            1 << 62,
                            tenant=(session.tenant_id
                                    if session is not None else None))
                        faults.note_degradation("force_spill", run_info)
                        trace.event("ladder_rung", what=what, rung=2,
                                    action="force_spill")
                        _note_rung(run_info, rung)
                        _note_progress("ladder_rung", "force_spill")
                        continue
                    if rung == 2 and fallback is not None:
                        rung = 3
                        faults.note_degradation("fallback", run_info)
                        trace.event("ladder_rung", what=what, rung=3,
                                    action="fallback")
                        _note_rung(run_info, rung)
                        _note_progress("ladder_rung", "fallback")
                        return fallback()
                elif isinstance(e, faults.HungError) and \
                        hang_relaunches < conf.max_task_retries:
                    # a watchdog kill-on-suspicion, not a failure: its
                    # own relaunch budget (a false-positive hang must
                    # not drain the error-retry budget) and no backoff
                    # sleep — but never relaunch past the deadline
                    if deadline is not None and \
                            _time.monotonic() >= deadline:
                        trace.event("deadline_exceeded", what=what,
                                    during="hang_relaunch")
                        raise faults.DeadlineError(
                            f"{what}: hang-relaunch budget exhausted by "
                            f"deadline (after {hang_relaunches} "
                            f"relaunches)") from e
                    faults.note_retry(run_info)
                    hang_relaunches += 1
                    trace.event("hang_relaunch", what=what,
                                n=hang_relaunches)
                    continue
                elif cat in ("retryable", "resource") and \
                        retries < conf.max_task_retries:
                    sleep_s = faults.backoff_ms(retries) / 1000.0
                    if deadline is not None:
                        remaining = deadline - _time.monotonic()
                        if remaining <= 0:
                            trace.event("deadline_exceeded", what=what,
                                        during="retry")
                            raise faults.DeadlineError(
                                f"{what}: retry budget exhausted by "
                                f"deadline (after {retries} retries)"
                            ) from e
                        sleep_s = min(sleep_s, remaining)
                    faults.note_retry(run_info)
                    retries += 1
                    trace.event("retry", what=what, n=retries,
                                category=cat,
                                backoff_ms=round(sleep_s * 1000, 2))
                    _note_progress("retry", cat)
                    t0 = _time.perf_counter_ns()
                    faults._sleep(sleep_s)
                    if conf.monitor_enabled:
                        monitor.count_time("retry_backoff",
                                           _time.perf_counter_ns() - t0)
                    continue
                raise faults.ensure_classified(e) from e
    finally:
        if saved_target is not None:
            # restore-to-max: with concurrent tasks two ladders can
            # interleave their save/restore — taking the max keeps a
            # degraded (halved) target from outliving the query even if
            # the saves raced
            if session is not None:
                session.batch_target = max(session.batch_target or 0,
                                           saved_target)
            else:
                conf.target_batch_bytes = max(conf.target_batch_bytes,
                                              saved_target)


def run_pool_plan(node, ctx: ExecContext, what: str = "pool_task"):
    """Executor-PROCESS entry for one shipped plan proto
    (runtime/executor_pool.py worker): decode -> execute -> crash-atomic
    commit, driven through the in-process resilience ladder — a
    transient fault burns an executor-local retry (or a resource fault a
    ladder rung) before it costs the driver a cross-process re-queue.
    No row fallback here: the driver owns the lineage and re-executes
    lost partitions itself. conf.task_deadline_ms bounds all attempts,
    same contract as the supervised thread path. Returns the executed
    operator (its metrics carry the stage statistics the worker reports
    back)."""
    import time as _time

    from blaze_tpu.config import conf
    from blaze_tpu.plan import decode_plan

    def attempt():
        op = decode_plan(node)  # fresh operator state per attempt
        list(execute_plan(op, ctx))
        return op

    deadline = None
    if conf.task_deadline_ms and conf.task_deadline_ms > 0:
        deadline = _time.monotonic() + conf.task_deadline_ms / 1000.0
    return run_task_with_resilience(attempt, what=what, ctx=ctx,
                                    deadline=deadline)


def _note_rung(run_info: Optional[dict], rung: int) -> None:
    if run_info is not None:
        run_info["ladder_rung"] = max(run_info.get("ladder_rung", 0), rung)


def _note_progress(kind: str, detail: str) -> None:
    """Mirror a resilience event into the live progress registry (the
    /queries waterfall's retry/rung annotations). One truthiness check
    when live introspection is off; events are rare, so the lazy import
    on the enabled path is fine."""
    from blaze_tpu.config import conf

    if conf.progress_enabled:
        from blaze_tpu.runtime import progress

        progress.note_event(kind, detail)


def _fused_chain(op: MapLikeOp) -> tuple:
    """Longest chain of MapLikeOps ending at `op` (top-down order)."""
    chain = [op]
    while isinstance(chain[-1].child, MapLikeOp):
        chain.append(chain[-1].child)
    return chain[0], chain[-1].child, list(reversed(chain))


def execute_fused(op: MapLikeOp, ctx: ExecContext) -> BatchStream:
    """Execute a map-like operator, fusing its maximal map-like chain.

    Chains containing host-evaluated expressions (digests/JSON/UDF — see
    Operator.jit_safe) run UNJITTED: device ops still dispatch eagerly on
    device, host kernels get concrete arrays (hostfns.host_apply) — no
    dependence on XLA host callbacks, which need a CPU device listed in
    jax_platforms beside the accelerator."""
    top, source, chain = _fused_chain(op)
    jit = all(c.jit_safe() for c in chain)
    key = ("fused", jit, top.plan_key())
    # the catch-all kind says which chain it is: operator class names, in
    # execution order, from the plan's structure only
    names = [c.label() for c in chain]
    program = ".".join(["fused"] + names)
    # each filter of the chain compacts every batch the chain is handed
    compactions = sum(isinstance(c, FilterExec) for c in chain)

    def make():
        from blaze_tpu.exprs.compiler import cse_scope

        fns = [c.make_batch_fn() for c in chain]

        def fused(batch: ColumnBatch) -> ColumnBatch:
            # one CSE scope PER OP: shared subexpressions within an op
            # evaluate once; a chain-wide scope would retain every
            # intermediate batch in the memo until the chain ends (ops
            # build fresh batches, so cross-op hits can't happen anyway)
            for label, fn in zip(names, fns):
                with cse_scope(), jax.named_scope(label):
                    batch = fn(batch)
            return batch

        return fused

    def gen():
        for batch in source.execute(ctx):
            ctx.check_running()
            fused = jit_cache.get_or_compile(key + batch.shape_key(), make,
                                             jit=jit, name=program)
            t0 = time.perf_counter_ns()
            with op.metrics.timer():
                out = fused(batch)
            batch_ns = time.perf_counter_ns() - t0
            add_compute_split(op, batch_ns, device=jit)
            if compactions:
                compile_service.note_filter_batches(compacted=compactions)
            if conf.monitor_enabled:
                # unjitted chains (host kernels: digests/JSON/UDF) bill
                # host_compute; a fused jit dispatch bills fused_dispatch:
                # host time round an asynchronous dispatch, NOT device
                # time (that comes from the profiler trace only)
                monitor.count_time(
                    "fused_dispatch" if jit else "host_compute", batch_ns)
            yield out

    return count_stream(op, gen())


def execute_plan(root: Operator, ctx: Optional[ExecContext] = None) -> BatchStream:
    ctx = ctx or ExecContext()
    return root.execute(ctx)


def execute_stage_or_plan(root: Operator,
                          ctx: Optional[ExecContext] = None) -> BatchStream:
    """Whole-stage single-dispatch attempt first, streaming otherwise.

    Used by stage DRIVERS (shuffle writers, the mesh exchange) whose
    subtree is a complete stage: a matching scan→filter→project→partial
    agg pipeline runs as ONE jit program (stage_compiler), so a shuffle
    map task costs one dispatch instead of one-per-batch. Agg-less
    chains stay streaming (chain_ok=False): one whole-stage batch would
    defeat the drivers' bounded staging/spill."""
    ctx = ctx or ExecContext()
    from blaze_tpu.runtime.stage_compiler import try_run_stage

    staged = try_run_stage(root, ctx, chain_ok=False)
    if staged is not None:
        return iter([staged])
    return root.execute(ctx)


def collect(root: Operator, ctx: Optional[ExecContext] = None) -> ColumnBatch:
    """Materialize all output into one batch (test/driver helper)."""
    ctx = ctx or ExecContext()
    from blaze_tpu.runtime.stage_compiler import try_run_stage

    staged = try_run_stage(root, ctx)
    if staged is not None:
        return staged
    return _collect_streamed(root, ctx)


def _collect_streamed(root: Operator, ctx: ExecContext) -> ColumnBatch:
    from blaze_tpu.ops.common import concat_batches

    batches = list(execute_plan(root, ctx))
    if not batches:
        return ColumnBatch.empty(root.schema)
    if len(batches) == 1:
        return batches[0]
    return concat_batches(batches, root.schema)


def collect_fetch(root: Operator, pack: Callable,
                  ctx: Optional[ExecContext] = None):
    """Run the plan and fetch `pack(batch) -> 1-D f64 array` to the host
    in ONE dependent device→host round trip.

    Every dependent dispatch-and-pull is a host round trip, so a collect
    that pulls validation flags and then the result pays twice. Here the
    stage
    compiler's oob/num_rows flags ride the SAME fetch as the packed
    result (optimistic execution): if the flags show the memoized dense
    range no longer covers the data, the packed result is discarded and
    the stage recomputes through the probe/fallback loop — correctness
    is unchanged, only the pull count drops.

    No reference analog: the reference engine is host-resident and its
    collect is free (rt.rs polls batches over an in-process FFI stream).
    """
    return collect_fetch_async(root, pack, ctx)()


def collect_fetch_async(root: Operator, pack: Callable,
                        ctx: Optional[ExecContext] = None):
    """collect_fetch split into dispatch and fetch: returns a zero-arg
    `finish()` whose call pulls the packed result (and, on a tripped
    stage flag, recomputes via the probe/fallback loop).

    Lets a driver PIPELINE partitions/reps: dispatch partition i+1's
    program before pulling partition i's result, hiding the pull's host
    round trip behind the next dispatch's device time (the deployment
    shape bench.py measures as steady-state).
    collect_fetch is this plus an immediate finish()."""
    import jax.numpy as jnp
    import numpy as np

    ctx = ctx or ExecContext()
    from blaze_tpu.runtime.stage_compiler import try_run_stage

    # the pack fn participates in the jit key: one plan may be fetched
    # through several different packings (digest vs full export). Pin the
    # fn so its id() can never be recycled onto a different pack (the jit
    # cache outlives the caller's reference).
    pack_id = (getattr(pack, "__qualname__", ""), id(pack))
    _pack_pins[id(pack)] = pack

    staged = try_run_stage(root, ctx, deferred=True)
    if staged is not None:
        out, flags, retry, commit_metrics = staged
        if flags is not None:
            key = ("collect_fetch", root.plan_key(), out.shape_key(),
                   pack_id)

            def make():
                def f(out, flags):
                    return jnp.concatenate(
                        [flags.astype(jnp.float64), pack(out)])
                return f

            fn = jit_cache.get_or_compile(key, make)
            packed_dev = fn(out, flags)  # dispatched, NOT pulled

            def finish():
                packed = np.asarray(packed_dev)
                if not bool(packed[0]):
                    commit_metrics()
                    return packed[2:]
                out2 = retry()
                key2 = ("collect_fetch_plain", root.plan_key(),
                        out2.shape_key(), pack_id)
                fn2 = jit_cache.get_or_compile(key2, lambda: pack)
                return np.asarray(fn2(out2))

            return finish
        if commit_metrics is not None:
            commit_metrics()
    else:
        out = _collect_streamed(root, ctx)

    key = ("collect_fetch_plain", root.plan_key(), out.shape_key(), pack_id)
    fn = jit_cache.get_or_compile(key, lambda: pack)
    packed_dev = fn(out)
    return lambda: np.asarray(packed_dev)


def collect_arrow(root: Operator, ctx: Optional[ExecContext] = None):
    from blaze_tpu.columnar.arrow_io import batch_to_arrow

    return batch_to_arrow(collect(root, ctx))


# strong refs for collect_fetch pack fns (keyed by id; see pack_id above)
_pack_pins: dict = {}


def metric_tree(root: Operator) -> MetricNode:
    node = MetricNode.from_operator(root)
    # process-global compile + resilience counters ride along as extra
    # children (no handler of their own: embedders that only set the root
    # handler are unaffected; tree-walking embedders get the telemetry)
    node.children = list(node.children) + [compile_service.telemetry_node(),
                                           faults.telemetry_node()]
    return node
