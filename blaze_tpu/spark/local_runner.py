"""Local multi-stage execution: the test/standalone stand-in for Spark.

Ref topology: SURVEY.md §3.3 — in deployment, Spark schedules stages and
moves shuffle blocks; this runner executes the same per-task native plans
(stages.plan_stages output) in dependency order in one process, wiring the
resource registry exactly the way the JVM shim would:

  map stage    : one task per upstream partition; each commits
                 <dir>/shuffle_<S>_<M>.data/.index through the
                 shuffle-manager drop-in (spark/shuffle_manager.py)
  reduce reads : "shuffle:<S>" resolves to a per-partition iterator over
                 all map outputs' partition-p segments (the MapStatus fetch)
  broadcast    : one collect task; "broadcast:<S>" replays its frames

This is also the local-mode execution path (the reference's CI runs Spark
local-mode for the same reason, .github/workflows/tpcds.yml).
"""

from __future__ import annotations

import base64
import os
import sys
import tempfile
from typing import Dict, List, Optional

from blaze_tpu.columnar.batch import ColumnBatch, pull_rows
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.ops.common import concat_batches
from blaze_tpu.plan import decode_plan, fingerprint_plan
from blaze_tpu.plan import plan_pb2 as pb
from blaze_tpu.plan.fingerprint import fingerprint_query
from blaze_tpu.runtime import artifacts, faults, history, journal, monitor
from blaze_tpu.runtime import compile_service, placement, resources, trace
from blaze_tpu.runtime import supervisor as supervisor_mod
from blaze_tpu.runtime.executor import execute_plan, run_task_with_resilience
from blaze_tpu.runtime.supervisor import Supervisor, TaskSpec
from blaze_tpu.spark.convert_strategy import apply_strategy
from blaze_tpu.spark.plan_model import SparkPlan
from blaze_tpu.spark.stages import Stage, local_resource_id, plan_stages

import threading

# Conversion critical section: converters._pending_exports is a process
# global, so [discard stale, convert, drain] must be atomic per query or
# a concurrent query's drain swallows this one's FFI exports.
_convert_lock = threading.Lock()


def run_plan(root: SparkPlan, num_partitions: int = 4,
             work_dir: Optional[str] = None,
             mesh_exchange: str = "auto",
             mesh_quota: Optional[int] = None,
             run_info: Optional[Dict[str, int]] = None,
             session=None) -> ColumnBatch:
    """Convert + execute a Spark plan tree locally; returns the collected
    result batch.

    mesh_exchange: "auto" runs each shuffle stage's exchange in HBM over
    the device mesh when the partition count fits (parallel/
    stage_exchange.py), falling back to the file path on quota overflow or
    unsupported shapes; "off" always uses .data/.index files. mesh_quota
    caps the per-device-per-partition staging rows (None = safe default,
    no overflow possible).

    run_info: optional dict populated with execution-path counters
    ("mesh_stages", "file_stages" — a stage that went through files whole,
    or an in-HBM stage any of whose batches did, past the memory budget or
    the quota: such a stage counts in "mesh_stages" as well, so the two may
    sum to more than the plan's shuffle stages —, "broadcast_stages",
    "mesh_devices" —
    the fewest devices any mesh exchange's output sat on — and
    "mesh_host_bytes" — bytes of mesh-exchanged partitions that crossed
    the host on their way to the consuming task, 0 where each is consumed
    on the chip that owns it) so callers — the multichip dryrun,
    chip_smoke.py, tests — can assert WHICH transport carried each
    exchange rather than trusting the result alone.

    When conf.trace_enabled, the whole run is a "query" span in the
    engine trace (runtime/trace.py) and every stage/task below inherits
    its query_id; with conf.trace_export_dir set, the Chrome trace and a
    run-ledger line are exported on completion (README "Observability").

    session: the QuerySession (runtime/service.py) when running under
    the multi-tenant service — carries tenant id, priority, the shared
    fair scheduler, the admission-stamped deadline, and the per-session
    batch-target override. None = standalone single-query driver.
    """
    from blaze_tpu.config import conf

    if run_info is None:
        run_info = {}
    qid = (session.query_id if session is not None
           else run_info.get("query_id")) or trace.new_query_id()
    run_info["query_id"] = qid
    tenant = (session.tenant_id if session is not None
              else run_info.get("tenant_id", "")) or ""
    if tenant:
        run_info["tenant_id"] = tenant
    from blaze_tpu.runtime import memory

    mgr = memory.get_manager()
    # resource accounting: register the active query (copy-boundary
    # attribution), reset the memory high-water mark, and lazily start
    # the Prometheus endpoint + sampler when conf.metrics_port is set
    monitor.begin_query(qid, mgr)
    # query-history taps (runtime/history.py): per-op row counts and
    # whole-stage group cardinality accumulate under this qid until
    # record_run pops them at close (no-op with conf.history_dir unset)
    history.begin_query(qid)
    # write-ahead journal (runtime/journal.py): the admission record
    # opens this query's crash-recovery log (no-op with journal_dir
    # unset); the terminal record in the finally below settles it.
    # Stream micro-batches (run_info["stream"], runtime/streaming.py)
    # skip per-batch journals: the stream's checkpoint record is the
    # durability unit, and a crashed batch is re-processed from the
    # last checkpoint — billing it driver_restart at takeover would
    # double-count work the resumed stream replays by design.
    jnl = (None if run_info.get("stream")
           else journal.journal_for(qid))
    if jnl is not None:
        jnl.admitted(tenant_id=tenant)
    if conf.progress_enabled:
        from blaze_tpu.runtime import progress

        progress.begin_query(qid, tenant_id=tenant or None)
    # the query's driver thread advertises its session for ladder/batch
    # scoping (supervisor.current_session) — pool workers inherit it
    # through their _Task instead
    prev_session = getattr(supervisor_mod._current, "session", None)
    supervisor_mod._current.session = session
    try:
        # correlation ids pushed UNCONDITIONALLY (trace.context is a
        # cheap stack push, not gated on trace_enabled): with several
        # queries live at once, monitor/history attribution must read
        # the per-thread context — the single-slot _active_qid fallback
        # can't name this thread's query
        trace.anchor_clock()
        with trace.context(query_id=qid, tenant_id=tenant or None):
            with trace.profiled_span("run_plan"):
                with trace.span("query", query_id=qid,
                                num_partitions=num_partitions,
                                mesh_exchange=mesh_exchange):
                    out = _run_plan_inner(root, num_partitions, work_dir,
                                          mesh_exchange, mesh_quota,
                                          run_info, session)
                    # the caller's last pull runs after this context is
                    # popped: the batch remembers whose it is, so
                    # to_numpy records its d2h span under this query
                    out._query_id = qid
                    return out
    finally:
        supervisor_mod._current.session = prev_session
        # the flight recorder needs the query's wall-clock start for its
        # monitor-ring slice; finish_query pops the acct holding it
        t0 = monitor.query_t0(qid) if conf.flight_dir else None
        # roll-ups (bytes by boundary, peak memory, spill, compile ms)
        # merged into run_info BEFORE the ledger export, plus the
        # always-on leak check (resource_leak event + counter)
        monitor.finish_query(qid, run_info, mgr)
        # export even on failure: a failed query's trace is the one you
        # most want to read
        if conf.trace_enabled and conf.trace_export_dir:
            trace.export_query(qid, run_info)
        # per-query continuous-profiling artifacts (collapsed stacks +
        # speedscope), fleet-merged — same export-even-on-failure rule
        if conf.profile_enabled and conf.profile_export_dir:
            from blaze_tpu.runtime import profiler

            profiler.export_query(qid)
        # persist the run's fingerprinted statistics (after the monitor
        # roll-up so the record carries the byte/spill/compile counters)
        if conf.history_dir:
            history.record_run(qid, run_info)
        if jnl is not None:
            # terminal journal record (classified from the in-flight
            # exception, the flight-recorder posture below): a journal
            # with a complete line never enters a recovery replay
            exc = sys.exc_info()[1]
            jnl.complete("failed" if exc is not None else "ok",
                         error=type(exc).__name__ if exc is not None
                         else "")
        if conf.flight_dir:
            # black-box dossier on failure / deadline / hang / leak —
            # classifies the in-flight exception via sys.exc_info (this
            # finally runs while it propagates; run_plan has no except)
            from blaze_tpu.runtime import flight_recorder

            flight_recorder.on_query_end(qid, run_info, started_at=t0)
        if conf.progress_enabled:
            from blaze_tpu.runtime import progress

            progress.finish_query(qid)


def _run_plan_inner(root: SparkPlan, num_partitions: int,
                    work_dir: Optional[str], mesh_exchange: str,
                    mesh_quota: Optional[int],
                    run_info: Optional[Dict[str, int]] = None,
                    session=None) -> ColumnBatch:
    if run_info is None:
        run_info = {}
    from blaze_tpu.spark import aqe

    # an adaptive root carries the session's AQE settings: the stages
    # below read coalesced ranges of partitions (spark/aqe.py)
    root, adaptive = aqe.unwrap_adaptive(root)
    run_info.setdefault("mesh_stages", 0)
    run_info.setdefault("file_stages", 0)
    run_info.setdefault("broadcast_stages", 0)
    run_info.setdefault("pool_stages", 0)
    run_info.setdefault("recovered_stages", 0)
    run_info.setdefault("map_tasks_run", 0)
    from blaze_tpu.config import conf

    # the `plan` span: tagging, conversion, stage split and the set-up
    # of the stage loop, up to the first stage
    with trace.span("plan") as psp:
        # task setup reclaims dead writers' leftovers (artifact temps in the
        # work dirs via BlazeShuffleManager, spill files here), and the
        # trace export dir is bounded to conf.history_retention_runs
        # (ledger.jsonl lines + trace_<qid>.json files — it grew without
        # limit before)
        artifacts.sweep_orphans([conf.spill_dir])
        # driver-crash recovery (runtime/journal.py): replay incomplete
        # journals once per process — verified stage commits land in the
        # resume map each shuffle-map stage consults below
        journal.ensure_recovery_scan()
        if conf.trace_export_dir:
            trace.rotate_export_dir()
        telemetry_before = faults.TELEMETRY.snapshot()
        from blaze_tpu.runtime import pipeline

        pipeline_before = pipeline.TELEMETRY.snapshot()
        from blaze_tpu.spark import converters, fallback

        # per-query resource namespace: concurrent queries both number their
        # stages from 0, so every shuffle/broadcast registry key is prefixed
        # with this query's id ("<qid>/shuffle:<sid>")
        ns = f"{run_info['query_id']}/" if run_info.get("query_id") else ""
        with _convert_lock:
            apply_strategy(root)
            converters.drain_exports()  # discard stale prior conversions
            stages = plan_stages(root, default_partitions=num_partitions,
                                 namespace=run_info.get("query_id", ""))
            # Register a row-export iterator for every FFI-bridged
            # (NeverConvert) subtree — the ConvertToNativeBase.scala:59-98
            # handshake: the subtree runs on the row engine (fallback.py)
            # and feeds native FfiReaderExec.
            exports = converters.drain_exports()
        for rid, subtree in exports.items():
            def provider(partition, nparts, _p=subtree):
                return fallback.export_iterator(_p, partition, nparts)
            resources.put(rid, provider)
        jnl = (None if run_info.get("stream")
               else journal.journal_for(run_info.get("query_id", "")))
        if jnl is not None:
            # the plan record pins what this journal is a log OF: the
            # pre-AQE query fingerprint (stable across runs of the same
            # plan, known before execution) plus the stage skeleton
            # (per-stage fingerprints — the resume keys — are journaled
            # with each stage_commit, computed after AQE re-optimization)
            jnl.plan(fingerprint=fingerprint_query(
                         [fingerprint_plan(s.plan) for s in stages]),
                     num_partitions=num_partitions,
                     stages=[{"stage_id": s.stage_id, "kind": s.kind,
                              "num_partitions": s.num_partitions,
                              "plan_proto": base64.b64encode(
                                  s.plan.SerializeToString()).decode()}
                             for s in stages])
        work_dir = work_dir or tempfile.mkdtemp(prefix="blaze_tpu_stages_")
        os.makedirs(work_dir, exist_ok=True)

        # the shuffle-manager drop-in tracks map outputs (MapStatus) and
        # serves reduce-side readers — the role BlazeShuffleManager plays as
        # spark.shuffle.manager in deployment
        from blaze_tpu.spark.shuffle_manager import BlazeShuffleManager

        shuffle_mgr = BlazeShuffleManager(work_dir)
        # AQE statistics: completed shuffles' total bytes + partition counts
        shuffle_bytes: Dict[int, int] = {}
        shuffle_parts: Dict[int, int] = {}
        mesh_stats: List[Dict[str, int]] = []
        # shuffle stages whose partitions a mesh exchange left on their
        # owner chips: the tasks that read them are placed there
        resident: set = set()

        # the task supervisor owns this query's worker pool, watchdog (hang
        # detection + deadlines), straggler speculation and the per-operator
        # circuit breaker (runtime/supervisor.py); disabled it degrades each
        # stage to the sequential inline path. Under the service the session
        # routes tasks through the SHARED fair scheduler and carries the
        # admission-stamped query deadline; breaker state stays per-query
        # (one CircuitBreaker per Supervisor, one Supervisor per run_plan).
        sup = Supervisor(run_info, session=session)
        # process-isolated executors (runtime/executor_pool.py): when a pool
        # is active, eligible shuffle-map stages ship their task plans to
        # worker PROCESSES (crash containment) instead of the thread pool;
        # the pool failing degrades back to the in-process path below
        from blaze_tpu.runtime import executor_pool

        pool = executor_pool.active()
        # live-introspection taps (runtime/progress.py): conditional import
        # once per run, one is-None check per stage — zero work when off
        if conf.progress_enabled:
            from blaze_tpu.runtime import progress
        else:
            progress = None
        qid = run_info.get("query_id", "")
        psp.set(stages=len(stages))
    try:
        for stage in stages:
            # re-optimize THIS stage with the statistics of completed
            # shuffles before running it (ref: AQE per-stage re-entry)
            with trace.context(stage_id=stage.stage_id):
                n = (aqe.apply_dynamic_join_selection(
                    stage.plan, shuffle_bytes, shuffle_parts)
                     if shuffle_bytes else 0)
                coalesced = (aqe.plan_coalescing(stage, adaptive)
                             if adaptive is not None
                             and stage.kind != "broadcast" else None)
            if n:
                import logging

                logging.getLogger(__name__).info(
                    "AQE: converted %d SMJ(s) to broadcast join "
                    "(stage %d)", n, stage.stage_id)
            if coalesced is not None and stage.source is not None:
                # the row interpreter's rung reads what the tasks read
                stage.source = _copy_tree_readers(
                    stage.source, _read_forms(stage.plan).get)
            # canonical plan fingerprint (plan/fingerprint.py), computed
            # AFTER AQE re-optimization — the executed shape is the one
            # history statistics must key on. Skipped when nothing
            # records it (neither tracing nor the history store is on).
            fp = (fingerprint_plan(stage.plan)
                  if conf.trace_enabled or conf.history_dir
                  or jnl is not None else None)
            if progress is not None:
                progress.stage_begin(
                    qid, stage.stage_id, stage.kind, fingerprint=fp,
                    tasks=(1 if stage.kind == "broadcast"
                           else _input_tasks(stage, stages,
                                             fallback=num_partitions)))
            if stage.kind == "shuffle_map":
                shuffle_parts[stage.stage_id] = stage.num_partitions
                with trace.context(stage_id=stage.stage_id), \
                        trace.span("stage", stage_id=stage.stage_id,
                                   stage_kind="shuffle_map",
                                   fingerprint=fp,
                                   tasks=_input_tasks(stage, stages)) as sp:
                    if coalesced is not None:
                        aqe.read_ranges(coalesced)
                    if jnl is not None and fp:
                        # a crashed driver's verified stage commit for
                        # this fingerprint? reuse it — zero map tasks run
                        logical = _resume_shuffle_stage(
                            stage, stages, shuffle_mgr, fp, jnl,
                            run_info, ns)
                        if logical is not None:
                            shuffle_bytes[stage.stage_id] = logical
                            sp.set(transport="journal", bytes=logical,
                                   **monitor.stage_span_attrs(
                                       run_info["query_id"],
                                       stage.stage_id))
                            if progress is not None:
                                progress.stage_end(qid, stage.stage_id)
                            continue
                    prids = (_pool_stage_rids(stage)
                             if pool is not None else None)
                    if prids is not None:
                        try:
                            logical = _run_shuffle_stage_pooled(
                                stage, stages, shuffle_mgr, pool,
                                run_info, ns, prids, jnl=jnl, fp=fp)
                        except Exception as e:  # noqa: BLE001 — classified
                            cat = faults.classify(e)
                            if cat in ("fatal", "plan"):
                                raise
                            # pool unavailable / exhausted retries:
                            # degrade to the in-process transports —
                            # same row multisets either way
                            faults.note_error(cat, run_info)
                            faults.note_degradation("pool_to_thread",
                                                    run_info)
                            trace.event("degrade", what="pool_to_thread",
                                        category=cat,
                                        error=type(e).__name__)
                        else:
                            shuffle_bytes[stage.stage_id] = logical
                            run_info["pool_stages"] += 1
                            sp.set(transport="pool", bytes=logical,
                                   **monitor.stage_span_attrs(
                                       run_info["query_id"],
                                       stage.stage_id))
                            if progress is not None:
                                progress.stage_end(qid, stage.stage_id)
                            continue
                    if mesh_exchange == "auto":
                        from blaze_tpu.parallel.stage_exchange import (
                            run_mesh_shuffle_stage,
                        )

                        stats: Dict[str, int] = {}
                        mesh_stats.append(stats)
                        # a transient/resource failure on the mesh degrades
                        # to the file exchange (same row multisets by
                        # design); plan/fatal/killed relay — another
                        # transport won't fix a broken plan
                        try:
                            ntasks = _input_tasks(stage, stages)
                            mesh_ok = run_mesh_shuffle_stage(
                                stage.plan, stage.stage_id, ntasks,
                                quota=mesh_quota,
                                work_dir=work_dir, stats=stats,
                                namespace=ns, sup=sup,
                                task_devices=_task_devices(
                                    stage, ntasks, resident),
                                by_ranges=adaptive is not None)
                        except Exception as e:  # noqa: BLE001 — classified
                            cat = faults.classify(e)
                            if cat in ("killed", "fatal", "plan"):
                                raise
                            faults.note_error(cat, run_info)
                            faults.note_degradation("mesh_to_file", run_info)
                            trace.event("degrade", what="mesh_to_file",
                                        category=cat,
                                        error=type(e).__name__)
                            mesh_ok = False
                        if mesh_ok:
                            shuffle_bytes[stage.stage_id] = \
                                stats.get("bytes", 0)
                            run_info["mesh_stages"] += 1
                            ndev = stats.get("devices", 1)
                            run_info["mesh_devices"] = min(
                                run_info.get("mesh_devices", ndev), ndev)
                            if ndev > 1:
                                resident.add(stage.stage_id)
                            if stats["file_batches"]:
                                # past the budget or the quota a batch
                                # left HBM through files: the stage is a
                                # file stage too
                                run_info["file_stages"] += 1
                            compile_service.note_exchange_kept(
                                stats["slices"], stats["slice_rows"],
                                stats["slices_cut"], stats["slices_packed"])
                            sp.set(transport="mesh",
                                   bytes=stats.get("bytes", 0),
                                   pinned_bytes=stats["pinned_bytes"],
                                   **monitor.stage_span_attrs(
                                       run_info["query_id"],
                                       stage.stage_id))
                            if progress is not None:
                                progress.stage_end(qid, stage.stage_id)
                            continue
                    logical = _run_shuffle_stage(stage, stages, shuffle_mgr,
                                                 sup, run_info, ns=ns,
                                                 jnl=jnl, fp=fp,
                                                 resident=resident)
                    # logical (uncompressed) bytes: the mesh path reports
                    # the same unit, so the AQE threshold is
                    # transport-independent
                    shuffle_bytes[stage.stage_id] = logical
                    run_info["file_stages"] += 1
                    sp.set(transport="file", bytes=logical,
                           **monitor.stage_span_attrs(
                               run_info["query_id"], stage.stage_id))
                if progress is not None:
                    progress.stage_end(qid, stage.stage_id)
            elif stage.kind == "broadcast":
                with trace.context(stage_id=stage.stage_id), \
                        trace.span("stage", stage_id=stage.stage_id,
                                   stage_kind="broadcast",
                                   fingerprint=fp, tasks=1) as sp:
                    frames = _run_broadcast_stage(stage, stages, sup,
                                                  run_info, ns=ns)
                    if pool is not None:
                        # executors read broadcasts from the driver's
                        # shuffle server, same frames the local
                        # provider replays
                        pool.server.register_frames(
                            f"{ns}broadcast:{stage.stage_id}", frames)
                    sp.set(**monitor.stage_span_attrs(
                        run_info["query_id"], stage.stage_id))
                run_info["broadcast_stages"] += 1
                if progress is not None:
                    progress.stage_end(qid, stage.stage_id)
            else:
                parts = _input_tasks(stage, stages, fallback=num_partitions)
                with trace.context(stage_id=stage.stage_id), \
                        trace.span("stage", stage_id=stage.stage_id,
                                   stage_kind="result",
                                   fingerprint=fp, tasks=parts) as sp:
                    if coalesced is not None:
                        aqe.read_ranges(coalesced)
                    out = _run_result_stage(stage, parts, sup, run_info,
                                            resident)
                    sp.set(**monitor.stage_span_attrs(
                        run_info["query_id"], stage.stage_id))
                if progress is not None:
                    progress.stage_end(qid, stage.stage_id)
                return _merge_fallback_root_sort(root, out, parts)
        raise AssertionError("no result stage produced")
    finally:
        sup.close()
        if run_info["mesh_stages"]:
            # what the mesh stages' overflow sent through files: the only
            # exchanged bytes that cross the host
            run_info["mesh_host_bytes"] = sum(
                st.get("host_bytes", 0) for st in mesh_stats)
        faults.run_info_delta(telemetry_before, run_info)
        # pipelined-execution accounting for this query: streams/sinks
        # opened, and a leak indicator (must be 0 once every task stream
        # is torn down) — chaos_soak asserts on both
        after = pipeline.TELEMETRY.snapshot()
        run_info["pipeline_streams"] = (
            after.get("streams_opened", 0) + after.get("sinks_opened", 0)
            - pipeline_before.get("streams_opened", 0)
            - pipeline_before.get("sinks_opened", 0))
        run_info["pipeline_live_streams"] = pipeline.live_streams()
        # release per-query registry entries: FFI export subtrees and the
        # shuffle/broadcast providers (the mesh path's providers pin full
        # capacity-padded HBM batches — leaking them across queries would
        # exhaust device memory)
        for rid in exports:
            resources.pop(rid)
        for stage in stages:
            for key in (f"{ns}shuffle:{stage.stage_id}",
                        f"{ns}shuffle:{stage.stage_id}:all",
                        f"{ns}shuffle:{stage.stage_id}:ranges",
                        f"{ns}broadcast:{stage.stage_id}",
                        f"{ns}broadcast_sink:{stage.stage_id}"):
                resources.pop(key)
            if pool is not None:
                pool.server.unregister(f"{ns}shuffle:{stage.stage_id}")
                pool.server.unregister(f"{ns}broadcast:{stage.stage_id}")
            shuffle_mgr.unregister_shuffle(stage.stage_id)


def _merge_fallback_root_sort(root: SparkPlan, out: ColumnBatch,
                              parts: int) -> ColumnBatch:
    """Ordered collect for a NeverConvert root sort: the native-root case
    merges in _run_result_stage, but a fallback root sort produced
    per-partition order only — merge on the row engine."""
    if (root.kind != "SortExec" or parts <= 1
            or root.strategy != "NeverConvert"):
        return out
    import pandas as pd

    from blaze_tpu.columnar.arrow_io import batch_from_arrow
    from blaze_tpu.spark import fallback

    df = pd.DataFrame(out.to_numpy())
    srt = SparkPlan("SortExec", root.schema, [], dict(root.attrs))
    merged = fallback._op_sort_frame(srt, df)
    return batch_from_arrow(fallback._to_arrow(merged, root.schema),
                            schema=root.schema)


def _input_tasks(stage: Stage, stages: List[Stage],
                 fallback: int = 1) -> int:
    """Task count for a stage = its upstream shuffle partition count, or the
    ranges an adaptive plan coalesced its reads into; `fallback` when it has
    dependencies but none are shuffles (scans -> 1)."""
    if stage.tasks is not None:
        return stage.tasks
    if not stage.depends_on:
        return 1
    upstream = [stages[d].num_partitions for d in stage.depends_on
                if stages[d].kind == "shuffle_map"]
    return max(upstream) if upstream else fallback


def _task_devices(stage: Stage, ntasks: int, resident) -> Optional[list]:
    """The chip of each of the stage's `ntasks` tasks (runtime/placement.py's
    rule), or None where the stage reads no partitions that a mesh exchange
    left on their owners: those tasks run on the default device, unplaced."""
    if not resident or not any(d in resident for d in stage.depends_on):
        return None
    return [placement.owner(t, ntasks) for t in range(ntasks)]


def _schema_of_reader(node: pb.PlanNode):
    from blaze_tpu.plan.from_proto import decode_schema

    return decode_schema(node.ipc_reader.schema)


def _run_shuffle_stage(stage: Stage, stages: List[Stage],
                       shuffle_mgr, sup: Supervisor, run_info=None,
                       ns: str = "", jnl=None, fp=None,
                       resident=None) -> int:
    """Runs the map tasks through the shuffle manager (register ->
    per-task writer slot -> commit MapStatus -> reduce-side reader
    resource); returns the stage's total LOGICAL output bytes
    (uncompressed, live rows only — the AQE statistic).

    Each map task is a re-runnable resilience unit: the writer's
    crash-atomic commit means a failed attempt left no final files, so a
    retry simply re-executes. The supervisor may also race a speculative
    twin against a straggling attempt — the ExecContext's commit gate
    makes first-commit win and the loser abort cleanly. The ladder's
    last rung re-runs the task's map subtree (stage.source) on the row
    interpreter, feeding the native shuffle writer through an ipc_reader
    — the committed file format is identical either way."""
    ntasks = _input_tasks(stage, stages)
    # the reader schema is the writer's input schema
    reader_schema = decode_plan(stage.plan.shuffle_writer.input).schema
    handle = shuffle_mgr.register_shuffle(
        stage.stage_id, stage.num_partitions, reader_schema)
    op_kinds = stage.op_kinds()
    specs: List[TaskSpec] = []
    slots = []
    devs = _task_devices(stage, ntasks, resident) or [None] * ntasks
    for task in range(ntasks):
        node = pb.PlanNode()
        node.CopyFrom(stage.plan)
        slot = shuffle_mgr.get_writer(handle, task)
        node.shuffle_writer.data_file = slot.data_path
        node.shuffle_writer.index_file = slot.index_path

        def attempt(ctx, node=node):
            op = decode_plan(node)  # fresh operator state per attempt
            list(execute_plan(op, ctx))
            return op

        fb = (None if stage.source is None else
              lambda node=node, task=task: _fallback_shuffle_task(
                  stage, node, task, ntasks))
        specs.append(TaskSpec(
            what=f"shuffle_map[{stage.stage_id}:{task}]",
            attempt_fn=attempt, partition=task, num_partitions=ntasks,
            fallback_fn=fb, op_kinds=op_kinds, device=devs[task]))
        slots.append(slot)
    ops = sup.run_tasks(("shuffle", stage.stage_id), specs)
    logical = 0
    for task, (op, slot) in enumerate(zip(ops, slots)):
        written = op.metrics.values.get("shuffle_logical_bytes", 0)
        trace.record_value("shuffle_write_bytes", written)
        logical += written
        _register_slot_repair(stage, slot, task, ntasks, run_info)
        slot.commit()
    if run_info is not None:
        run_info["map_tasks_run"] = (
            run_info.get("map_tasks_run", 0) + ntasks)
    if jnl is not None and fp:
        jnl.stage_commit(stage.stage_id, fp, logical,
                         _journal_outputs(slots))
    resources.put(f"{ns}shuffle:{stage.stage_id}",
                  lambda partition: shuffle_mgr.get_reader_host(handle,
                                                                partition))
    return logical


# repair attempts are epoch-stamped off this fence so a re-executed map
# output can never collide with its quarantined predecessor's name
_repair_fence = artifacts.EpochFence()


def _journal_outputs(slots) -> List[dict]:
    """stage_commit payload: each map output's committed paths, epoch
    and whole-file digest (the recovery scan's cross-check)."""
    outs = []
    for slot in slots:
        crc = None
        try:
            _raw, meta = artifacts.read_index(slot.index_path)
            if meta is not None:
                crc = meta["data_crc"]
        except (OSError, faults.CorruptArtifactError):
            pass
        outs.append({"map_id": slot.map_id,
                     "data_path": slot.data_path,
                     "index_path": slot.index_path,
                     "epoch": artifacts.epoch_of(slot.data_path),
                     "data_crc": crc})
    return outs


def _register_stage_repairs(stage: Stage, slots, ntasks: int,
                            run_info=None) -> None:
    for task, slot in enumerate(slots):
        _register_slot_repair(stage, slot, task, ntasks, run_info)


def _register_slot_repair(stage: Stage, slot, task: int, ntasks: int,
                          run_info=None) -> None:
    """Arm lineage repair for one committed map output: on read-path
    corruption (artifacts.handle_corruption) ONLY the producing map task
    re-runs — in-process, under a fresh repair epoch so the new pair
    never collides with the quarantined names — recommits, and replaces
    its MapStatus (shuffle_manager replace-by-map_id). Armed BEFORE the
    slot's own commit: the MapStatus parse is itself a verifying read.
    unregister_shuffle forgets the registration with the files."""
    node = pb.PlanNode()
    node.CopyFrom(stage.plan)

    def repair(task=task, slot=slot, node=node):
        epoch = _repair_fence.advance(slot.data_path)
        new_data = artifacts.stamp_epoch(slot.data_path, epoch)
        new_index = artifacts.stamp_epoch(slot.index_path, epoch)
        node.shuffle_writer.data_file = new_data
        node.shuffle_writer.index_file = new_index
        op = decode_plan(node)
        list(execute_plan(op, ExecContext(partition=task,
                                          num_partitions=ntasks)))
        slot.data_path, slot.index_path = new_data, new_index
        slot.commit()
        if run_info is not None:
            run_info["map_tasks_run"] = (
                run_info.get("map_tasks_run", 0) + 1)
        # the repaired pair is itself repairable; the registration
        # under the OLD name stays to serve its redirect
        artifacts.register_repair(new_data, repair)
        return new_data, new_index

    artifacts.register_repair(slot.data_path, repair)


def _resume_shuffle_stage(stage: Stage, stages: List[Stage], shuffle_mgr,
                          fp: str, jnl, run_info,
                          ns: str = "") -> Optional[int]:
    """Reuse a crashed driver's committed stage: when the recovery scan
    harvested a VERIFIED stage_commit for this stage's fingerprint, the
    journaled pairs become this run's map outputs and no map task
    re-runs (the `map_tasks_run` counter proves it). Returns the stage's
    logical bytes, or None to execute normally."""
    rec = journal.take_resume(fp)
    if rec is None:
        return None
    ntasks = _input_tasks(stage, stages)
    outputs = sorted(rec.get("outputs") or [],
                     key=lambda o: int(o.get("map_id", 0)))
    if len(outputs) != ntasks:
        return None  # partitioning changed since the crash: recompute
    reader_schema = decode_plan(stage.plan.shuffle_writer.input).schema
    handle = shuffle_mgr.register_shuffle(
        stage.stage_id, stage.num_partitions, reader_schema)
    slots = []
    try:
        for task, out in enumerate(outputs):
            slot = shuffle_mgr.get_writer(handle, task)
            slot.data_path = str(out["data_path"])
            slot.index_path = str(out["index_path"])
            _register_slot_repair(stage, slot, task, ntasks, run_info)
            slot.commit()
            slots.append(slot)
    except (OSError, ValueError, KeyError, faults.CorruptArtifactError):
        # artifacts vanished between scan and resume: run the stage
        shuffle_mgr.unregister_shuffle(stage.stage_id, delete_files=False)
        return None
    logical = int(rec.get("logical_bytes", 0))
    trace.event("journal_replay", stage_id=stage.stage_id,
                fingerprint=fp, tasks=ntasks)
    run_info["recovered_stages"] = run_info.get("recovered_stages", 0) + 1
    journal.note_query_recovered(run_info.get("query_id", ""))
    # re-journal under THIS query's id: a second crash resumes the same
    jnl.stage_commit(stage.stage_id, fp, logical, outputs)
    resources.put(f"{ns}shuffle:{stage.stage_id}",
                  lambda partition: shuffle_mgr.get_reader_host(handle,
                                                                partition))
    return logical


def _pool_stage_rids(stage: Stage) -> Optional[List[str]]:
    """Reader resource ids of a shuffle-map stage when EVERY one is
    servable to executor processes over the driver's shuffle server
    (committed shuffle partitions — including `:all` build-side reads,
    which workers reassemble by fetching every partition of the base
    rid, mmap-first — and broadcast frame lists). None marks the stage
    pool-ineligible — it needs driver-local state a worker process
    cannot reach (FFI export iterators, UDF eval callbacks, RSS/sink
    consumers, fs providers) — and it runs in-process instead."""
    rids: List[str] = []
    servable = True

    def walk(msg) -> None:
        nonlocal servable
        for fd, val in msg.ListFields():
            if fd.type == fd.TYPE_MESSAGE:
                vals = val if fd.is_repeated else (val,)
                for v in vals:
                    walk(v)
            elif fd.name == "provider_resource_id":
                local = local_resource_id(val)
                if local.endswith(":ranges"):
                    # coalesced reads are cut on the driver (spark/aqe.py)
                    servable = False
                elif (local.startswith("shuffle:")
                        or local.startswith("broadcast:")):
                    rids.append(val)
                else:
                    servable = False
            elif fd.name.endswith("resource_id") and val:
                servable = False

    walk(stage.plan)
    return rids if servable else None


def _run_shuffle_stage_pooled(stage: Stage, stages: List[Stage],
                              shuffle_mgr, pool, run_info, ns: str,
                              rids: List[str], jnl=None, fp=None) -> int:
    """The map stage on the PROCESS pool: each task's plan proto ships to
    an executor over the control socket; the worker epoch-stamps the
    writer paths, reads upstream input from the driver's shuffle server,
    and commits crash-atomically in its own process. The driver admits
    each result through the epoch fence, points the writer slot at the
    accepted attempt's files, commits the MapStatus, sweeps stale-epoch
    twins, and publishes the outputs to BOTH registries — the in-process
    resource registry (downstream result/broadcast stages run locally)
    and the shuffle server (downstream POOLED stages fetch from
    workers)."""
    from blaze_tpu.runtime import executor_pool

    ntasks = _input_tasks(stage, stages)
    reader_schema = decode_plan(stage.plan.shuffle_writer.input).schema
    handle = shuffle_mgr.register_shuffle(
        stage.stage_id, stage.num_partitions, reader_schema)
    # driver-issued correlation ids ride the task payload: the worker
    # replays them into its trace context, so executor-side spans and
    # counter attribution share the driver's query/stage/task ids (the
    # telemetry-federation join key)
    ctx = trace.current_context()
    # `:all` build-side reads: the worker reassembles the whole relation
    # by fetching every partition of the base rid (mmap-first), so ship
    # each one's partition count — the only driver-local fact it needs
    rid_parts = {}
    for rid in rids:
        local = local_resource_id(rid)
        if local.startswith("shuffle:") and local.endswith(":all"):
            rid_parts[rid] = stages[int(local.split(":")[1])].num_partitions
    specs: List[executor_pool.PoolTaskSpec] = []
    slots = []
    for task in range(ntasks):
        node = pb.PlanNode()
        node.CopyFrom(stage.plan)
        slot = shuffle_mgr.get_writer(handle, task)
        node.shuffle_writer.data_file = slot.data_path
        node.shuffle_writer.index_file = slot.index_path
        specs.append(executor_pool.PoolTaskSpec(
            key=f"{ns}shuffle:{stage.stage_id}:{task}",
            kind="plan",
            payload={"partition": task, "num_partitions": ntasks,
                     "rids": rids, "rid_parts": rid_parts,
                     "query_id": ctx.get("query_id"),
                     "tenant_id": ctx.get("tenant_id"),
                     "stage_id": stage.stage_id,
                     "task_id": task,
                     "what": f"shuffle_map[{stage.stage_id}:{task}]"},
            blob=node.SerializeToString(),
            what=f"shuffle_map[{stage.stage_id}:{task}]"))
        slots.append(slot)
    results = pool.run_tasks(specs)
    logical = 0
    for task, (res, slot) in enumerate(zip(results, slots)):
        base_data, base_index = slot.data_path, slot.index_path
        # the accepted attempt's epoch-stamped pair becomes the slot's
        # committed artifact; every fenced twin is swept
        slot.data_path = res["data_path"]
        slot.index_path = res["index_path"]
        written = int(res.get("logical_bytes", 0))
        trace.record_value("shuffle_write_bytes", written)
        logical += written
        # repairs re-run in-process even for pool-committed outputs: the
        # reader resources the map subtree needs are in BOTH registries
        _register_slot_repair(stage, slot, task, ntasks, run_info)
        slot.commit()
        artifacts.sweep_stale_epochs(
            base_data, base_index, artifacts.epoch_of(res["data_path"]))
    if run_info is not None:
        run_info["map_tasks_run"] = (
            run_info.get("map_tasks_run", 0) + ntasks)
    if jnl is not None and fp:
        jnl.stage_commit(stage.stage_id, fp, logical,
                         _journal_outputs(slots))
    resources.put(f"{ns}shuffle:{stage.stage_id}",
                  lambda partition: shuffle_mgr.get_reader_host(handle,
                                                                partition))
    pool.server.register_shuffle(
        f"{ns}shuffle:{stage.stage_id}",
        [(slot.data_path, slot.index_path) for slot in slots])
    return logical


def _fallback_shuffle_task(stage: Stage, node: pb.PlanNode, task: int,
                           ntasks: int):
    """Ladder rung 3 for a map task: run the map subtree on the row
    interpreter and pipe its Arrow batches into the NATIVE shuffle writer
    via an ipc_reader — repartitioning, serde and the atomic commit stay
    on the engine path, so readers can't tell a degraded map output from
    a healthy one."""
    from blaze_tpu.columnar.arrow_io import batch_from_arrow
    from blaze_tpu.plan.to_proto import encode_schema
    from blaze_tpu.spark import fallback
    from blaze_tpu.spark.converters import bridge_schema

    sch = bridge_schema(stage.source)
    # qid-prefixed: concurrent queries run fallback tasks with the same
    # (sid, task) pair; the worker thread's trace context names the query
    qid = trace.current_context().get("query_id", "")
    rid = f"{qid}/__fallback_src:{stage.stage_id}:{task}"

    def provider(partition=task, nparts=ntasks):
        for rb in fallback.export_iterator(stage.source, partition, nparts):
            yield batch_from_arrow(rb, schema=sch)

    resources.put(rid, provider)
    try:
        node2 = pb.PlanNode()
        node2.CopyFrom(node)
        reader = pb.PlanNode()
        reader.ipc_reader.schema.CopyFrom(encode_schema(sch))
        reader.ipc_reader.provider_resource_id = rid
        reader.ipc_reader.num_partitions = ntasks
        node2.shuffle_writer.input.CopyFrom(reader)
        op = decode_plan(node2)
        # inherit the supervised task's commit gate (if any): a fallback
        # racing a speculative twin must still arbitrate the publish
        ctx = ExecContext(partition=task, num_partitions=ntasks,
                          commit_gate=supervisor_mod.current_commit_gate())
        list(execute_plan(op, ctx))
        return op
    finally:
        resources.pop(rid)


def _run_broadcast_stage(stage: Stage, stages: List[Stage],
                         sup: Supervisor, run_info=None,
                         ns: str = "") -> List[bytes]:
    # a broadcast stage runs ONE task but must see its upstream shuffles'
    # WHOLE output — a plan like broadcast(final_agg(exchange(...)))
    # would otherwise read only partition 0 and broadcast a quarter of
    # the relation (caught by the tpcds q01 catalogue cell)
    _rewrite_shuffle_readers_all(stage.plan, stages)
    frames: List[bytes] = []
    resources.put(f"{ns}broadcast_sink:{stage.stage_id}", frames.append)

    def attempt(ctx):
        del frames[:]  # a half-pushed earlier attempt must not leak frames
        op = decode_plan(stage.plan)
        list(execute_plan(op, ctx))
        return op

    fb = (None if stage.source is None else
          lambda: _fallback_broadcast_task(stage, stages, frames))
    # speculatable=False: both twins would push into the ONE frames sink
    sup.run_tasks(("broadcast", stage.stage_id), [TaskSpec(
        what=f"broadcast[{stage.stage_id}]", attempt_fn=attempt,
        partition=0, num_partitions=1, fallback_fn=fb,
        op_kinds=stage.op_kinds(), speculatable=False)])
    resources.put(f"{ns}broadcast:{stage.stage_id}",
                  lambda partition=0: iter(list(frames)))
    return frames


def _fallback_broadcast_task(stage: Stage, stages: List[Stage],
                             frames: List[bytes]) -> None:
    """Ladder rung 3 for a broadcast stage: the collect subtree runs on
    the row interpreter (reading ALL upstream shuffle partitions, like
    the native rewrite) and its batches are serialized into the same
    frame format the sink consumers replay."""
    from blaze_tpu.columnar import serde
    from blaze_tpu.columnar.arrow_io import batch_from_arrow
    from blaze_tpu.spark import fallback
    from blaze_tpu.spark.converters import bridge_schema

    del frames[:]
    src = _copy_tree_readers_all(stage.source, stages)
    sch = bridge_schema(src)
    for rb in fallback.export_iterator(src, 0, 1):
        frames.append(serde.serialize_batch(batch_from_arrow(rb,
                                                             schema=sch)))


def _copy_tree_readers_all(plan: SparkPlan, stages: List[Stage]) -> SparkPlan:
    """Copy a SparkPlan tree, pointing shuffle __IpcReaders at the
    all-partitions resource (the SparkPlan twin of
    _rewrite_shuffle_readers_all)."""
    from blaze_tpu.spark.aqe import _all_partitions_resource

    def whole(rid: str):
        local = local_resource_id(rid)
        if local.startswith("shuffle:") and not local.endswith(":all"):
            sid = int(local.split(":")[1])
            return _all_partitions_resource(
                rid, stages[sid].num_partitions), 1
        return None

    return _copy_tree_readers(plan, whole)


def _copy_tree_readers(plan: SparkPlan, remap) -> SparkPlan:
    """Copy a SparkPlan tree, each __IpcReader's (resource id, partitions)
    replaced by `remap(resource id)` where that gives one (copies because
    stage.source is shared with future retries)."""
    attrs = dict(plan.attrs)
    if plan.kind == "__IpcReader":
        new = remap(attrs.get("resource_id", ""))
        if new is not None:
            attrs["resource_id"], attrs["num_partitions"] = new
    return SparkPlan(plan.kind, plan.schema,
                     [_copy_tree_readers(c, remap) for c in plan.children],
                     attrs)


def _read_forms(node: pb.PlanNode) -> Dict[str, tuple]:
    """shuffle resource id -> (resource id, partitions) of the readers
    under `node` that read that shuffle whole or by coalesced ranges."""
    from blaze_tpu.spark.aqe import ipc_readers

    forms = {}
    for r in ipc_readers(node):
        base, _, how = r.provider_resource_id.rpartition(":")
        if how in ("all", "ranges"):
            forms[base] = (r.provider_resource_id,
                           1 if how == "all" else r.num_partitions)
    return forms


def _rewrite_shuffle_readers_all(node: pb.PlanNode,
                                 stages: List[Stage]) -> None:
    """Point every shuffle ipc_reader under `node` at the chained
    all-partitions resource (spark/aqe.py registers it on demand)."""
    from blaze_tpu.spark.aqe import _all_partitions_resource

    which = node.WhichOneof("node")
    if which is None:
        return
    if which == "ipc_reader":
        rid = node.ipc_reader.provider_resource_id
        local = local_resource_id(rid)
        if local.startswith("shuffle:") and not local.endswith(":all"):
            sid = int(local.split(":", 1)[1])
            node.ipc_reader.provider_resource_id = \
                _all_partitions_resource(rid, stages[sid].num_partitions)
        return
    inner = getattr(node, which)
    for fd, val in inner.ListFields():
        if fd.message_type is not None and \
                fd.message_type.name == "PlanNode":
            if fd.is_repeated:
                for child in val:
                    _rewrite_shuffle_readers_all(child, stages)
            else:
                _rewrite_shuffle_readers_all(val, stages)


def _fallback_result_task(stage: Stage, p: int, parts: int,
                          schema) -> List[ColumnBatch]:
    """Ladder rung 3 for one result-stage task: the full result subtree
    (including any root sort the native path strips for the host-ordered
    collect — re-sorting sorted rows is a no-op) runs on the row
    interpreter and comes back as one device batch."""
    from blaze_tpu.columnar.arrow_io import batch_from_arrow
    from blaze_tpu.spark import fallback

    df = fallback._execute(stage.source, p, parts)
    return [batch_from_arrow(fallback._to_arrow(df, schema), schema=schema)]


def _root_sort_split(op):
    """(specs, limit, strip_depth) for a host-ordered collect, or None.

    A root ORDER BY orders the driver COLLECT: the result is pulled to
    host anyway, so the ordering happens host-side during materialization
    (ops/host_sort.py) instead of compiling a full-input lax.sort. Shapes:
    a fetch-less root SortExec, or a GlobalLimit over (LocalLimit*) over
    a fetch-less SortExec. TakeOrdered (SortExec with fetch) keeps its
    device top-k fold — it bounds the pull — and merges host-side."""
    from blaze_tpu.ops.basic import GlobalLimitExec, LocalLimitExec
    from blaze_tpu.ops.sort import SortExec

    if isinstance(op, SortExec) and op.fetch is None:
        return list(op.specs), None, 1
    if isinstance(op, GlobalLimitExec):
        child = op.children[0]
        depth = 2
        while (isinstance(child, LocalLimitExec)
               and not isinstance(child, GlobalLimitExec)):
            child = child.children[0]
            depth += 1
        if isinstance(child, SortExec) and child.fetch is None:
            return list(child.specs), op.limit, depth
    return None


def _run_result_stage(stage: Stage, parts: int, sup: Supervisor,
                      run_info=None, resident=None) -> ColumnBatch:
    """`parts` is the upstream exchange's partition count (_input_tasks) —
    NOT the global default: an 8-way repartition read with 4 tasks would
    silently drop half the shuffle partitions."""
    from blaze_tpu.ops import host_sort
    from blaze_tpu.runtime.stage_compiler import try_run_stage

    op = decode_plan(stage.plan)
    split = (_root_sort_split(op)
             if host_sort.host_supported(op.schema) else None)
    strip = split[2] if split else 0

    from blaze_tpu.ops.parquet import ParquetSinkExec
    if (isinstance(op, ParquetSinkExec) and not op.is_remote()
            and (parts > 1 or os.path.isdir(op.path))):
        # stale-part overwrite semantics are a driver-side, before-any-
        # dispatch step: clearing from task 0 raced task scheduling and
        # could delete parts the current run had already written
        ParquetSinkExec.clear_stale_parts(op.path)

    op_kinds = stage.op_kinds()
    specs: List[TaskSpec] = []
    devs = _task_devices(stage, parts, resident) or [None] * parts
    for p in range(parts):
        def attempt(task_ctx):
            op_p = decode_plan(stage.plan)  # fresh operator state per task
            for _ in range(strip):
                op_p = op_p.children[0]
            staged = try_run_stage(op_p, task_ctx)
            if staged is not None:
                return [staged]
            return list(execute_plan(op_p, task_ctx))

        fb = (None if stage.source is None else
              lambda p=p: _fallback_result_task(stage, p, parts, op.schema))
        specs.append(TaskSpec(
            what=f"result[{stage.stage_id}:{p}]", attempt_fn=attempt,
            partition=p, num_partitions=parts, fallback_fn=fb,
            op_kinds=op_kinds, device=devs[p]))
    batches: List[ColumnBatch] = []
    for lst in sup.run_tasks(("result", stage.stage_id), specs):
        batches.extend(lst)
    # the collect layer: from the tasks' return to the result batch
    # (pulls, host sort, merge, re-upload)
    with trace.span("collect", partitions=parts, batches=len(batches),
                    host_sorted=split is not None) as sp:
        return _collect_result(stage, op, split, batches, parts, sup,
                               run_info, sp)


def _collect_result(stage: Stage, op, split, batches: List[ColumnBatch],
                    parts: int, sup: Supervisor, run_info,
                    sp) -> ColumnBatch:
    from blaze_tpu.columnar import serde
    from blaze_tpu.ops import host_sort
    from blaze_tpu.ops.basic import GlobalLimitExec
    from blaze_tpu.ops.sort import SortExec, truncate
    from blaze_tpu.ops.sort_keys import sort_batch

    if split is not None:
        specs, limit, _ = split
        if not batches:
            return ColumnBatch.empty(op.schema)

        def merge():
            # ordered collect: ONE pull per partition result, order +
            # truncate on host, hand the driver the host view (no second
            # pull). A pure function of `batches`, so a failed device
            # pull/upload mid-merge simply re-runs.
            hbs = [serde.to_host(b) for b in batches
                   if pull_rows(b, "collect.rows") > 0]
            if not hbs:
                return ColumnBatch.empty(op.schema)
            hb = host_sort.host_concat(hbs)
            perm = host_sort.sort_perm(hb, specs)
            if limit is not None:
                perm = perm[:limit]
            hb = host_sort.host_take(hb, perm)
            sp.set(rows=hb.num_rows)
            out = host_sort.host_to_device(hb)
            out._host_numpy = host_sort.host_to_pylike(hb)
            return out

        # the merge tail runs inline on the driver (it needs every
        # partition's batches) but still honors deadlines + the breaker
        return run_task_with_resilience(
            merge, what=f"result_merge[{stage.stage_id}]",
            run_info=run_info, deadline=sup.deadline(),
            on_error=sup.breaker.note_failure, session=sup.session)

    if not batches:
        return ColumnBatch.empty(op.schema)
    # placed tasks leave their results on their chips; the driver's merge
    # programs take them on one
    out = concat_batches([placement.put(b, placement.here())
                          for b in batches], op.schema)
    # Ordered collect for the remaining shapes (device path): a root
    # TakeOrdered (SortExec with fetch) sorted each partition with a
    # bounded top-k; merging the sorted partitions gives the total order
    # (the analog of Spark's range-partitioned global sort collect). A
    # GlobalLimit above a Project (no sort below) is an UNORDERED limit.
    if parts > 1:
        if isinstance(op, SortExec):
            out = sort_batch(out, op.specs)
            if op.fetch:
                out = truncate(out, op.fetch)
        elif isinstance(op, GlobalLimitExec):
            from blaze_tpu.ops.basic import LocalLimitExec

            child = op.children[0]
            while (isinstance(child, LocalLimitExec)
                   and not isinstance(child, GlobalLimitExec)):
                child = child.children[0]
            if isinstance(child, SortExec):
                out = sort_batch(out, child.specs)
            out = truncate(out, op.limit)
    return out
