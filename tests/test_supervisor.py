"""Task supervisor: pool + heartbeats, hang detection, task/query
deadlines, straggler speculation with first-commit-wins, the per-operator
circuit breaker, kill-flag cooperation across the execution paths (fused
chains, whole-stage, native ABI), and the crash-atomic commit gate."""

import os
import threading
import time
import types

import numpy as np
import pytest

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import ColumnBatch
from blaze_tpu.config import conf
from blaze_tpu.ops.base import (
    ExecContext,
    MapLikeOp,
    Operator,
    SpeculationLostError,
    TaskKilledError,
)
from blaze_tpu.runtime import artifacts, faults
from blaze_tpu.runtime import supervisor as sup_mod
from blaze_tpu.runtime.supervisor import (
    CircuitBreaker,
    CommitGate,
    Supervisor,
    TaskSpec,
)


@pytest.fixture(autouse=True)
def _clean_supervisor_conf():
    saved = {k: getattr(conf, k) for k in (
        "enable_supervisor", "max_concurrent_tasks", "task_deadline_ms",
        "query_deadline_ms", "hang_detect_ms", "speculation_multiplier",
        "breaker_failure_threshold", "max_task_retries",
        "retry_backoff_ms")}
    yield
    for k, v in saved.items():
        setattr(conf, k, v)
    faults.install(None)
    faults.reset_telemetry()


# ---------------------------------------------------------------------------
# commit gate (first-commit-wins)
# ---------------------------------------------------------------------------


def test_commit_gate_first_claim_wins():
    g = CommitGate()
    assert g.claim() is True
    assert g.claim() is False
    g.abort()  # a failed publisher releases the gate for the retry
    assert g.claim() is True


def test_commit_shuffle_pair_gate_loser_aborts(tmp_path):
    data = str(tmp_path / "s.data")
    index = str(tmp_path / "s.index")
    gate = CommitGate()

    def write(payload):
        def w(dp, ip):
            open(dp, "wb").write(payload)
            open(ip, "wb").write(b"i")
            return [len(payload)]
        return w

    assert artifacts.commit_shuffle_pair(write(b"winner"), data, index,
                                         gate=gate) == [6]
    with pytest.raises(SpeculationLostError):
        artifacts.commit_shuffle_pair(write(b"loser!"), data, index,
                                      gate=gate)
    # exactly one committed pair, the winner's, and no temps left behind
    assert open(data, "rb").read() == b"winner"
    assert sorted(os.listdir(tmp_path)) == ["s.data", "s.index"]


def test_commit_gate_released_when_publish_fails(tmp_path):
    data = str(tmp_path / "d" / "s.data")  # missing dir: os.replace fails
    index = str(tmp_path / "d" / "s.index")
    gate = CommitGate()

    def write(dp, ip):
        open(dp, "wb").write(b"x")
        open(ip, "wb").write(b"i")
        return [1]

    with pytest.raises(OSError):
        artifacts.commit_shuffle_pair(write, data, index, gate=gate)
    # the claim was rolled back: the surviving lineage can still commit
    assert gate.claim() is True


# ---------------------------------------------------------------------------
# orphan-sweep lockfile
# ---------------------------------------------------------------------------


def test_sweep_skips_directory_locked_by_live_process(tmp_path):
    dead = 1
    while artifacts._pid_alive(dead):
        dead += 7919
    orphan = tmp_path / f"a.data{artifacts.ORPHAN_TAG}{dead}.0"
    orphan.write_bytes(b"x")
    lock = tmp_path / artifacts.SWEEP_LOCK
    lock.write_text(str(os.getpid()))  # "another" live sweeper holds it
    assert artifacts.sweep_orphans([str(tmp_path)]) == []
    assert orphan.exists()
    lock.unlink()
    assert len(artifacts.sweep_orphans([str(tmp_path)])) == 1


def test_sweep_breaks_stale_lock_of_dead_sweeper(tmp_path):
    dead = 1
    while artifacts._pid_alive(dead):
        dead += 7919
    orphan = tmp_path / f"a.data{artifacts.ORPHAN_TAG}{dead}.0"
    orphan.write_bytes(b"x")
    (tmp_path / artifacts.SWEEP_LOCK).write_text(str(dead))
    swept = artifacts.sweep_orphans([str(tmp_path)])
    assert len(swept) == 1 and not orphan.exists()
    assert not (tmp_path / artifacts.SWEEP_LOCK).exists()


def test_sweep_lock_never_treated_as_orphan():
    assert artifacts._orphan_pid(artifacts.SWEEP_LOCK) == -1


# ---------------------------------------------------------------------------
# kill-flag cooperation
# ---------------------------------------------------------------------------

_SCHEMA = T.Schema([T.Field("k", T.INT64)])


def _batch(n=8):
    return ColumnBatch.from_numpy(
        {"k": np.arange(n, dtype=np.int64)}, _SCHEMA)


class _Src(Operator):
    def __init__(self, batches):
        super().__init__([])
        self._batches = batches

    @property
    def schema(self):
        return _SCHEMA

    def execute(self, ctx):
        yield from self._batches


class _Identity(MapLikeOp):
    @property
    def schema(self):
        return self.child.schema

    def make_batch_fn(self):
        return lambda b: b


def test_kill_flag_stops_fused_chain_at_batch_boundary():
    op = _Identity(_Src([_batch(), _batch(), _batch()]))
    checks = [1]  # allow exactly one batch-boundary check

    def is_running():
        checks[0] -= 1
        return checks[0] >= 0

    got = []
    with pytest.raises(TaskKilledError):
        for b in op.execute(ExecContext(is_running=is_running)):
            got.append(b)
    assert len(got) == 1, "killed at the SECOND batch boundary"


def test_kill_flag_stops_whole_stage_capture():
    from blaze_tpu.ops.basic import RenameColumnsExec
    from blaze_tpu.runtime.stage_compiler import try_run_stage

    op = RenameColumnsExec(_Src([_batch()]), ["k2"])
    with pytest.raises(TaskKilledError):
        try_run_stage(op, ExecContext(is_running=lambda: False))


def test_native_entry_kill_flag_round_trip():
    from blaze_tpu.runtime import native_entry as NE

    NE.clear_kill()
    ctx = NE._native_ctx(0)
    assert ctx.is_running() and not NE.kill_requested()
    assert NE.kill_state() == b"\x00"
    NE.request_kill()
    assert NE.kill_requested() and NE.kill_state() == b"\x01"
    with pytest.raises(TaskKilledError):
        ctx.check_running()
    NE.clear_kill()
    assert not NE.kill_requested()


def test_native_abi_kill_flag():
    from blaze_tpu import native as N
    from blaze_tpu.runtime import native_entry as NE

    if not N.available():
        pytest.skip("native library not built")
    NE.clear_kill()
    try:
        N.request_kill()  # C ABI -> embedded python -> shared flag
        assert NE.kill_requested()
        assert N.kill_requested()
        N.clear_kill()
        assert not NE.kill_requested()
        assert not N.kill_requested()
    finally:
        NE.clear_kill()


# ---------------------------------------------------------------------------
# supervisor unit behavior
# ---------------------------------------------------------------------------


def test_pool_serialized_while_nonconcurrent_spec_armed():
    conf.max_concurrent_tasks = 4
    faults.install({"points": {"op": {"nth": 10 ** 9}}})
    assert Supervisor()._pool_width() == 1
    faults.install({"concurrent": True, "points": {"op": {"nth": 10 ** 9}}})
    assert Supervisor()._pool_width() == 4
    faults.install(None)
    assert Supervisor()._pool_width() == 4


def test_run_tasks_ordered_results_and_concurrency():
    conf.max_concurrent_tasks = 4
    sup = Supervisor()
    peak = [0]
    live = [0]
    lock = threading.Lock()

    def attempt(ctx):
        with lock:
            live[0] += 1
            peak[0] = max(peak[0], live[0])
        time.sleep(0.05)
        with lock:
            live[0] -= 1
        return ctx.partition * 10

    try:
        specs = [TaskSpec(what=f"t{i}", attempt_fn=attempt, partition=i,
                          num_partitions=4) for i in range(4)]
        assert sup.run_tasks("s", specs) == [0, 10, 20, 30]
    finally:
        sup.close()
    assert peak[0] > 1, "tasks must actually overlap on the pool"


def test_conf_is_one_value_on_every_task_thread():
    """conf.<knob> is a plain attribute: what the driver thread set with
    conf.update is what a supervisor task reads on its pool thread, with
    nothing captured or replayed."""
    assert type(conf).__getattribute__ is object.__getattribute__
    conf.update(max_concurrent_tasks=4, retry_backoff_ms=7)
    sup = Supervisor()
    driver = threading.get_ident()

    def attempt(ctx):
        time.sleep(0.02)    # so the pool spreads the four over threads
        return threading.get_ident(), conf.retry_backoff_ms

    try:
        specs = [TaskSpec(what=f"t{i}", attempt_fn=attempt, partition=i,
                          num_partitions=4) for i in range(4)]
        seen = sup.run_tasks("s", specs)
    finally:
        sup.close()
    assert [v for _, v in seen] == [7] * 4
    assert any(ident != driver for ident, _ in seen)


def test_first_task_error_kills_siblings():
    conf.max_concurrent_tasks = 4
    sup = Supervisor()
    killed = threading.Event()

    def bad(ctx):
        time.sleep(0.02)
        raise ValueError("boom")

    def slow(ctx):
        for _ in range(200):
            if not ctx.is_running():
                killed.set()
                ctx.check_running()
            time.sleep(0.01)
        return "finished"

    try:
        with pytest.raises(ValueError):
            sup.run_tasks("s", [
                TaskSpec(what="bad", attempt_fn=bad),
                TaskSpec(what="slow", attempt_fn=slow),
            ])
    finally:
        sup.close()
    assert killed.wait(2.0), "sibling must be cooperatively cancelled"


def test_hang_detection_relaunches_attempt():
    conf.hang_detect_ms = 120
    conf.max_concurrent_tasks = 2
    sup = Supervisor(run_info := {})
    calls = []

    def attempt(ctx):
        calls.append(1)
        if len(calls) == 1:
            # stop heartbeating without finishing: a cooperative wedge.
            # The watchdog kill sets the attempt's event; we surface it
            # like a batch-boundary check would.
            ev = sup_mod.current_kill_event()
            assert ev is not None
            if ev.wait(10.0):
                ctx.check_running()
            pytest.fail("watchdog never killed the hung attempt")
        return "ok"

    t0 = time.monotonic()
    try:
        assert sup.run_tasks("s", [TaskSpec(what="t", attempt_fn=attempt)]) \
            == ["ok"]
    finally:
        sup.close()
    assert run_info.get("hangs_detected", 0) == 1
    assert run_info.get("retries", 0) == 1
    # detection within hang_detect_ms plus watchdog tick slack
    assert time.monotonic() - t0 < 2.0


def test_task_deadline_raises_deadline_error():
    conf.task_deadline_ms = 150
    sup = Supervisor()

    def attempt(ctx):
        for _ in range(500):
            ctx.check_running()
            time.sleep(0.01)
        return "finished"

    t0 = time.monotonic()
    try:
        with pytest.raises(faults.DeadlineError):
            sup.run_tasks("s", [TaskSpec(what="t", attempt_fn=attempt)])
    finally:
        sup.close()
    assert time.monotonic() - t0 < 3.0


def test_noncooperative_task_abandoned_at_deadline():
    conf.task_deadline_ms = 150
    sup = Supervisor()
    release = threading.Event()

    def attempt(ctx):
        release.wait(20.0)  # ignores the kill flag entirely
        return "late"

    t0 = time.monotonic()
    try:
        with pytest.raises(faults.DeadlineError):
            sup.run_tasks("s", [TaskSpec(what="t", attempt_fn=attempt)])
    finally:
        release.set()  # let the abandoned thread exit
        sup.close()
    assert time.monotonic() - t0 < sup._ABANDON_GRACE + 2.0


def test_speculation_first_commit_wins(tmp_path):
    conf.speculation_multiplier = 2.0
    conf.max_concurrent_tasks = 2
    sup = Supervisor(run_info := {})
    # seed the stage's duration stats so the straggler threshold exists
    sup._record_duration("s", 0.02)
    sup._record_duration("s", 0.02)
    data, index = str(tmp_path / "t.data"), str(tmp_path / "t.index")
    attempts = []

    def attempt(ctx):
        attempts.append(ctx)
        me = len(attempts)
        if me == 1:
            # primary straggles until killed by the winning twin
            for _ in range(2000):
                ctx.check_running()
                time.sleep(0.005)
            pytest.fail("primary was never killed")
        payload = b"twin"

        def write(dp, ip):
            open(dp, "wb").write(payload)
            open(ip, "wb").write(b"i")
            return [len(payload)]

        artifacts.commit_shuffle_pair(write, data, index,
                                      gate=ctx.commit_gate)
        return "twin-result"

    try:
        out = sup.run_tasks("s", [TaskSpec(what="t", attempt_fn=attempt)])
    finally:
        sup.close()
    assert out == ["twin-result"]
    assert run_info.get("speculations_launched") == 1
    assert run_info.get("speculations_won") == 1
    assert open(data, "rb").read() == b"twin"
    assert artifacts.find_orphans([str(tmp_path)]) == []


def test_breaker_trips_after_threshold_and_reroutes():
    conf.breaker_failure_threshold = 2
    br = CircuitBreaker(info := {})

    def err(point):
        e = faults.RetryableError("x")
        e.point = point
        return e

    br.note_failure(err("op.FooExec"), "retryable")
    assert br.tripped() == frozenset()
    br.note_failure(err("op.FooExec"), "retryable")
    assert br.tripped() == frozenset({"FooExec"})
    assert br.should_reroute(frozenset({"FooExec", "SortExec"}))
    assert not br.should_reroute(frozenset({"BarExec"}))
    assert info.get("breaker_trips") == 1
    # unattributable failures never count
    br.note_failure(ValueError("no point"), "fatal")
    br.note_failure(err("spill.write"), "retryable")
    assert br.tripped() == frozenset({"FooExec"})


def test_breaker_reroutes_doomed_task_to_fallback():
    conf.breaker_failure_threshold = 2
    conf.max_task_retries = 3
    conf.retry_backoff_ms = 0
    sup = Supervisor(run_info := {})

    def attempt(ctx):
        e = faults.RetryableError("always down")
        e.point = "op.FooExec"
        raise e

    try:
        out = sup.run_tasks("s", [TaskSpec(
            what="t", attempt_fn=attempt, fallback_fn=lambda: "fb",
            op_kinds=frozenset({"FooExec"}))])
    finally:
        sup.close()
    assert out == ["fb"]
    assert run_info.get("breaker_trips") == 1
    assert run_info.get("breaker_reroutes", 0) >= 1


def test_supervisor_disabled_runs_sequential():
    conf.enable_supervisor = False
    sup = Supervisor()
    main_thread = threading.current_thread()
    seen = []

    def attempt(ctx):
        seen.append(threading.current_thread())
        return ctx.partition

    try:
        assert sup.run_tasks("s", [
            TaskSpec(what="a", attempt_fn=attempt, partition=0),
            TaskSpec(what="b", attempt_fn=attempt, partition=1),
        ]) == [0, 1]
    finally:
        sup.close()
    assert all(t is main_thread for t in seen)
    assert sup._pool is None, "disabled path must never build a pool"


# ---------------------------------------------------------------------------
# integration: validator queries under the supervised pool
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    from blaze_tpu.spark import validator

    d = str(tmp_path_factory.mktemp("supervisor_tables"))
    return validator.generate_tables(d, rows=3000)


def _run_query(tables, tmp_path, query, mode, spec=None):
    from blaze_tpu.spark import validator
    from blaze_tpu.spark.local_runner import run_plan

    paths, frames = tables
    plan, oracle = validator.QUERIES[query](paths, frames, mode)
    faults.install(spec)
    info = {}
    try:
        out = run_plan(plan, num_partitions=4, work_dir=str(tmp_path),
                       mesh_exchange="off", run_info=info)
    finally:
        faults.install(None)
    diff = validator._compare(
        validator._to_pandas(out).reset_index(drop=True),
        oracle().reset_index(drop=True))
    assert diff is None, diff
    assert artifacts.find_orphans([str(tmp_path)]) == []
    return info


def test_concurrent_pool_matches_oracle(tables, tmp_path):
    conf.max_concurrent_tasks = 4
    info = _run_query(tables, tmp_path, "q3_join_agg_sort", "smj")
    assert info.get("file_stages", 0) >= 1


def test_stall_hang_detected_and_recovered(tables, tmp_path):
    conf.hang_detect_ms = 250
    t0 = time.monotonic()
    info = _run_query(
        tables, tmp_path, "q2_q06_core_agg", "bhj",
        {"seed": 21, "points": {"op": {"kind": "stall", "nth": 3,
                                       "ms": 30_000}}})
    assert info.get("faults_injected", 0) >= 1
    assert info.get("hangs_detected", 0) >= 1
    assert info.get("retries", 0) >= 1
    # a 30s stall must not cost 30s: detection within hang_detect_ms
    # (plus compile/retry time, far under the stall length)
    assert time.monotonic() - t0 < 20.0


def test_speculative_twin_beats_stalled_straggler(tables, tmp_path):
    from blaze_tpu.spark import validator
    from blaze_tpu.spark.local_runner import run_plan

    paths, frames = tables
    # warm the jit caches so attempt durations reflect execution
    plan, _ = validator.QUERIES["q3_join_agg_sort"](paths, frames, "smj")
    run_plan(plan, num_partitions=4, mesh_exchange="off")

    conf.speculation_multiplier = 3.0
    conf.max_concurrent_tasks = 4
    t0 = time.monotonic()
    info = _run_query(
        tables, tmp_path, "q3_join_agg_sort", "smj",
        {"seed": 22, "concurrent": True,
         "points": {"op": {"kind": "stall", "nth": 6, "ms": 15_000}}})
    assert info.get("speculations_launched", 0) >= 1
    assert info.get("speculations_won", 0) >= 1
    assert time.monotonic() - t0 < 12.0, "twin must beat the 15s stall"


def test_query_deadline_enforced(tables, tmp_path):
    from blaze_tpu.spark import validator
    from blaze_tpu.spark.local_runner import run_plan

    paths, frames = tables
    plan, _ = validator.QUERIES["q1_scan_filter_project"](paths, frames,
                                                          "bhj")
    faults.install({"seed": 23, "points": {"op": {"kind": "stall",
                                                  "nth": 1, "ms": 30_000}}})
    conf.query_deadline_ms = 800
    t0 = time.monotonic()
    try:
        with pytest.raises(faults.DeadlineError):
            run_plan(plan, num_partitions=4, work_dir=str(tmp_path),
                     mesh_exchange="off", run_info={})
    finally:
        faults.install(None)
    assert time.monotonic() - t0 < 10.0


def test_breaker_recovers_persistently_failing_operator(tables, tmp_path):
    conf.breaker_failure_threshold = 2
    info = _run_query(
        tables, tmp_path, "q2_q06_core_agg", "bhj",
        {"seed": 24, "points": {"op.ParquetScanExec":
                                {"kind": "io", "fail_times": 10 ** 9}}})
    assert info.get("breaker_trips", 0) == 1
    assert info.get("breaker_reroutes", 0) >= 1
