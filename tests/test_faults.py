"""Fault-injection harness + resilience ladder: taxonomy classification,
deterministic injection schedules, retry/backoff, the degradation ladder
(halve batch -> force spill -> CPU fallback), crash-atomic artifact
commits with orphan reclamation, spill-page accounting, and chaos runs of
the validator queries under injected faults (every run must still match
the pandas oracle)."""

import errno
import os

import numpy as np
import pytest

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import ColumnBatch
from blaze_tpu.config import conf
from blaze_tpu.ops.base import TaskKilledError
from blaze_tpu.runtime import artifacts, faults
from blaze_tpu.runtime import memory as M
from blaze_tpu.runtime.executor import run_task_with_resilience


@pytest.fixture(autouse=True)
def _clean_faults():
    yield
    faults.install(None)
    faults.reset_telemetry()


# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("exc,cat", [
    (MemoryError("x"), "resource"),
    (RuntimeError("RESOURCE_EXHAUSTED: out of HBM"), "resource"),
    (RuntimeError("Out of memory while allocating"), "resource"),
    (OSError(errno.ECONNRESET, "reset"), "retryable"),
    (OSError(errno.EINTR, "interrupted"), "retryable"),
    (OSError(errno.ENOENT, "missing"), "fatal"),
    (RuntimeError("UNAVAILABLE: device link"), "retryable"),
    (NotImplementedError("no such op"), "plan"),
    (ValueError("boom"), "fatal"),
    (KeyError("k"), "fatal"),
    (TaskKilledError("killed"), "killed"),
    (faults.ResourceExhaustedError("x"), "resource"),
    (faults.RetryableError("x"), "retryable"),
])
def test_classify(exc, cat):
    assert faults.classify(exc) == cat


def test_ensure_classified_wraps_retryable():
    e = OSError(errno.ECONNRESET, "reset")
    w = faults.ensure_classified(e)
    assert isinstance(w, faults.RetryableError)
    assert w.__cause__ is e


def test_ensure_classified_leaves_fatal_unwrapped():
    # callers (and tests) matching ValueError/KeyError must keep working
    e = ValueError("boom")
    assert faults.ensure_classified(e) is e


def test_category_class_invariants():
    assert issubclass(faults.ResourceExhaustedError, faults.RetryableError)
    assert issubclass(faults.PlanError, NotImplementedError)
    for cat, cls in faults.CATEGORY_CLASSES.items():
        assert cls.category == cat


# ---------------------------------------------------------------------------
# injection registry
# ---------------------------------------------------------------------------


def _drive(point, n):
    fired = []
    for i in range(n):
        try:
            faults.inject(point)
        except faults.FaultError as e:
            fired.append((i, type(e).__name__))
    return fired


def test_inject_disabled_is_noop():
    faults.install(None)
    assert _drive("op.FilterExec", 50) == []
    assert faults.stats().get("faults_injected", 0) == 0


def test_inject_nth_fires_exactly_once():
    faults.install({"points": {"serde.encode": {"nth": 3, "kind": "io"}}})
    fired = _drive("serde.encode", 6)
    assert fired == [(2, "RetryableError")]
    assert faults.injection_log == [("serde.encode", 3)]


def test_inject_fail_times():
    faults.install({"points": {"spill.write": {"fail_times": 2}}})
    fired = _drive("spill.write", 5)
    assert [i for i, _ in fired] == [0, 1]


def test_inject_prefix_match():
    # a rule on "op" covers "op.<OperatorName>"
    faults.install({"points": {"op": {"nth": 2}}})
    try:
        faults.inject("op.SortExec")
    except faults.FaultError:
        pytest.fail("first call must pass")
    with pytest.raises(faults.RetryableError) as ei:
        faults.inject("op.HashJoinExec")
    assert ei.value.injected and ei.value.point == "op.HashJoinExec"


@pytest.mark.parametrize("kind,cls", [
    ("io", faults.RetryableError),
    ("oom", faults.ResourceExhaustedError),
    ("plan", faults.PlanError),
    ("fatal", faults.FatalError),
])
def test_inject_kind_maps_to_taxonomy(kind, cls):
    faults.install({"points": {"jit.compile": {"nth": 1, "kind": kind}}})
    with pytest.raises(cls):
        faults.inject("jit.compile")


def test_prob_schedule_deterministic_by_seed():
    spec = {"seed": 42, "points": {"op": {"prob": 0.3}}}
    faults.install(spec)
    _drive("op.ScanExec", 200)
    log_a = list(faults.injection_log)
    assert log_a, "p=.3 over 200 calls must fire"

    faults.install(spec)  # same seed: bit-identical replay
    _drive("op.ScanExec", 200)
    assert faults.injection_log == log_a

    faults.install({"seed": 43, "points": {"op": {"prob": 0.3}}})
    _drive("op.ScanExec", 200)
    assert faults.injection_log != log_a


def test_backoff_schedule_seeded_and_bounded():
    conf.retry_backoff_ms = 10
    try:
        faults.install({"seed": 7, "points": {}})
        seq = [faults.backoff_ms(a) for a in range(4)]
        for a, ms in enumerate(seq):
            assert 10 * (2 ** a) * 0.75 <= ms <= 10 * (2 ** a) * 1.25
        faults.install({"seed": 7, "points": {}})
        assert [faults.backoff_ms(a) for a in range(4)] == seq
    finally:
        conf.retry_backoff_ms = 10


# ---------------------------------------------------------------------------
# retry / ladder (run_task_with_resilience)
# ---------------------------------------------------------------------------


@pytest.fixture
def no_sleep(monkeypatch):
    slept = []
    monkeypatch.setattr(faults, "_sleep", slept.append)
    return slept


def test_retry_then_succeed(no_sleep):
    calls = []

    def attempt():
        calls.append(1)
        if len(calls) < 3:
            raise faults.RetryableError("flaky")
        return "ok"

    info = {}
    assert run_task_with_resilience(attempt, run_info=info) == "ok"
    assert len(calls) == 3 and info["retries"] == 2
    assert len(no_sleep) == 2
    # exponential: attempt-1 backoff window is twice attempt-0's
    assert 0.0075 <= no_sleep[0] <= 0.0125
    assert 0.015 <= no_sleep[1] <= 0.025


def test_retries_bounded(no_sleep):
    calls = []

    def attempt():
        calls.append(1)
        raise OSError(errno.ECONNRESET, "reset")

    old = conf.max_task_retries
    conf.max_task_retries = 2
    try:
        with pytest.raises(faults.RetryableError):
            run_task_with_resilience(attempt)
    finally:
        conf.max_task_retries = old
    assert len(calls) == 3  # initial + 2 retries


def test_fatal_relayed_immediately(no_sleep):
    def attempt():
        raise ValueError("boom")

    with pytest.raises(ValueError):
        run_task_with_resilience(attempt)
    assert no_sleep == []


def test_killed_never_retried(no_sleep):
    info = {}

    def attempt():
        raise TaskKilledError("stop")

    with pytest.raises(TaskKilledError):
        run_task_with_resilience(attempt, run_info=info)
    assert no_sleep == [] and "errors.killed" not in info


def test_ladder_rung1_halves_batch_target(no_sleep):
    seen = []
    old = conf.target_batch_bytes

    def attempt():
        seen.append(conf.target_batch_bytes)
        if len(seen) == 1:
            raise faults.ResourceExhaustedError("oom")
        return "ok"

    info = {}
    assert run_task_with_resilience(attempt, run_info=info) == "ok"
    assert seen[1] == max(old // 2, 1 << 20)
    assert conf.target_batch_bytes == old, "restored after the task"
    assert info["ladder_rung"] == 1 and info["degraded.halve_batch"] == 1


def test_ladder_rung2_forces_spill(no_sleep):
    class Probe:
        spills = 0

        def mem_used(self):
            return 1024

        def spill(self):
            Probe.spills += 1
            return 1024

    old_mgr = M._global
    mgr = M.init(1 << 30)
    mgr.register(Probe())
    calls = []

    def attempt():
        calls.append(1)
        if len(calls) < 3:
            raise faults.ResourceExhaustedError("oom")
        return "ok"

    info = {}
    try:
        assert run_task_with_resilience(attempt, run_info=info) == "ok"
    finally:
        M._global = old_mgr
    assert Probe.spills == 1
    assert info["ladder_rung"] == 2 and info["degraded.force_spill"] == 1


def test_ladder_rung3_routes_to_fallback(no_sleep):
    def attempt():
        raise faults.ResourceExhaustedError("oom")

    info = {}
    out = run_task_with_resilience(attempt, run_info=info,
                                   fallback=lambda: "fallback-result")
    assert out == "fallback-result"
    assert info["ladder_rung"] == 3
    assert info["task_fallbacks"] == 1
    assert info["errors.resource"] == 3


def test_ladder_exhausted_without_fallback(no_sleep):
    def attempt():
        raise MemoryError("oom")

    with pytest.raises(faults.ResourceExhaustedError):
        run_task_with_resilience(attempt)


def test_ladder_disabled_treats_resource_as_retryable(no_sleep):
    calls = []

    def attempt():
        calls.append(1)
        raise faults.ResourceExhaustedError("oom")

    old_ladder, old_retries = conf.enable_degradation_ladder, \
        conf.max_task_retries
    conf.enable_degradation_ladder = False
    conf.max_task_retries = 1
    try:
        with pytest.raises(faults.ResourceExhaustedError):
            run_task_with_resilience(attempt, fallback=lambda: "x")
    finally:
        conf.enable_degradation_ladder = old_ladder
        conf.max_task_retries = old_retries
    assert len(calls) == 2  # plain retry path, fallback never consulted


# ---------------------------------------------------------------------------
# crash-atomic artifacts + orphan reclamation
# ---------------------------------------------------------------------------


def test_commit_file_atomic(tmp_path):
    final = str(tmp_path / "out.bin")
    artifacts.commit_file(lambda p: open(p, "wb").write(b"payload"), final)
    assert open(final, "rb").read() == b"payload"
    assert artifacts.find_orphans([str(tmp_path)]) == []


def test_commit_shuffle_pair_crash_leaves_no_residue(tmp_path):
    data = str(tmp_path / "s_0_0.data")
    index = str(tmp_path / "s_0_0.index")
    faults.install({"points": {"shuffle.commit": {"nth": 1, "kind": "io"}}})

    def write(dp, ip):
        open(dp, "wb").write(b"dddd")
        open(ip, "wb").write(b"iiii")
        return [4]

    with pytest.raises(faults.RetryableError):
        artifacts.commit_shuffle_pair(write, data, index)
    # the simulated crash-at-commit leaves NEITHER final names nor temps
    assert not os.path.exists(data) and not os.path.exists(index)
    assert os.listdir(tmp_path) == []

    # the retry (fault consumed) commits both atomically
    lengths = artifacts.commit_shuffle_pair(write, data, index)
    assert lengths == [4]
    assert sorted(os.listdir(tmp_path)) == ["s_0_0.data", "s_0_0.index"]


def test_sweep_orphans_reclaims_dead_pids(tmp_path):
    dead = 1
    while artifacts._pid_alive(dead):  # find a pid that isn't running
        dead += 7919
    ours = tmp_path / f"a.data{artifacts.ORPHAN_TAG}{os.getpid()}.0"
    theirs = tmp_path / f"b.data{artifacts.ORPHAN_TAG}{dead}.0"
    spill = tmp_path / f"blz{dead}-xyz.spill"
    for p in (ours, theirs, spill):
        p.write_bytes(b"x")
    swept = artifacts.sweep_orphans([str(tmp_path)])
    assert len(swept) == 2
    assert ours.exists(), "a live writer's in-progress temp must survive"
    assert not theirs.exists() and not spill.exists()
    swept = artifacts.sweep_orphans([str(tmp_path)], include_self=True)
    assert len(swept) == 1 and not ours.exists()


# ---------------------------------------------------------------------------
# spill-page accounting (satellite: host spill pages vs. the budget)
# ---------------------------------------------------------------------------

_SCHEMA = T.Schema([T.Field("k", T.INT64), T.Field("v", T.FLOAT64)])


def _batch(n=64):
    return ColumnBatch.from_numpy({
        "k": np.arange(n, dtype=np.int64),
        "v": np.arange(n, dtype=np.float64)}, _SCHEMA)


def test_spill_pages_tracked_against_budget(tmp_path):
    old_mgr = M._global
    mgr = M.init(1 << 30)
    try:
        sf = M.SpillFile(_SCHEMA, dir=str(tmp_path), manager=mgr)
        n = sf.write(_batch())
        assert n > 0 and sf.pending_bytes == n
        assert mgr.spill_pages_pending() == n
        assert mgr.mem_used() >= n, "unflushed pages count against budget"
        assert mgr.host_spill_bytes == n and mgr.host_spill_files == 1

        out = list(sf.read())  # read flushes the pages first
        assert sf.pending_bytes == 0 and mgr.spill_pages_pending() == 0
        assert int(out[0].num_rows) == 64

        sf.write(_batch())
        freed = mgr.release(1)  # pressure flushes pages before consumers
        assert freed > 0 and mgr.spill_pages_pending() == 0

        sf.close()
        assert mgr.mem_used() == 0
    finally:
        M._global = old_mgr


def test_spill_file_untracked_on_gc(tmp_path):
    old_mgr = M._global
    mgr = M.init(1 << 30)
    try:
        sf = M.SpillFile(_SCHEMA, dir=str(tmp_path), manager=mgr)
        sf.write(_batch())
        del sf  # weakref tracking must never keep the file alive
        assert mgr.spill_pages_pending() == 0
    finally:
        M._global = old_mgr


# ---------------------------------------------------------------------------
# C ABI category codes
# ---------------------------------------------------------------------------


def test_native_category_codes_round_trip():
    from blaze_tpu.runtime import native_entry

    assert faults.NATIVE_CATEGORY_CODES["none"] == 0
    for cat, code in faults.NATIVE_CATEGORY_CODES.items():
        assert faults.NATIVE_CODE_CATEGORIES[code] == cat
        if cat == "none":
            continue
        exc = native_entry.exception_for_code(code, "msg")
        assert native_entry.error_category_code(exc) == code


def test_native_entry_codes_match_classify():
    from blaze_tpu.runtime import native_entry

    assert native_entry.error_category_code(MemoryError("x")) == 2
    assert native_entry.error_category_code(ValueError("x")) == 4
    assert native_entry.error_category_code(
        NotImplementedError("x")) == 3
    assert native_entry.error_category_code(TaskKilledError("x")) == 5


# ---------------------------------------------------------------------------
# chaos: validator queries under injected faults
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    from blaze_tpu.spark import validator

    d = str(tmp_path_factory.mktemp("chaos_tables"))
    return validator.generate_tables(d, rows=4000)


def _run_chaos(tables, tmp_path, query, mode, spec):
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


def test_chaos_q1_op_oom_recovers(tables, tmp_path):
    info = _run_chaos(tables, tmp_path, "q1_scan_filter_project", "bhj",
                      {"seed": 11, "points": {"op": {"nth": 2,
                                                     "kind": "oom"}}})
    assert info.get("faults_injected", 0) >= 1
    assert info.get("degradations", 0) >= 1


def test_chaos_q2_commit_fault_recovers(tables, tmp_path):
    info = _run_chaos(tables, tmp_path, "q2_q06_core_agg", "bhj",
                      {"seed": 12, "points": {"shuffle.commit":
                                              {"nth": 1, "kind": "io"}}})
    assert info.get("faults_injected", 0) >= 1
    assert info.get("retries", 0) >= 1


def test_chaos_q3_serde_fault_recovers(tables, tmp_path):
    info = _run_chaos(tables, tmp_path, "q3_join_agg_sort", "smj",
                      {"seed": 13, "points": {"serde.encode":
                                              {"nth": 1, "kind": "io"}}})
    assert info.get("faults_injected", 0) >= 1
    assert info.get("retries", 0) >= 1


def test_chaos_result_stage_fallback_rung3(tables, tmp_path):
    # 3 consecutive OOMs push one result task down the whole ladder to
    # the row interpreter; the answer must still match the oracle
    info = _run_chaos(tables, tmp_path, "q1_scan_filter_project", "bhj",
                      {"seed": 14, "points": {"op": {"fail_times": 3,
                                                     "kind": "oom"}}})
    assert info.get("ladder_rung", 0) == 3
    assert info.get("task_fallbacks", 0) == 1


def test_chaos_shuffle_map_fallback_rung3(tables, tmp_path):
    info = _run_chaos(tables, tmp_path, "q4_repartition_sort", "bhj",
                      {"seed": 15, "points": {"op": {"fail_times": 3,
                                                     "kind": "oom"}}})
    assert info.get("ladder_rung", 0) == 3
    assert info.get("task_fallbacks", 0) == 1


def test_chaos_broadcast_fallback_rung3(tables, tmp_path):
    info = _run_chaos(tables, tmp_path, "q3_join_agg_sort", "bhj",
                      {"seed": 16, "points": {"op": {"fail_times": 3,
                                                     "kind": "oom"}}})
    assert info.get("ladder_rung", 0) == 3
    assert info.get("task_fallbacks", 0) >= 1


# ---------------------------------------------------------------------------
# "stall" injection kind (ISSUE 3: the hang that never raises)
# ---------------------------------------------------------------------------


def test_inject_stall_delays_then_continues():
    import time as _time

    faults.install({"points": {"op": {"kind": "stall", "nth": 1,
                                      "ms": 60}}})
    t0 = _time.monotonic()
    faults.inject("op.ScanExec")  # a stall is a delay, not an error
    assert _time.monotonic() - t0 >= 0.05
    assert faults.stats().get("stalls_injected") == 1
    assert faults.stats().get("faults_injected") == 1
    faults.inject("op.ScanExec")  # nth=1: fires once


def test_inject_stall_interrupted_by_kill_flag():
    import time as _time
    import types as _types

    from blaze_tpu.runtime import supervisor as sup_mod

    att = sup_mod.TaskAttempt(
        _types.SimpleNamespace(deadline=None,
                               next_attempt_id=lambda: 1), False)
    att.kill(reason="hung")
    sup_mod._current.attempt = att
    try:
        faults.install({"points": {"op": {"kind": "stall", "nth": 1,
                                          "ms": 30_000}}})
        t0 = _time.monotonic()
        with pytest.raises(TaskKilledError):
            faults.inject("op.ScanExec")
        assert _time.monotonic() - t0 < 5.0, "kill must cut the stall short"
    finally:
        sup_mod._current.attempt = None


# ---------------------------------------------------------------------------
# deadline-aware backoff (the retry budget cannot outlive the deadline)
# ---------------------------------------------------------------------------


def test_retry_backoff_clamped_to_deadline(no_sleep):
    import time as _time

    def attempt():
        raise faults.RetryableError("flaky")

    old = conf.retry_backoff_ms
    conf.retry_backoff_ms = 60_000  # would sleep ~a minute unclamped
    try:
        with pytest.raises(faults.RetryableError):
            run_task_with_resilience(
                attempt, deadline=_time.monotonic() + 0.05)
    finally:
        conf.retry_backoff_ms = old
    assert no_sleep, "retryable failures must still back off"
    assert all(s <= 0.06 for s in no_sleep), \
        f"sleeps must be clamped to the remaining budget, got {no_sleep}"


def test_hang_relaunch_budgeted_separately_from_retries(no_sleep):
    # a watchdog kill-on-suspicion (HungError) must not drain the error
    # retry budget: 1 hang + max_task_retries real failures still wins
    errors = [faults.HungError("suspected hang"),
              faults.RetryableError("flaky"),
              faults.RetryableError("flaky")]

    def attempt():
        if errors:
            raise errors.pop(0)
        return "ok"

    old = conf.max_task_retries
    conf.max_task_retries = 2
    try:
        info = {}
        assert run_task_with_resilience(attempt, run_info=info) == "ok"
        assert info["retries"] == 3
    finally:
        conf.max_task_retries = old
    assert len(no_sleep) == 2, "hang relaunches skip the backoff sleep"


def test_retry_exhausted_by_deadline_reclassified(no_sleep):
    import time as _time

    def attempt():
        raise faults.RetryableError("flaky")

    # budget already spent: the would-be retry surfaces as DeadlineError
    # (fatal — the scheduler must NOT treat it as retryable again)
    with pytest.raises(faults.DeadlineError):
        run_task_with_resilience(attempt,
                                 deadline=_time.monotonic() - 1.0)
    assert no_sleep == []
    assert faults.classify(faults.DeadlineError("x")) == "fatal"
