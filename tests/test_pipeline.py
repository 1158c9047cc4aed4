"""Pipelined async execution (runtime/pipeline.py): ordered delivery vs
the serial stream, bounded queues, MemManager reservation/backpressure,
kill/deadline propagation through blocked producers, speculation-loser
teardown, pool-thread trace correlation, the write-side Sink, and e2e
equality of pipelined vs serial query runs on the pandas oracle."""

import threading
import time

import pytest

from blaze_tpu.config import conf
from blaze_tpu.ops.base import ExecContext, TaskKilledError
from blaze_tpu.runtime import faults
from blaze_tpu.runtime import memory as M
from blaze_tpu.runtime import pipeline, trace


@pytest.fixture(autouse=True)
def _clean_pipeline():
    saved = {k: getattr(conf, k) for k in
             ("enable_pipeline", "io_threads", "prefetch_batches",
              "trace_enabled")}
    yield
    for k, v in saved.items():
        setattr(conf, k, v)
    faults.install(None)
    faults.reset_telemetry()
    trace.reset()
    assert pipeline.live_streams() == 0


def _ctx(running=None, manager=None):
    return ExecContext(is_running=running or (lambda: True),
                       mem_manager=manager)


# ---------------------------------------------------------------------------
# ordering, exhaustion, error relay
# ---------------------------------------------------------------------------


def test_ordered_delivery_matches_serial():
    items = list(range(257))
    out = list(pipeline.prefetch(iter(items), 4))
    assert out == items
    assert pipeline.live_streams() == 0


def test_offload_applies_fn_in_order():
    out = list(pipeline.offload(iter(range(50)), lambda x: x * 3, 3))
    assert out == [x * 3 for x in range(50)]


def test_error_relays_after_preceding_items():
    def gen():
        yield 1
        yield 2
        raise ValueError("boom")

    s = pipeline.prefetch(gen(), 2)
    got = []
    with pytest.raises(ValueError, match="boom"):
        for x in s:
            got.append(x)
    # the serial stream would deliver both items before raising
    assert got == [1, 2]
    assert pipeline.live_streams() == 0


def test_pool_thread_error_stays_classifiable():
    def gen():
        yield 1
        raise faults.ResourceExhaustedError("hbm")

    s = pipeline.prefetch(gen(), 2)
    with pytest.raises(faults.ResourceExhaustedError) as ei:
        list(s)
    assert faults.classify(ei.value) == "resource"


def test_disabled_returns_serial_iterator():
    conf.enable_pipeline = False
    s = pipeline.prefetch(iter(range(5)))
    assert not isinstance(s, pipeline.PrefetchStream)
    assert list(s) == list(range(5))


def test_armed_nonconcurrent_fault_spec_forces_serial():
    faults.install({"seed": 1, "points": {}})
    assert not pipeline.enabled()
    faults.install({"seed": 1, "concurrent": True, "points": {}})
    assert conf.enable_pipeline and pipeline.enabled()


# ---------------------------------------------------------------------------
# bounded queue + memory backpressure
# ---------------------------------------------------------------------------


def test_queue_blocks_at_prefetch_batches():
    produced = []

    def gen():
        i = 0
        while True:
            produced.append(i)
            yield i
            i += 1

    s = pipeline.prefetch(gen(), 3)
    time.sleep(0.3)
    # depth 3 in the queue plus at most one in the pump's hand
    assert len(produced) <= 4, produced
    for _ in range(2):
        next(s)
    time.sleep(0.3)
    assert len(produced) <= 6, produced
    s.close()
    assert pipeline.live_streams() == 0


def test_memmanager_reservation_and_backpressure():
    mgr = M.MemManager(total=500)
    produced = []

    def gen():
        for i in range(100):
            produced.append(i)
            yield i

    # each 600B item alone exceeds the 500B budget: the producer must
    # hold at exactly ONE undelivered item (the always-one allowance)
    # instead of racing ahead through the 8-deep queue
    s = pipeline.prefetch(gen(), 8, manager=mgr, charge=lambda _: 600)
    time.sleep(0.3)
    assert mgr.pipeline_reserved == 600
    assert len(produced) == 1
    got = [next(s), next(s), next(s)]
    assert got == [0, 1, 2]
    s.close()
    assert mgr.pipeline_reserved == 0
    assert mgr.mem_used() == 0
    assert pipeline.live_streams() == 0


def test_backpressure_always_allows_one_item():
    # another consumer holds the WHOLE budget: the pipeline must still
    # make progress one item at a time instead of deadlocking
    mgr = M.MemManager(total=1000)

    class Hog(M.MemConsumer):
        def mem_used(self):
            return 5000

    mgr.register(Hog())
    s = pipeline.prefetch(iter(range(10)), 4, manager=mgr,
                          charge=lambda _: 100)
    assert list(s) == list(range(10))
    assert mgr.pipeline_reserved == 0


def test_close_mid_stream_releases_reservations():
    mgr = M.MemManager(total=1 << 30)
    s = pipeline.prefetch(iter(range(100)), 4, manager=mgr,
                          charge=lambda _: 1000)
    assert next(s) == 0
    time.sleep(0.1)
    assert mgr.pipeline_reserved > 0
    s.close()
    assert mgr.pipeline_reserved == 0
    assert pipeline.live_streams() == 0


# ---------------------------------------------------------------------------
# kill propagation + teardown
# ---------------------------------------------------------------------------


def test_kill_flag_propagates_through_blocked_producer():
    # the producer sits inside a slow source read; the kill must surface
    # on the CONSUMER within ~one poll tick, not after the source yields
    killed = threading.Event()
    entered = threading.Event()

    def gen():
        yield 0
        entered.set()
        time.sleep(1.0)  # "blocked" I/O
        yield 1

    ctx = _ctx(running=lambda: not killed.is_set())
    s = pipeline.prefetch(gen(), 2, ctx=ctx)
    assert next(s) == 0
    entered.wait(2.0)
    killed.set()
    t0 = time.monotonic()
    with pytest.raises(TaskKilledError):
        next(s)
        next(s)
    assert time.monotonic() - t0 < 0.9  # did not wait out the sleep
    s.close()
    assert pipeline.live_streams() == 0


def test_producer_side_kill_check():
    # kill flag already down at construction: the pump's own
    # ctx.check_running() raises on the pool thread and relays
    ctx = _ctx(running=lambda: False)
    s = pipeline.prefetch(iter(range(10)), 2, ctx=ctx)
    with pytest.raises(TaskKilledError):
        list(s)
    assert pipeline.live_streams() == 0


def test_speculation_loser_teardown():
    # a speculation loss is a TaskKilledError subclass raised by the
    # kill flag; the loser's streams must quiesce without leaking
    # threads or reservations (the winner already owns the output)
    from blaze_tpu.ops.base import SpeculationLostError

    mgr = M.MemManager(total=1 << 30)
    lost = threading.Event()

    def running():
        if lost.is_set():
            raise SpeculationLostError("lost the commit race")
        return True

    ctx = ExecContext(is_running=lambda: not lost.is_set(),
                      mem_manager=mgr)
    src = iter(range(1000))
    s = pipeline.prefetch(src, 4, ctx=ctx, manager=mgr,
                          charge=lambda _: 10)
    assert next(s) == 0
    lost.set()
    with pytest.raises(TaskKilledError):
        while True:
            next(s)
    s.close()
    assert mgr.pipeline_reserved == 0
    assert pipeline.live_streams() == 0
    # the pump is quiesced: no orphan production after teardown
    before = next(src)
    time.sleep(0.2)
    assert next(src) == before + 1


def test_deadline_kill_unblocks_full_queue_producer(monkeypatch):
    # producer blocked on a FULL queue + consumer gone: close() (the
    # count_stream finally in ops/base.py) must quiesce it promptly
    monkeypatch.setattr(conf, "prefetch_batches", 1)
    s = pipeline.prefetch(iter(range(1000)), 1)
    assert next(s) == 0
    time.sleep(0.1)
    t0 = time.monotonic()
    s.close()
    assert time.monotonic() - t0 < 5.0
    assert pipeline.live_streams() == 0


# ---------------------------------------------------------------------------
# trace correlation + occupancy stats
# ---------------------------------------------------------------------------


def test_trace_context_replayed_on_pool_thread():
    conf.trace_enabled = True
    trace.reset()
    seen = []

    def gen():
        # runs on the I/O pool: must observe the constructing thread's ids
        seen.append(trace.current_context())
        yield 1

    with trace.context(query_id="qP", stage_id=7, task_id="map[7:0]"):
        s = pipeline.prefetch(gen(), 2)
        assert list(s) == [1]
    assert seen[0].get("query_id") == "qP"
    assert seen[0].get("stage_id") == 7
    assert seen[0].get("task_id") == "map[7:0]"
    # the finalize stats event carries the same correlation ids
    stats = [r for r in trace.TRACE.snapshot()
             if r["kind"] == "pipeline_stats"]
    assert stats and stats[0]["query_id"] == "qP"
    assert stats[0]["stage_id"] == 7


def test_conf_is_one_value_on_the_pump_thread():
    """A value set with conf.update on the constructing thread is the
    value the producer reads on the I/O pool's thread."""
    conf.update(enable_pipeline=True, prefetch_batches=3)
    seen = []

    def gen():
        seen.append((threading.get_ident(), conf.prefetch_batches))
        yield 1

    assert list(pipeline.prefetch(gen(), 2)) == [1]
    (ident, value), = seen
    assert value == 3 and ident != threading.get_ident()


def test_occupancy_stats_and_histograms():
    conf.trace_enabled = True
    trace.reset()

    def gen():
        for i in range(5):
            time.sleep(0.01)
            yield i

    s = pipeline.prefetch(gen(), 2, name="t")
    assert list(s) == list(range(5))
    st = s.stats()
    assert st["items"] == 5
    assert 0.0 <= st["overlap_pct"] <= 100.0
    assert st["producer_busy_ms"] > 0
    hists = trace.histograms_snapshot()
    assert "pipeline_queue_depth" in hists
    assert "pipeline_overlap_pct" in hists


def test_explain_analyze_overlap_annotation():
    conf.trace_enabled = True
    trace.reset()
    with trace.span("stage", stage_id=1, stage_kind="shuffle_map"):
        trace.event("pipeline_stats", pipeline="t", items=4,
                    producer_busy_ms=10.0, consumer_wait_ms=2.5,
                    overlap_pct=75.0, max_depth=2)

    class _Op:
        children = ()

        def name(self):
            return "X"

        class metrics:
            @staticmethod
            def snapshot():
                return {}

    txt = trace.explain_analyze(_Op())
    assert "overlap=75%" in txt


# ---------------------------------------------------------------------------
# fault point io.prefetch
# ---------------------------------------------------------------------------


def test_io_prefetch_fires_on_pool_thread_and_classifies():
    faults.install({"seed": 3, "concurrent": True,
                    "points": {"io.prefetch": {"nth": 2, "kind": "io"}}})
    assert pipeline.enabled()
    s = pipeline.prefetch(iter(range(10)), 2)
    with pytest.raises(faults.RetryableError) as ei:
        list(s)
    assert ei.value.injected and ei.value.point == "io.prefetch"
    assert pipeline.live_streams() == 0


def test_io_prefetch_fires_on_serial_path_too():
    faults.install({"seed": 3,
                    "points": {"io.prefetch": {"nth": 2, "kind": "io"}}})
    assert not pipeline.enabled()  # non-concurrent spec forces serial
    s = pipeline.prefetch(iter(range(10)), 2)
    with pytest.raises(faults.RetryableError):
        list(s)


def test_io_prefetch_in_known_points():
    assert "io.prefetch" in faults.KNOWN_POINTS


# ---------------------------------------------------------------------------
# write-side Sink
# ---------------------------------------------------------------------------


def test_sink_preserves_submit_order():
    out = []
    sk = pipeline.Sink(out.append, 2)
    for i in range(100):
        sk.submit(i)
    sk.close()
    assert out == list(range(100))
    assert pipeline.live_streams() == 0


def test_sink_error_relays_to_submitter():
    def bad(_):
        raise faults.RetryableError("disk")

    sk = pipeline.Sink(bad, 2)
    with pytest.raises(faults.RetryableError):
        for i in range(50):
            sk.submit(i)
        sk.close()
    assert pipeline.live_streams() == 0


def test_sink_abort_discards_and_releases():
    mgr = M.MemManager(total=1 << 30)
    slow = threading.Event()

    def fn(_):
        slow.wait(0.05)

    sk = pipeline.Sink(fn, 4, manager=mgr)
    for i in range(4):
        sk.submit(i, nbytes=100)
    sk.abort()
    assert mgr.pipeline_reserved == 0
    assert pipeline.live_streams() == 0
    sk.abort()  # idempotent


def test_sink_inline_when_disabled():
    conf.enable_pipeline = False
    out = []
    sk = pipeline.Sink(out.append, 2)
    sk.submit(1)
    assert out == [1]  # synchronous
    sk.close()
    sk.abort()


# ---------------------------------------------------------------------------
# e2e: pipelined run equals the serial run equals the pandas oracle
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    from blaze_tpu.spark import validator

    d = str(tmp_path_factory.mktemp("pipeline_tables"))
    return validator.generate_tables(d, rows=4000)


@pytest.mark.parametrize("query,mode", [
    ("q2_q06_core_agg", "bhj"),
    ("q3_join_agg_sort", "smj"),
    ("q4_repartition_sort", "bhj"),
])
def test_e2e_pipelined_matches_oracle(tables, tmp_path, query, mode):
    from blaze_tpu.spark import validator
    from blaze_tpu.spark.local_runner import run_plan

    paths, frames = tables
    plan, oracle = validator.QUERIES[query](paths, frames, mode)
    info = {}
    out = run_plan(plan, num_partitions=4, work_dir=str(tmp_path),
                   mesh_exchange="off", run_info=info)
    diff = validator._compare(
        validator._to_pandas(out).reset_index(drop=True),
        oracle().reset_index(drop=True))
    assert diff is None, diff
    assert info.get("pipeline_streams", 0) > 0  # pipelining actually ran
    assert info.get("pipeline_live_streams") == 0
    assert M.get_manager().pipeline_reserved == 0


def test_e2e_serial_equals_pipelined(tables, tmp_path):
    from blaze_tpu.spark import validator
    from blaze_tpu.spark.local_runner import run_plan

    paths, frames = tables
    results = []
    for on in (True, False):
        conf.enable_pipeline = on
        plan, oracle = validator.QUERIES["q3_join_agg_sort"](
            paths, frames, "smj")
        out = run_plan(plan, num_partitions=4,
                       work_dir=str(tmp_path / f"p{on}"),
                       mesh_exchange="off")
        results.append(
            validator._to_pandas(out).reset_index(drop=True))
    import pandas as pd

    pd.testing.assert_frame_equal(results[0], results[1])


def test_e2e_chaos_io_prefetch_recovers(tables, tmp_path):
    # an io fault on the pool thread at the queue hand-off must be
    # classified, retried by the ladder, and the answer still exact
    from blaze_tpu.runtime import artifacts
    from blaze_tpu.spark import validator
    from blaze_tpu.spark.local_runner import run_plan

    paths, frames = tables
    plan, oracle = validator.QUERIES["q2_q06_core_agg"](
        paths, frames, "bhj")
    faults.install({"seed": 21, "concurrent": True,
                    "points": {"io.prefetch": {"nth": 3, "kind": "io"}}})
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
    assert info.get("faults_injected", 0) >= 1
    assert info.get("retries", 0) >= 1
    assert info.get("pipeline_live_streams") == 0
    assert artifacts.find_orphans([str(tmp_path)]) == []
    assert M.get_manager().pipeline_reserved == 0
