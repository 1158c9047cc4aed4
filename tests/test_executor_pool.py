"""Process-isolated executors (ISSUE 12): crash containment, epoch-fenced
recovery, and graceful capacity degradation.

The headline robustness property under test: a task attempt that outlives
its epoch (a zombie — the executor was declared dead on heartbeat but the
process kept running) must have its late result REJECTED at the fence. It
must not overwrite the retried attempt's shuffle artifact (epoch-stamped
names make the overwrite impossible by construction; the sweep removes the
loser) and must not double-count in the ledger (tasks_done counts each key
once per batch).

Pool startup costs ~2-3s (workers import jax); the kill/zombie tests each
spin a dedicated pool so death counters start from zero.
"""

import os
import socket
import time

import numpy as np
import pytest

from blaze_tpu.config import conf
from blaze_tpu.runtime import artifacts
from blaze_tpu.runtime import executor_pool as ep
from blaze_tpu.runtime import shuffle_server as ss


# ---------------------------------------------------------------------------
# wire format
# ---------------------------------------------------------------------------


def test_wire_roundtrip_header_and_blob():
    a, b = socket.socketpair()
    try:
        blob = os.urandom(200_000)
        ss.send_msg(a, {"type": "task", "k": [1, 2, 3]}, blob)
        msg, got = ss.recv_msg(b)
        assert msg == {"type": "task", "k": [1, 2, 3]}
        assert got == blob
        # empty-blob control message
        ss.send_msg(b, {"type": "ping"})
        msg, got = ss.recv_msg(a)
        assert msg == {"type": "ping"} and got == b""
    finally:
        a.close()
        b.close()


def test_wire_rejects_bad_magic():
    a, b = socket.socketpair()
    try:
        a.sendall(b"XXXX" + b"\x00" * 12)
        with pytest.raises(ss.WireError):
            ss.recv_msg(b)
    finally:
        a.close()
        b.close()


def test_shuffle_server_fetch_roundtrip(tmp_path):
    """Register epoch-stamped .data/.index artifacts; a client must read
    back exactly the per-partition segments that were written."""
    parts = [b"alpha", b"", b"gamma" * 100]
    data = b"".join(parts)
    offs = np.zeros(len(parts) + 1, dtype="<u8")
    np.cumsum([len(p) for p in parts], out=offs[1:])
    dp, ip = str(tmp_path / "m0.data"), str(tmp_path / "m0.index")
    with open(dp, "wb") as f:
        f.write(data)
    with open(ip, "wb") as f:
        f.write(offs.tobytes())

    server = ss.ShuffleServer(str(tmp_path / "shf.sock"))
    server.start()
    try:
        server.register_shuffle("shuffle:0", [(dp, ip)])
        server.register_frames("broadcast:1", [b"f1", b"f22"])
        client = ss.ShuffleClient(server.sock_path)
        try:
            for pid, want in enumerate(parts):
                assert client.fetch("shuffle:0", pid) == want
            assert client.fetch("broadcast:1", 0) == b"f1f22"
            with pytest.raises(KeyError):
                client.fetch("shuffle:missing", 0)
        finally:
            client.close()
    finally:
        server.close()


# ---------------------------------------------------------------------------
# epoch stamping + fence (the zombie-rejection substrate)
# ---------------------------------------------------------------------------


def test_epoch_stamp_and_sweep(tmp_path):
    base = str(tmp_path / "s0_m1.data")
    e1 = artifacts.stamp_epoch(base, 1)
    e2 = artifacts.stamp_epoch(base, 2)
    assert e1 != e2 != base
    assert artifacts.epoch_of(e1) == 1 and artifacts.epoch_of(e2) == 2
    assert artifacts.epoch_of(base) == 0
    assert artifacts.stamp_epoch(base, 0) == base
    # zombie (epoch 1) and winner (epoch 2) write DIFFERENT paths — the
    # late attempt cannot overwrite the retried attempt's artifact
    with open(e1, "wb") as f:
        f.write(b"zombie")
    with open(e2, "wb") as f:
        f.write(b"winner")
    idx1 = artifacts.stamp_epoch(str(tmp_path / "s0_m1.index"), 1)
    with open(idx1, "wb") as f:
        f.write(b"zidx")
    artifacts.sweep_stale_epochs(base, str(tmp_path / "s0_m1.index"), 2)
    assert not os.path.exists(e1) and not os.path.exists(idx1)
    with open(e2, "rb") as f:
        assert f.read() == b"winner"


def test_epoch_fence_rejects_stale_and_forgotten():
    fence = artifacts.EpochFence()
    e1 = fence.advance("t1")
    e2 = fence.advance("t1")
    assert e2 == e1 + 1
    assert not fence.admit("t1", e1)       # zombie attempt: rejected
    assert fence.admit("t1", e2)           # current attempt: admitted
    assert fence.fenced_total == 1
    fence.forget("t1")
    # a straggler after batch teardown still mismatches (missing == 0)
    assert not fence.admit("t1", e2)
    assert fence.fenced_total == 2


# ---------------------------------------------------------------------------
# pool lifecycle + dispatch
# ---------------------------------------------------------------------------


@pytest.fixture
def fast_death_conf():
    saved = {k: getattr(conf, k) for k in
             ("executor_death_ms", "executor_heartbeat_ms",
              "executor_restart_backoff_ms", "max_task_retries")}
    conf.executor_death_ms = 600
    conf.executor_heartbeat_ms = 50
    conf.executor_restart_backoff_ms = 50
    yield
    for k, v in saved.items():
        setattr(conf, k, v)


def _start_pool(count=2, slots=2):
    pool = ep.ExecutorPool(count=count, slots=slots)
    pool.start()
    return pool


def test_pool_echo_capacity_and_stats(fast_death_conf):
    pool = _start_pool(count=2, slots=2)
    try:
        assert pool.live_count() == 2
        assert pool.capacity() == 4
        specs = [ep.PoolTaskSpec(f"echo:{i}", "echo", {"value": i * 10})
                 for i in range(6)]
        out = pool.run_tasks(specs, timeout=60)
        assert [r["value"] for r in out] == [0, 10, 20, 30, 40, 50]
        st = pool.stats()
        assert st["tasks_done"] == 6 and st["deaths_total"] == 0
        assert st["inflight"] == 0
    finally:
        pool.close()


def test_pool_worker_retry_ladder_flaky(fast_death_conf, tmp_path):
    """A retryable failure is re-queued by the DRIVER (cross-process
    attempt, epoch advanced) and succeeds within max_task_retries."""
    pool = _start_pool(count=2, slots=1)
    try:
        marker = str(tmp_path / "flaky.n")
        spec = ep.PoolTaskSpec("flaky:0", "flaky",
                               {"marker": marker, "times": 1})
        out = pool.run_tasks([spec], timeout=60)
        assert out[0]["ok"]
        assert pool.stats()["tasks_done"] == 1
    finally:
        pool.close()


def test_pool_fatal_error_classified(fast_death_conf, tmp_path):
    from blaze_tpu.runtime import faults

    pool = _start_pool(count=1, slots=1)
    try:
        marker = str(tmp_path / "fatal.n")
        spec = ep.PoolTaskSpec("fatal:0", "flaky",
                               {"marker": marker, "times": 99,
                                "category": "fatal"})
        with pytest.raises(faults.FatalError):
            pool.run_tasks([spec], timeout=60)
    finally:
        pool.close()


def test_pool_sigkill_recovery_and_dossier(fast_death_conf, tmp_path,
                                           monkeypatch):
    """SIGKILL a busy executor mid-batch: the batch still completes, the
    seat respawns, capacity shrinks then recovers, and exactly one
    executor_death dossier is captured for the kill."""
    import signal

    from blaze_tpu.runtime import flight_recorder

    monkeypatch.setattr(conf, "flight_dir", str(tmp_path / "flight"))
    caps = []
    pool = _start_pool(count=2, slots=2)
    pool.on_membership(lambda p: caps.append(p.capacity()))
    try:
        specs = [ep.PoolTaskSpec(f"sl:{i}", "sleep", {"ms": 600})
                 for i in range(4)]
        import threading

        box = {}

        def run():
            box["out"] = pool.run_tasks(specs, timeout=120)

        t = threading.Thread(target=run)
        t.start()
        deadline = time.monotonic() + 10
        busy = {}
        while not busy and time.monotonic() < deadline:
            busy = pool.busy_pids()
            time.sleep(0.02)
        assert busy, "no executor picked up work"
        seat, pid = next(iter(busy.items()))
        os.kill(pid, signal.SIGKILL)
        t.join(timeout=120)
        assert len(box["out"]) == 4 and all(r["ok"] for r in box["out"])
        st = pool.stats()
        assert st["deaths_total"] == 1
        assert st["tasks_done"] == 4  # displaced attempts count ONCE
        # seat respawned: capacity dipped to 2 then recovered to 4
        deadline = time.monotonic() + 20
        # the membership callback runs on the respawning thread, after
        # the seat counts as live: wait for its report too
        while ((pool.live_count() < 2 or caps[-1:] != [4])
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert pool.live_count() == 2 and pool.capacity() == 4
        assert 2 in caps and caps[-1] == 4
        assert pool.restarts_total == 1
        dossiers = flight_recorder.list_dossiers(str(tmp_path / "flight"))
        deaths = [d for d in dossiers
                  if d.get("trigger") == "executor_death"]
        assert len(deaths) == 1
        doc = flight_recorder.load(deaths[0]["path"])
        detail = doc.get("detail") or {}
        assert detail.get("reason") in ("exit", "heartbeat")
        assert detail.get("signal") in (int(signal.SIGKILL), None)
        assert "recovery" in detail
        assert "last_heartbeat_age_ms" in detail
    finally:
        pool.close()


def test_pool_zombie_epoch_fence_no_double_count(fast_death_conf):
    """THE acceptance test: hang an executor mid-task (stops heartbeats,
    defers its result send — process stays alive). The driver declares
    heartbeat death, re-queues the displaced attempt on the surviving
    seat, and the batch completes. When the zombie wakes and delivers its
    stale-epoch result, the fence rejects it: no second completion for
    the key, no double-count in the ledger."""
    pool = _start_pool(count=2, slots=1)
    try:
        specs = [ep.PoolTaskSpec(f"z:{i}", "sleep", {"ms": 400})
                 for i in range(2)]
        import threading

        box = {}

        def run():
            box["out"] = pool.run_tasks(specs, timeout=120)

        t = threading.Thread(target=run)
        t.start()
        deadline = time.monotonic() + 10
        busy = {}
        while len(busy) < 2 and time.monotonic() < deadline:
            busy = pool.busy_pids()
            time.sleep(0.02)
        assert busy, "no executor picked up work"
        seat = next(iter(busy))
        fenced_before = pool.fence.fenced_total
        done_before = pool.tasks_done
        assert pool.hang_executor(seat, 2500)
        t.join(timeout=120)
        assert len(box["out"]) == 2 and all(r["ok"] for r in box["out"])
        st = pool.stats()
        assert st["deaths_total"] >= 1  # heartbeat death was declared
        # ledger: each key completed exactly once despite two attempts
        assert pool.tasks_done - done_before == 2
        # the zombie wakes ~2.5s after the hang and sends its stale
        # result; the fence must reject it
        deadline = time.monotonic() + 15
        while (pool.fence.fenced_total <= fenced_before
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert pool.fence.fenced_total > fenced_before
        assert pool.tasks_done - done_before == 2  # STILL two: no double
    finally:
        pool.close()


def test_pool_unavailable_when_all_seats_retired(fast_death_conf):
    """Exhaust the restart budget: run_tasks must raise
    PoolUnavailableError (callers degrade to the in-process runtime)
    rather than hang."""
    saved = conf.executor_restart_max
    conf.executor_restart_max = 0
    try:
        pool = _start_pool(count=1, slots=1)
        try:
            import signal
            import threading

            specs = [ep.PoolTaskSpec("u:0", "sleep", {"ms": 5000})]
            box = {}

            def run():
                try:
                    pool.run_tasks(specs, timeout=60)
                except Exception as e:  # noqa: BLE001 — asserted below
                    box["err"] = e

            t = threading.Thread(target=run)
            t.start()
            deadline = time.monotonic() + 10
            while not pool.busy_pids() and time.monotonic() < deadline:
                time.sleep(0.02)
            for pid in pool.pids().values():
                os.kill(pid, signal.SIGKILL)
            t.join(timeout=60)
            assert isinstance(box.get("err"), ep.PoolUnavailableError)
        finally:
            pool.close()
    finally:
        conf.executor_restart_max = saved


# ---------------------------------------------------------------------------
# service capacity + health
# ---------------------------------------------------------------------------


class _StubPool:
    """Capacity-interface stub so the service/monitor tests don't pay
    process-spawn latency."""

    def __init__(self, live, slots=2):
        self.live, self.slots = live, slots
        self._cbs = []
        self.deaths_total = self.restarts_total = self.tasks_done = 0

    def capacity(self):
        return self.live * self.slots

    def live_count(self):
        return self.live

    def on_membership(self, cb):
        self._cbs.append(cb)

    def set_live(self, n):
        self.live = n
        for cb in list(self._cbs):
            cb(self)

    def stats(self):
        return {"count": 2, "live": self.live,
                "capacity": self.capacity(), "slots": self.slots,
                "inflight": 0, "deaths_total": self.deaths_total,
                "restarts_total": self.restarts_total,
                "fenced_total": 0, "tasks_done": self.tasks_done}

    def executors(self):
        return [{"exec_id": f"exec{i}", "pid": 1000 + i, "generation": 0,
                 "up": i < self.live, "inflight": 0} for i in range(2)]


def test_service_capacity_shrinks_and_recovers():
    from blaze_tpu.runtime.service import QueryService

    svc = QueryService(max_concurrent=8)
    stub = _StubPool(live=2, slots=3)
    svc.attach_pool(stub)
    try:
        assert svc.capacity() == 6
        stub.set_live(1)          # death: admission window shrinks
        assert svc.capacity() == 3
        stub.set_live(2)          # rejoin: recovers
        assert svc.capacity() == 6
        assert svc.stats()["capacity"] == 6
    finally:
        svc.close()


def test_healthz_503_only_at_zero_executors():
    from blaze_tpu.runtime import monitor

    stub = _StubPool(live=1)
    ep.activate(stub)
    try:
        snap = monitor.health_snapshot()
        assert snap["ok"] and snap["executors_live"] == 1
        status, _ctype, _body = monitor.serve_path("/healthz")
        assert status == 200
        stub.set_live(0)
        snap = monitor.health_snapshot()
        assert not snap["ok"]
        status, _ctype, body = monitor.serve_path("/healthz")
        assert status == 503 and body  # body still carries the snapshot
    finally:
        ep.deactivate(stub)


def test_prometheus_executor_gauges():
    from blaze_tpu.runtime import monitor

    stub = _StubPool(live=1)
    stub.restarts_total = 3
    ep.activate(stub)
    try:
        text = monitor.prometheus_text()
        assert 'blaze_executor_up{exec_id="exec0"} 1' in text
        assert 'blaze_executor_up{exec_id="exec1"} 0' in text
        assert "blaze_executor_live 1" in text
        assert "blaze_executor_restarts_total 3" in text
        assert "blaze_service_capacity" in text
    finally:
        ep.deactivate(stub)


# ---------------------------------------------------------------------------
# pooled plan execution end-to-end
# ---------------------------------------------------------------------------


def _q3_plan(tmp_path, rng, n_ss=1200, n_dd=120):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu.columnar import types as T
    from blaze_tpu.exprs import ir
    from blaze_tpu.spark import plan_model as P

    ss_t = pa.table({
        "ss_sold_date_sk": pa.array(rng.integers(0, n_dd, n_ss), pa.int64()),
        "ss_item_sk": pa.array(rng.integers(0, 30, n_ss), pa.int64()),
        "ss_ext_sales_price": pa.array(rng.random(n_ss) * 100),
    })
    dd_t = pa.table({
        "d_date_sk": pa.array(np.arange(n_dd), pa.int64()),
        "d_moy": pa.array((np.arange(n_dd) // 30) % 12 + 1, pa.int32()),
    })
    ss_path = str(tmp_path / "ss.parquet")
    dd_path = str(tmp_path / "dd.parquet")
    pq.write_table(ss_t, ss_path)
    pq.write_table(dd_t, dd_path)
    SS = T.Schema([T.Field("ss_sold_date_sk", T.INT64),
                   T.Field("ss_item_sk", T.INT64),
                   T.Field("ss_ext_sales_price", T.FLOAT64)])
    DD = T.Schema([T.Field("d_date_sk", T.INT64), T.Field("d_moy", T.INT32)])

    def build():
        ss_scan = P.scan(SS, [(ss_path, [])])
        dd_scan = P.scan(DD, [(dd_path, [])])
        dd_flt = P.filter_(dd_scan, ir.Binary(ir.BinOp.EQ, ir.col("d_moy"),
                                              ir.lit(3)))
        ss_x = P.shuffle_exchange(ss_scan, [ir.col("ss_sold_date_sk")], 4)
        dd_x = P.shuffle_exchange(dd_flt, [ir.col("d_date_sk")], 4)
        join_schema = T.Schema(list(SS.fields) + list(DD.fields))
        j = P.smj(ss_x, dd_x, [ir.col("ss_sold_date_sk")],
                  [ir.col("d_date_sk")], "inner", join_schema)
        partial = P.hash_agg(j, "partial", [ir.col("ss_item_sk")], ["item"],
                             [{"fn": "sum",
                               "args": [ir.col("ss_ext_sales_price")],
                               "dtype": T.FLOAT64, "name": "s"}],
                             T.Schema([T.Field("item", T.INT64)]))
        agg_x = P.shuffle_exchange(partial, [ir.col("item")], 4)
        final = P.hash_agg(agg_x, "final", [ir.col("item")], ["item"],
                           [{"fn": "sum",
                             "args": [ir.col("ss_ext_sales_price")],
                             "dtype": T.FLOAT64, "name": "s"}],
                           T.Schema([T.Field("item", T.INT64),
                                     T.Field("s", T.FLOAT64)]))
        return P.sort(final, [(ir.col("s"), False, True)])

    return build


def test_pooled_plan_matches_inprocess(fast_death_conf, tmp_path, rng):
    """The q3-shaped plan answers identically whether its shuffle-map
    stages run in executor processes (plan shipped as proto, shuffle
    reads served over the socket, epoch-stamped artifacts committed by
    the driver) or in the driver's own threads."""
    from blaze_tpu.spark.local_runner import run_plan

    build = _q3_plan(tmp_path, rng)
    ri_plain = {}
    out_plain = run_plan(build(), num_partitions=4, mesh_exchange="off",
                         run_info=ri_plain)
    assert ri_plain.get("pool_stages", 0) == 0

    pool = _start_pool(count=2, slots=2)
    ep.activate(pool)
    try:
        ri_pool = {}
        out_pool = run_plan(build(), num_partitions=4, mesh_exchange="off",
                            run_info=ri_pool)
        assert ri_pool.get("pool_stages", 0) >= 1
    finally:
        ep.deactivate(pool)
        pool.close()

    dp = out_plain.to_numpy()
    dq = out_pool.to_numpy()
    order_p = np.argsort(np.asarray(dp["item"]))
    order_q = np.argsort(np.asarray(dq["item"]))
    np.testing.assert_array_equal(np.asarray(dp["item"])[order_p],
                                  np.asarray(dq["item"])[order_q])
    np.testing.assert_allclose(np.asarray(dp["s"])[order_p],
                               np.asarray(dq["s"])[order_q], rtol=1e-9)
