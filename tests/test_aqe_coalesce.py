"""Spark's AQE partition coalescing (spark/aqe.py): the rule against a plain
transcription of Spark 3.0's loop, and q3 at Spark's default of 200 shuffle
partitions under an adaptive root, on the CPU at a few batches of 8,192 rows:
reduce tasks over ranges of partitions, the same ranges for every shuffle a
stage reads by partition, one slice cut per (map batch, range), the same rows
as the reference and as the run of one task a partition; without the root, or
on two devices, one task a partition as before."""

import importlib.util
import json
import math
import os
import time

import jax
import numpy as np
import pandas as pd
import pytest

from blaze_tpu.config import conf
from blaze_tpu.parallel.stage_exchange import GroupedBatches
from blaze_tpu.runtime import compile_service, trace
from blaze_tpu.spark import aqe
from blaze_tpu.spark import plan_model as P
from blaze_tpu.spark.local_runner import run_plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
ROWS = 50_000
SEED = 11
BATCH_ROWS = 8192
ADVISORY = 128 << 10     # of 50,000 fact rows' ~1.3 MB: about ten ranges
PARAMS = {"month": 11, "manufact": 128}
RTOL = 1e-9              # the configuration's float_rtol
JOIN_STAGES = (2, 4)     # q3's stages: 0 fact, 1 dates, 2 date join,
RESULT_STAGE = 5         # 3 items, 4 item join, 5 final agg and order


def spark_coalesce(stats, advisory, min_num):
    """ShufflePartitionsUtil.coalescePartitions of Spark 3.0, line for line:
    `stats[j][i]` is bytesByPartitionId(i) of shuffle j."""
    total = sum(sum(s) for s in stats)
    max_target = max(int(math.ceil(total / float(min_num))), 16)
    target = min(max_target, advisory)
    num_partitions = len(stats[0])
    specs = []
    latest_split_point = 0
    coalesced_size = 0
    i = 0
    while i < num_partitions:
        total_of_current = 0
        j = 0
        while j < len(stats):
            total_of_current += stats[j][i]
            j += 1
        if i > latest_split_point and \
                coalesced_size + total_of_current > target:
            specs.append((latest_split_point, i))
            latest_split_point = i
            coalesced_size = total_of_current
        else:
            coalesced_size += total_of_current
        i += 1
    specs.append((latest_split_point, num_partitions))
    return specs, target


@pytest.mark.parametrize("shuffles", [1, 2, 3])
@pytest.mark.parametrize("min_num", [1, 8])
@pytest.mark.parametrize("advisory", [
    50,            # under most single partitions: a range a partition
    4_000,         # a few partitions a range
    10 ** 9])      # past the total: min_num decides
def test_the_rule_is_spark_3_0s(shuffles, min_num, advisory):
    rng = np.random.default_rng([shuffles, min_num, advisory])
    for partitions in (1, 7, 200):
        for _ in range(5):
            stats = rng.integers(0, 1_000, (shuffles, partitions))
            # empty partitions, some runs of them
            stats[rng.random((shuffles, partitions)) < 0.3] = 0
            stats[:, partitions // 3: partitions // 2] = 0
            got = aqe.coalesce_partitions(stats, advisory, min_num)
            assert got == spark_coalesce(stats.tolist(), advisory, min_num)
            ranges = got[0]
            assert ranges[0][0] == 0 and ranges[-1][1] == partitions
            assert all(a[1] == b[0] for a, b in zip(ranges, ranges[1:]))
            assert all(s < e for s, e in ranges)


def test_the_rule_at_its_edges():
    # nothing at all is one range (the target's floor of 16 bytes)
    assert aqe.coalesce_partitions([[0] * 200], 1 << 26, 1) == (
        [(0, 200)], 16)
    # a partition over the target is a range of its own, and so is each
    # after it; the ones before share a range
    assert aqe.coalesce_partitions([[1, 1, 100, 1, 1]], 10, 1) == (
        [(0, 2), (2, 3), (3, 5)], 10)
    # equal to the target still fits
    assert aqe.coalesce_partitions([[5, 5, 5]], 10, 1)[0] == [(0, 2), (2, 3)]
    # min_num asks for ranges the advisory size would not cut
    assert aqe.coalesce_partitions([[10] * 8], 1 << 26, 4) == (
        [(0, 2), (2, 4), (4, 6), (6, 8)], 20)


def _load(rel: str):
    spec = importlib.util.spec_from_file_location(
        "aqe_" + rel.replace("/", "_").replace(".", "_"),
        os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config() -> dict:
    with open(os.path.join(BENCH, "configs",
                           "tpcds_sf10_nobhj_p200.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """name -> one q3 through run_plan with tracing on: its frame, its
    differences from the reference, refusals, spans, the TELEMETRY deltas and
    every `GroupedBatches.take` (stage, ranges, grouped batches, slices cut).
    Knobs are restored after the module."""
    compare, evidence = _load("harness/compare.py"), _load(
        "harness/evidence.py")
    q03, q03_aqe = _load("queries/q03.py"), _load("queries/q03_aqe.py")
    config = _config()
    assert config["guarantees"]["float_rtol"] == RTOL
    paths, frames = _load("datagen/tpcds.py").generate(
        config, SEED, str(tmp_path_factory.mktemp("p200")), ROWS)
    want = q03.reference(frames, config, PARAMS)
    assert len(want) > 0
    real_devices, real_take = jax.devices, GroupedBatches.take
    takes: list = []

    def take(self, ranges):
        before = compile_service.TELEMETRY.snapshot()
        batches = len(self.batches)
        out = real_take(self, ranges)
        takes.append({
            "stage": trace.current_context().get("stage_id"),
            "ranges": list(ranges), "batches": batches,
            "cut": compile_service.TELEMETRY.snapshot()[
                "exchange_slices_cut"] - before.get("exchange_slices_cut", 0)})
        return out

    specs = {
        # name: (query, width, devices, aqe_broadcast_threshold, rows a
        # scan batch: several, or the table in one)
        "coalesced": (q03_aqe, 200, 1, None, BATCH_ROWS),
        "coalesced_smj": (q03_aqe, 200, 1, 0, BATCH_ROWS),
        "plain_200": (q03, 200, 1, None, None),
        "plain_4": (q03, 4, 1, None, BATCH_ROWS),
        "two_devices": (q03_aqe, 8, 2, None, BATCH_ROWS),
    }
    done: dict = {}

    def run(name: str) -> dict:
        if name in done:
            return done[name]
        query, width, devices, threshold, batch_rows = specs[name]
        cfg = json.loads(json.dumps(config))
        cfg["settings"]["exchange_width"] = width
        cfg["settings"]["aqe"]["advisory_partition_bytes"] = ADVISORY
        del takes[:]
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "devices",
                       lambda *a, **k: real_devices(*a, **k)[:devices])
            if batch_rows:
                # a scan's batch is max(batch_size, min(target,
                # max_batch_rows))
                mp.setattr(conf, "max_batch_rows", batch_rows)
                mp.setattr(conf, "batch_size", batch_rows)
            mp.setattr(conf, "trace_enabled", True)
            if threshold is not None:
                mp.setattr(conf, "aqe_broadcast_threshold", threshold)
            mp.setattr(GroupedBatches, "take", take)
            info: dict = {}
            before = compile_service.TELEMETRY.snapshot()
            got = compare.to_frame(run_plan(
                query.plan(paths, cfg, PARAMS), num_partitions=width,
                mesh_exchange="auto", run_info=info))
            after = compile_service.TELEMETRY.snapshot()
            spans = [r for r in trace.query_records(info["query_id"])
                     if r.get("type") == "span"]
        done[name] = {
            "frame": got, "spans": spans, "takes": list(takes),
            "wrong": compare.diff(got, want, RTOL, q03.ORDER_KEYS),
            "refused": evidence.refusals(info, devices, width),
            "telemetry": {k: v - before.get(k, 0) for k, v in after.items()
                          if isinstance(v, (int, float))}}
        return done[name]

    yield run
    trace.reset()


def _tasks(run: dict) -> dict:
    """stage id -> the `tasks` of its stage span."""
    return {s["stage_id"]: s["attrs"]["tasks"] for s in run["spans"]
            if s["kind"] == "stage"}


def _aqe_spans(run: dict) -> list:
    return [s for s in run["spans"] if s["kind"] == "aqe"]


@pytest.mark.parametrize("name", ["coalesced", "coalesced_smj"])
def test_coalesced_q3_equals_the_reference_and_one_task_a_partition(
        runs, name):
    run, plain = runs(name), runs("plain_200")
    assert run["wrong"] is None and run["refused"] == []
    assert plain["wrong"] is None and plain["refused"] == []
    pd.testing.assert_frame_equal(run["frame"], plain["frame"],
                                  check_exact=False, rtol=RTOL)
    tasks = _tasks(run)
    # the fact side is cut into several ranges; every task reads one
    assert 4 <= tasks[2] < 200
    assert all(tasks[s] < 200 for s in JOIN_STAGES + (RESULT_STAGE,))


def test_both_sides_of_a_join_stage_read_the_same_ranges(runs):
    """With the joins left sort-merge (dynamic join selection off), a join
    stage reads both its shuffles by partition: each is cut by the same
    ranges, which the rule takes from both sides' sizes."""
    run = runs("coalesced_smj")
    by_stage: dict = {}
    for t in run["takes"]:
        by_stage.setdefault(t["stage"], []).append(t["ranges"])
    assert sorted(by_stage) == [2, 4, RESULT_STAGE]
    for stage in JOIN_STAGES:
        left, right = by_stage[stage]
        assert left == right
        assert len(left) == _tasks(run)[stage]
    assert len(by_stage[2][0]) >= 4


def test_a_converted_join_reads_its_build_side_whole_once(runs):
    """Dynamic join selection turns both joins into broadcast joins: their
    small sides are cut and packed once a stage, as one range of every
    partition, and left out of the rule; the stage's ranges come from the
    side it reads by partition alone."""
    run = runs("coalesced")
    tasks, by_stage = _tasks(run), {}
    for t in run["takes"]:
        by_stage.setdefault(t["stage"], []).append(t["ranges"])
    assert sorted(by_stage) == [2, 4, RESULT_STAGE]
    for stage in JOIN_STAGES:
        whole, ranges = sorted(by_stage[stage], key=len)
        assert whole == [(0, 200)] and len(ranges) == tasks[stage]
    assert [s["attrs"]["shuffles"] for s in _aqe_spans(run)] == [1, 1, 1]


def test_a_fact_exchange_cuts_a_slice_per_map_batch_and_range(runs):
    run = runs("coalesced")
    (fact,) = [t for t in run["takes"] if t["stage"] == 2
               and len(t["ranges"]) > 1]
    assert fact["batches"] >= 6          # 50,000 rows at 8,192 a batch
    assert 0 < fact["cut"] <= fact["batches"] * len(fact["ranges"])
    assert fact["cut"] < fact["batches"] * 200 // 4
    # every slice a query cuts is cut per range (or whole, per batch)
    assert run["telemetry"]["exchange_slices_cut"] == sum(
        t["cut"] for t in run["takes"])
    assert run["telemetry"]["exchange_slices_packed"] > 0


def test_one_aqe_span_a_coalesced_stage_and_the_counters_agree(runs):
    run = runs("coalesced")
    spans = _aqe_spans(run)
    assert sorted(s["stage_id"] for s in spans) == [2, 4, RESULT_STAGE]
    tasks = _tasks(run)
    for s in spans:
        a = s["attrs"]
        assert a["partitions"] == 200 and a["tasks"] == tasks[s["stage_id"]]
        assert a["target_bytes"] == min(ADVISORY, max(a["bytes"], 16))
    tel = run["telemetry"]
    assert tel["aqe_reduce_tasks"] == sum(s["attrs"]["tasks"] for s in spans)
    assert sum(s["attrs"]["partitions"] for s in spans) == 600
    window = {"window": [{"spans": run["spans"]}], "profiled": [],
              "telemetry": tel}
    assert _load("metrics/reduce_tasks_per_query.py").read(window) == \
        tel["aqe_reduce_tasks"]


@pytest.mark.parametrize("name, width", [("plain_4", 4), ("plain_200", 200)])
def test_without_an_adaptive_root_a_task_a_partition(runs, name, width):
    run = runs(name)
    assert run["wrong"] is None and run["refused"] == []
    tasks = _tasks(run)
    assert [tasks[s] for s in JOIN_STAGES + (RESULT_STAGE,)] == [width] * 3
    assert not _aqe_spans(run) and not run["takes"]
    assert run["telemetry"].get("aqe_reduce_tasks", 0) == 0
    assert _load("metrics/reduce_tasks_per_query.py").read(
        {"window": [{"spans": run["spans"]}], "profiled": [],
         "telemetry": run["telemetry"]}) is None


def test_on_two_devices_the_adaptive_root_coalesces_nothing(runs):
    run = runs("two_devices")
    assert run["wrong"] is None and run["refused"] == []
    tasks = _tasks(run)
    assert [tasks[s] for s in JOIN_STAGES + (RESULT_STAGE,)] == [8] * 3
    assert not _aqe_spans(run) and not run["takes"]
    assert run["telemetry"].get("aqe_reduce_tasks", 0) == 0


def test_an_adaptive_root_is_unwrapped_with_its_settings():
    leaf = P.scan(None, [])
    root = P.adaptive(leaf, 1 << 20)
    assert root.kind == "AdaptiveSparkPlanExec" and root.children == [leaf]
    assert aqe.unwrap_adaptive(root) == (leaf, {
        "advisory_partition_bytes": 1 << 20})
    assert aqe.unwrap_adaptive(P.adaptive(leaf))[1] == {
        "advisory_partition_bytes": 64 << 20}
    assert aqe.unwrap_adaptive(leaf) == (leaf, None)


def test_the_cells_rehearsal_reads_both_metrics(monkeypatch):
    """`benchmarks/run.py --rehearse-rows` of the cell, in this process: every
    query correct, nothing compiled in the window, and the traced line holds
    the cell's own metric beside the window's compile count (a rehearsal's
    numbers are the CPU's: only counts are checked)."""
    monkeypatch.syspath_prepend(BENCH)
    monkeypatch.syspath_prepend(REPO)
    from harness import loop

    real = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a, **k: real(*a, **k)[:1])
    monkeypatch.setattr(conf, "trace_enabled", conf.trace_enabled)
    lines = []
    monkeypatch.setattr("builtins.print",
                        lambda *a, **k: lines.append(" ".join(map(str, a))))
    assert loop.run("sf10_q03_nobhj_p200", seed=2400420001, seconds=1.0,
                    traced=True, rehearse_rows=ROWS,
                    t_start=time.perf_counter()) == 0
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert metrics["compiles_in_window"]["value"] == 0
    # at these rows every stage is one range of all 200 partitions
    assert metrics["reduce_tasks_per_query"] == {"value": 3,
                                                 "unit": "count"}


def test_grouped_batches_serve_ranges_once_and_partitions_alike():
    """`GroupedBatches` on its own: each grouped batch's rows of a range
    are one slice, a range's slices come back packed in the order the
    batches came, the partition sizes are live bytes, a partition read
    alone is its rows of every batch, and a read by ranges is read once."""
    import jax.numpy as jnp

    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import ColumnBatch
    from blaze_tpu.parallel.stage_exchange import (
        group_by_partition, live_nbytes,
    )

    parts = 6
    schema = T.Schema([T.Field("k", T.INT64), T.Field("v", T.FLOAT64)])
    rng = np.random.default_rng(5)
    grouped, kept = GroupedBatches(schema, parts), []
    want_sizes = np.zeros(parts, np.int64)
    for n in (300, 517, 1):
        k = rng.integers(0, 1000, n)
        b = ColumnBatch.from_numpy({"k": k, "v": rng.random(n)}, schema)
        pid = np.full(b.capacity, parts, np.int32)
        pid[:n] = k % parts
        out, bounds = group_by_partition(b, jnp.asarray(pid), parts)
        bounds = np.asarray(bounds)
        grouped.add(out, bounds)
        kept.append(k)
        want_sizes += [live_nbytes(out, int(bounds[p + 1] - bounds[p]))
                       for p in range(parts)]
    assert grouped.sizes().tolist() == want_sizes.tolist()
    assert want_sizes.sum() > 0

    def rows(batches):
        return sorted(int(x) for b in batches for x in b.to_numpy()["k"])

    for p in range(parts):
        assert rows(grouped(p)) == sorted(
            int(x) for k in kept for x in k if x % parts == p)
    ranges = [(0, 2), (2, 5), (5, 6)]
    cut = grouped.take(ranges)
    for (s, e), batches in zip(ranges, cut):
        assert len(batches) == 1       # three slices, packed into one
        assert rows(batches) == sorted(
            int(x) for k in kept for x in k if s <= x % parts < e)
    with pytest.raises(RuntimeError):
        grouped.take(ranges)
    with pytest.raises(RuntimeError):
        list(grouped(0))
