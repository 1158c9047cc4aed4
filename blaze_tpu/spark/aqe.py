"""Adaptive re-optimization between stages (the AQE interplay).

Ref: Spark's AQE re-plans each query stage with runtime statistics; the
reference re-enters its conversion per stage and forces AQE on
(BlazeSparkSessionExtension.scala:33-34, shims AQE node recognition,
ShimsImpl.scala:271-299). The flagship AQE rewrite is dynamic join
selection: once a shuffle map stage has RUN and its output is small,
a planned sort-merge join over that shuffle becomes a broadcast join.

This module applies that rewrite at the PROTO level between stages in the
local runner: a `sort_merge_join` whose one input is an `ipc_reader` over
a completed shuffle with total bytes <= `conf.aqe_broadcast_threshold`
is replaced by a `broadcast_join` building from the small side — the
already-shuffled data is reused by reading ALL partitions of that shuffle
on every task (Spark's local-shuffle-reader + broadcast conversion).

A plan whose root is `plan_model.adaptive` (Spark's AdaptiveSparkPlanExec
with the session's AQE settings) also coalesces shuffle partitions, as
Spark's CoalesceShufflePartitions does: before each stage that reads
shuffles by partition, contiguous partitions are read together by one
task up to the advisory size (`coalesce_partitions`, Spark 3.0's
ShufflePartitionsUtil rule), the same ranges for every shuffle the stage
reads by partition, so co-partitioning holds. On one chip only: the
statistic is the in-HBM exchange's own (parallel/stage_exchange
.GroupedBatches), and a range across owner chips is not cut.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from blaze_tpu.config import conf
from blaze_tpu.plan import plan_pb2 as pb
from blaze_tpu.runtime import compile_service, resources, trace


def _reader_shuffle_sid(node: pb.PlanNode) -> Optional[Tuple[int, str]]:
    """(shuffle sid, resource id) when the subtree is exactly an ipc_reader
    over a shuffle (optionally under a Sort — Spark plans SMJ children as
    Sort over the exchange)."""
    which = node.WhichOneof("node")
    if which == "sort":
        return _reader_shuffle_sid(node.sort.input)
    if which != "ipc_reader":
        return None
    rid = node.ipc_reader.provider_resource_id
    # rids may carry a "<query_id>/" namespace prefix (concurrent queries);
    # parse the local part, keep the full rid for resource lookups
    local = rid.rsplit("/", 1)[-1]
    if not local.startswith("shuffle:"):
        return None
    return int(local.split(":", 1)[1]), rid


def _all_partitions_resource(rid: str, nparts: int) -> str:
    """Register (once) a provider that chains every partition of a shuffle
    — the broadcast build side needs the WHOLE relation on each task. An
    adaptive plan's exchange is cut and packed once, as one range, and the
    batches handed whole to every task."""
    from blaze_tpu.parallel.stage_exchange import GroupedBatches

    all_rid = f"{rid}:all"
    if resources.try_get(all_rid) is None:
        base = resources.get(rid)
        if isinstance(base, GroupedBatches) and base.sizes() is not None:
            (whole,) = base.take([(0, nparts)])
            resources.put(all_rid, lambda _partition: iter(whole))
            return all_rid

        def provider(_partition: int):
            for p in range(nparts):
                src = base(p)
                for item in src:
                    yield item

        resources.put(all_rid, provider)
    return all_rid


def _serve(parts: List[list]):
    """A provider handing task t the batches of parts[t]; one positional
    argument, as _call_provider counts."""
    def provider(task: int):
        return iter(parts[task])

    return provider


def unwrap_adaptive(root) -> Tuple[object, Optional[dict]]:
    """(the plan under an adaptive root, its AQE settings), or (root, None)
    for a plan without one."""
    if root.kind != "AdaptiveSparkPlanExec":
        return root, None
    return root.children[0], dict(root.attrs)


def coalesce_partitions(sizes: Sequence[Sequence[int]], advisory: int,
                        min_partitions: int) -> Tuple[List[Tuple[int, int]],
                                                      int]:
    """Spark 3.0's ShufflePartitionsUtil.coalescePartitions: the ranges
    [start, end) of partition indices one task each reads, and the target.

    `sizes[j][i]` is the bytes of shuffle j's partition i. An index's size is
    summed over the shuffles; the target is the advisory size, or less where
    `min_partitions` ranges must come of the total (never under 16 bytes, so
    an empty input is one range). Walking the indices in order, a range ends
    before index i > its start when its size with i's would pass the
    target."""
    per_index = np.asarray(sizes, np.int64).sum(axis=0)
    total = int(per_index.sum())
    target = min(int(advisory), max(-(-total // int(min_partitions)), 16))
    ranges, start, size = [], 0, 0
    for i, n in enumerate(per_index.tolist()):
        if i > start and size + n > target:
            ranges.append((start, i))
            start, size = i, n
        else:
            size += n
    ranges.append((start, len(per_index)))
    return ranges, target


@dataclasses.dataclass
class Coalesced:
    """A stage's reads by partition, coalesced: one task per range of
    partition indices, the same ranges for each shuffle in `rids`."""

    ranges: List[Tuple[int, int]]
    rids: List[str]
    total_bytes: int
    target_bytes: int


def ipc_readers(node: pb.PlanNode):
    """Every ipc_reader under `node`."""
    which = node.WhichOneof("node")
    if which == "ipc_reader":
        yield node.ipc_reader
    elif which is not None:
        for fd, val in getattr(node, which).ListFields():
            if (fd.message_type is not None
                    and fd.message_type.name == "PlanNode"):
                for child in (val if fd.is_repeated else [val]):
                    yield from ipc_readers(child)


def _partition_readers(node: pb.PlanNode) -> List[pb.IpcReaderNode]:
    """The ipc_readers under `node` that read a shuffle by partition (not
    the whole of it, as a converted broadcast join's build side does)."""
    return [r for r in ipc_readers(node) if re.fullmatch(
        r"shuffle:\d+", r.provider_resource_id.rsplit("/", 1)[-1])]


def plan_coalescing(stage, settings: dict) -> Optional[Coalesced]:
    """Coalesce `stage`'s reads by partition, after dynamic join selection:
    the ranges by the rule over each partition index's live bytes summed
    over the shuffles the stage reads by partition; its readers then read
    `<rid>:ranges`, one task a range (`stage.tasks`). None, and the stage
    as it was, where the stage reads no shuffle by partition, or one of
    them has no statistic (not a one-chip in-HBM exchange, or rows that
    went through files). `minPartitionNum` is defaultParallelism, one task
    slot: 1."""
    from blaze_tpu.parallel.stage_exchange import GroupedBatches

    readers = _partition_readers(stage.plan)
    rids = sorted({r.provider_resource_id for r in readers})
    bases = [resources.try_get(rid) for rid in rids]
    if not bases or not all(isinstance(b, GroupedBatches)
                            and b.sizes() is not None for b in bases):
        return None
    sizes = [b.sizes() for b in bases]
    if len({len(s) for s in sizes}) != 1:
        return None
    ranges, target = coalesce_partitions(
        sizes, settings["advisory_partition_bytes"], 1)
    for r in readers:
        r.provider_resource_id += ":ranges"
        r.num_partitions = len(ranges)
    stage.tasks = len(ranges)
    return Coalesced(ranges, rids, int(np.sum(sizes)), target)


def read_ranges(co: Coalesced) -> None:
    """Cut each shuffle a coalesced stage reads by partition per range,
    pack a range's slices, and register what task t is handed as
    `<rid>:ranges`; one `aqe` span, counted where the tasks are launched."""
    partitions = co.ranges[-1][1]
    with trace.span("aqe", partitions=partitions, tasks=len(co.ranges),
                    bytes=co.total_bytes, target_bytes=co.target_bytes,
                    shuffles=len(co.rids)):
        for rid in co.rids:
            resources.put(f"{rid}:ranges",
                          _serve(resources.get(rid).take(co.ranges)))
    compile_service.note_aqe_tasks(len(co.ranges))


def _rewrite_reader(node: pb.PlanNode, all_rid: str) -> None:
    """Point the build-side subtree at the all-partitions resource AND
    strip any Sort wrapper — the broadcast join sorts its build side
    itself, so a retained Sort would re-sort the whole relation once per
    task for nothing."""
    which = node.WhichOneof("node")
    if which == "sort":
        inner = pb.PlanNode()
        inner.CopyFrom(node.sort.input)
        node.CopyFrom(inner)
        _rewrite_reader(node, all_rid)
        return
    node.ipc_reader.provider_resource_id = all_rid


def apply_dynamic_join_selection(plan: pb.PlanNode,
                                 shuffle_bytes: Dict[int, int],
                                 shuffle_parts: Dict[int, int]) -> int:
    """Rewrite eligible SMJs to broadcast joins in place; returns the
    number of conversions (for metrics/tests)."""
    threshold = int(conf.aqe_broadcast_threshold)
    if threshold <= 0:
        return 0
    converted = 0
    which = plan.WhichOneof("node")
    if which is None:
        return 0
    node = getattr(plan, which)

    if which == "sort_merge_join":
        left_info = _reader_shuffle_sid(node.left)
        right_info = _reader_shuffle_sid(node.right)

        def size_of(info):
            if info is None or info[0] not in shuffle_bytes:
                return None
            return shuffle_bytes[info[0]]

        lsize, rsize = size_of(left_info), size_of(right_info)
        # the build side must be the NON-PRESERVED side: per-task unmatched
        # emission of a broadcast preserved side would duplicate rows
        # across tasks (Spark's canBroadcastBySize + build-side rules).
        # FULL preserves both sides -> never convertible.
        jt = node.join_type
        can_build_left = jt in (pb.JOIN_INNER, pb.JOIN_RIGHT)
        can_build_right = jt in (pb.JOIN_INNER, pb.JOIN_LEFT,
                                 pb.JOIN_LEFT_SEMI, pb.JOIN_LEFT_ANTI,
                                 pb.JOIN_EXISTENCE)
        candidates = []
        if can_build_left and lsize is not None and lsize <= threshold:
            candidates.append(("left", left_info, lsize))
        if can_build_right and rsize is not None and rsize <= threshold:
            candidates.append(("right", right_info, rsize))
        if candidates:
            side, info, _ = min(candidates, key=lambda c: c[2])
            sid, rid = info
            bj = pb.BroadcastJoinNode()
            bj.left.CopyFrom(node.left)
            bj.right.CopyFrom(node.right)
            for o in node.on:
                bj.on.add().CopyFrom(o)
            bj.join_type = node.join_type
            bj.build_is_left = side == "left"
            if node.HasField("join_filter"):
                bj.join_filter.CopyFrom(node.join_filter)
            if node.existence_name:
                bj.existence_name = node.existence_name
            all_rid = _all_partitions_resource(rid, shuffle_parts[sid])
            _rewrite_reader(bj.left if side == "left" else bj.right,
                            all_rid)
            plan.broadcast_join.CopyFrom(bj)
            converted += 1
            node = plan.broadcast_join

    for fd, val in node.ListFields():
        if fd.message_type is not None and fd.message_type.name == "PlanNode":
            if fd.is_repeated:
                for child in val:
                    converted += apply_dynamic_join_selection(
                        child, shuffle_bytes, shuffle_parts)
            else:
                converted += apply_dynamic_join_selection(
                    val, shuffle_bytes, shuffle_parts)
    return converted
