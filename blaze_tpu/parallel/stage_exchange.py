"""Stage-boundary exchange over the ICI mesh (the in-HBM shuffle path).

Integrates parallel/shuffle.py's `mesh_shuffle_batch` into stage execution
(VERDICT r1 #3, SURVEY.md §2.6): when a shuffle stage's partition count
fits the device mesh, the exchange runs as one jitted `shard_map`
all_to_all program and the reduce side consumes partitions straight from
HBM — no `.data`/`.index` files, no host round-trip. The file-based path
(ops/shuffle.py) remains both the cross-slice transport and the automatic
fallback when the staging quota overflows (the reference's analog is the
sort-repartitioner's spill path, shuffle/sort_repartitioner.rs:199-213).

The partition function is the same Spark-murmur3+pmod as the file path
(exprs/hash.py), so a partition's row multiset is identical on either
path and readers cannot tell them apart.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from blaze_tpu.columnar.batch import ColumnBatch, bucket_capacity
from blaze_tpu.columnar.types import Schema
from blaze_tpu.exprs import ir
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.plan import plan_pb2 as pb
from blaze_tpu.runtime import resources, trace
from blaze_tpu.runtime.executor import execute_plan


def mesh_key_indices(writer: pb.ShuffleWriterNode,
                     schema: Schema) -> Optional[List[int]]:
    """Key column indices for the mesh partition kernel, or None when the
    stage can't ride the mesh (computed keys need the file path's
    expression evaluation; non-hash partitionings don't gain from it)."""
    from blaze_tpu.plan.from_proto import decode_expr

    if writer.partitioning.kind != pb.HashRepartition.HASH:
        return None
    idx: List[int] = []
    for ke in writer.partitioning.keys:
        e = decode_expr(ke)
        if isinstance(e, ir.Col):
            idx.append(schema.index_of(e.name))
        elif isinstance(e, ir.BoundRef):
            idx.append(e.index)
        else:
            return None
    return idx


def run_mesh_shuffle_stage(stage_plan: pb.PlanNode, stage_id: int,
                           ntasks: int, quota: Optional[int] = None,
                           work_dir: Optional[str] = None,
                           stats: Optional[dict] = None,
                           namespace: str = "") -> bool:
    """Execute one shuffle_map stage's exchange over the device mesh.

    STREAMS: each map-output batch is exchanged as it is produced — the
    staging footprint is bounded by one batch's capacity x P, never the
    whole stage (ref analog: the incremental sort-repartitioner,
    sort_repartitioner.rs:199-213). A batch whose skew overflows the
    per-partition staging quota is routed through the FILE path
    immediately — the already-exchanged batches are kept and map subplans
    never re-execute; the reduce-side provider serves mesh-received
    batches and file segments transparently.

    Returns False — with nothing registered, nothing executed — only when
    the stage can't ride the mesh at all (shape/keys/partition count).
    """
    import os
    import tempfile

    from blaze_tpu.ops.basic import MemorySourceExec
    from blaze_tpu.ops.shuffle import ShuffleWriterExec, read_shuffle_partition
    from blaze_tpu.plan import decode_plan
    from blaze_tpu.plan.from_proto import _partitioning
    from blaze_tpu.runtime import jit_cache

    writer = stage_plan.shuffle_writer
    num_partitions = writer.partitioning.num_partitions
    devices = jax.devices()
    if num_partitions < 2:
        return False
    from blaze_tpu.config import conf
    from blaze_tpu.runtime import faults

    if conf.fault_injection_spec:
        faults.inject("exchange.stage")
    input_op = decode_plan(writer.input)
    key_idx = mesh_key_indices(writer, input_op.schema)
    if key_idx is None or not key_idx:
        return False
    if any(f.dtype.is_nested for f in input_op.schema.fields):
        return False  # variable element capacities can't stack on the mesh

    schema = input_op.schema
    Pn = num_partitions
    # P > D (VERDICT r4 #7): device d OWNS the contiguous partition block
    # [d*k, (d+1)*k), k = ceil(P/D). With one device the "exchange" is
    # purely local grouping — partitions stay in HBM with no all_to_all
    # and no host round trip at all, where the file exchange would pull
    # every map output to the host and write it out.
    use_d = min(len(devices), Pn)
    kpd = -(-Pn // use_d)
    use_d = -(-Pn // kpd)  # drop devices left with no partitions
    mesh = (Mesh(np.array(devices[:use_d]), ("p",)) if use_d > 1 else None)
    recv_parts: List[List[ColumnBatch]] = [[] for _ in range(Pn)]
    file_outputs: List[tuple] = []

    def exchange_local(batch: ColumnBatch) -> bool:
        """Single-device exchange: group by partition id on device, slice
        per partition; one host pull (the bounds) per macro-batch."""
        from blaze_tpu.ops.common import slice_batch
        from blaze_tpu.parallel.shuffle import partition_ids

        key = ("local_xchg", Pn, tuple(key_idx), batch.shape_key())

        def make():
            def run(b):
                from blaze_tpu.ops.join import sort_batch_by_keys

                pid = partition_ids(b, key_idx, Pn)
                sb = sort_batch_by_keys(b, [pid.astype(jnp.uint32)])
                bounds = jnp.searchsorted(
                    jnp.sort(pid), jnp.arange(Pn + 1, dtype=jnp.int32))
                return sb, bounds

            return run

        # one `exchange` span per macro-batch: dispatch, the bounds pull
        # (where the host waits for the grouping) and the slicing
        with trace.span("exchange", transport="local", partitions=Pn,
                        capacity=batch.capacity) as sp:
            sb, bounds = jit_cache.get_or_compile(key, make)(batch)
            bounds = np.asarray(bounds)
            for p in range(Pn):
                n = int(bounds[p + 1]) - int(bounds[p])
                if n:
                    recv_parts[p].append(
                        slice_batch(sb, int(bounds[p]), n))
            sp.set(rows=int(bounds[Pn]) - int(bounds[0]))
        return True

    def exchange_batch(batch: ColumnBatch) -> bool:
        """Exchange one batch over the mesh; False on quota overflow."""
        if use_d == 1:
            return exchange_local(batch)
        with trace.span("exchange", transport="mesh", partitions=Pn,
                        devices=use_d, capacity=batch.capacity) as sp:
            return exchange_mesh(batch, sp)

    def exchange_mesh(batch: ColumnBatch, sp) -> bool:
        n = int(batch.num_rows)
        sp.set(rows=n)
        per = max(1, -(-n // use_d))
        cap = bucket_capacity(per)
        # quota: rows one device may send one OWNER device (k partitions)
        q = min(quota * kpd, cap) if quota else cap
        slices = [
            batch.take(jnp.arange(cap, dtype=jnp.int32) + i * per,
                       min(max(n - i * per, 0), per))
            for i in range(use_d)
        ]
        cols = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0),
                            *[b.columns for b in slices])
        num_rows = jnp.array([int(b.num_rows) for b in slices], jnp.int32)

        key = ("mesh_xchg", Pn, use_d, cap, q, tuple(key_idx),
               slices[0].shape_key())

        def make():
            def step(local_cols, local_num_rows):
                from blaze_tpu.parallel.shuffle import (
                    mesh_shuffle_batch_grouped,
                )

                b = ColumnBatch(schema, local_cols, local_num_rows[0], cap)
                out, counts, overflow = mesh_shuffle_batch_grouped(
                    b, key_idx, "p", Pn, kpd, quota=q)
                return out.columns, counts[None], overflow[None]

            return jax.shard_map(step, mesh=mesh,
                                 in_specs=(P("p"), P("p")),
                                 out_specs=(P("p"), P("p"), P("p")))

        run = jit_cache.get_or_compile(key, make)
        out_cols, out_counts, overflow = run(cols, num_rows)
        if int(np.asarray(overflow).sum()) > 0:
            return False
        if stats is not None:
            # devices the shard_map's output actually sits on
            stats["devices"] = len(
                jax.tree_util.tree_leaves(out_cols)[0].devices())
        out_counts = np.asarray(out_counts)  # (use_d, kpd)
        recv_cap = use_d * q  # per-device received capacity
        full = ColumnBatch(schema, out_cols, jnp.asarray(0, jnp.int32),
                           use_d * recv_cap)
        for d in range(use_d):
            off = 0
            for j in range(kpd):
                p = d * kpd + j
                nrows = int(out_counts[d, j])
                if p >= Pn or nrows == 0:
                    off += nrows
                    continue
                # compact to the rows' own capacity bucket: retaining the
                # full staging capacity per slice would pin
                # O(batches * D^2 * q) padded rows in HBM across the stage
                cap_p = bucket_capacity(nrows)
                idx = jnp.arange(cap_p, dtype=jnp.int32) + \
                    (d * recv_cap + off)
                recv_parts[p].append(full.take(idx, nrows))
                off += nrows
        return True

    def spill_batch_to_file(batch: ColumnBatch) -> None:
        nonlocal work_dir
        if work_dir is None:
            work_dir = tempfile.mkdtemp(prefix="blaze_tpu_mesh_ovf_")
        i = len(file_outputs)
        data = os.path.join(work_dir, f"stage{stage_id}_meshovf{i}.data")
        index = os.path.join(work_dir, f"stage{stage_id}_meshovf{i}.index")
        op = ShuffleWriterExec(MemorySourceExec([batch], schema),
                               _partitioning(writer.partitioning),
                               data, index)
        list(execute_plan(op, ExecContext(partition=0, num_partitions=1)))
        file_outputs.append((data, index))

    # map side: stream every task's batches straight into the exchange
    # (whole-stage single-dispatch where the subtree matches). Exchanged
    # partitions stay PINNED in HBM until the consuming stage finishes,
    # so the mesh path honors the memory budget: once pinned bytes pass
    # half the budget, the remaining batches take the file path (the
    # reduce side reads both transparently).
    from blaze_tpu.runtime.executor import execute_stage_or_plan
    from blaze_tpu.runtime.memory import batch_nbytes, get_manager

    budget = get_manager().total // 2
    pinned = 0
    for task in range(ntasks):
        op = decode_plan(writer.input)  # fresh operator state per task
        for batch in execute_stage_or_plan(
                op, ExecContext(partition=task, num_partitions=ntasks)):
            if int(batch.num_rows) == 0:
                continue
            if pinned > budget or not exchange_batch(batch):
                spill_batch_to_file(batch)
            else:
                pinned += batch_nbytes(batch)

    def _unshard(x):
        # Batches sliced out of the shard_map output stay committed
        # across the mesh devices. Downstream task programs are
        # single-device: feeding them multi-device pytrees trips XLA
        # buffer mismatches (and a fresh compile against them can wait on
        # collectives that never run). Round-trip through host to an
        # UNCOMMITTED default-device array — committed placement would
        # break a later mesh stage's shard_map instead. Single-device
        # leaves (the real-chip case) pass through untouched.
        import numpy as np

        if hasattr(x, "devices") and len(x.devices()) > 1:
            return jnp.asarray(np.asarray(x))
        return x

    def provider(partition: int):
        # defaulted extra args would miscount as task-context params in
        # _call_provider's arity dispatch — close over state instead
        from blaze_tpu.ops.host_sort import host_supported
        from blaze_tpu.ops.shuffle import read_shuffle_partition_host

        for b in recv_parts[partition]:
            if mesh is None:  # one device: _unshard passes through
                yield jax.tree_util.tree_map(_unshard, b)
                continue
            # the span closes before the yield: a span must never stay
            # open across a generator's suspension
            with trace.span("exchange", transport="unshard",
                            partitions=Pn, capacity=b.capacity):
                b = jax.tree_util.tree_map(_unshard, b)
            yield b
        for data, index in file_outputs:
            if host_supported(schema):
                yield from read_shuffle_partition_host(data, index,
                                                       partition, schema)
            else:
                yield from read_shuffle_partition(data, index, partition,
                                                  schema)

    if stats is not None:
        import os as _os

        from blaze_tpu.runtime.memory import batch_nbytes

        # live-row-scaled logical bytes: batch_nbytes counts the padded
        # capacity bucket, which would bias the AQE threshold vs the file
        # path's on-disk measure
        total = 0
        for parts in recv_parts:
            for b in parts:
                cap = max(b.capacity, 1)
                total += batch_nbytes(b) * int(b.num_rows) // cap
        total += sum(_os.path.getsize(d) for d, _ in file_outputs)
        stats["bytes"] = int(total)
    resources.put(f"{namespace}shuffle:{stage_id}", provider)
    return True
