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

On a host of several chips a partition lives on the chip that owns it
(runtime/placement.py) from the all_to_all to the task that consumes it:
cut out of that chip's shard of the output, handed over there, and, where
the consuming stage exchanges again, sent on from there.
"""

from __future__ import annotations

import threading
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from blaze_tpu.columnar.batch import (
    Column, ColumnBatch, bucket_capacity, pull_array, pull_rows,
    rows_to_ranks,
)
from blaze_tpu.columnar.types import Schema
from blaze_tpu.exprs import ir
from blaze_tpu.ops import segment as seg
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.ops.common import adaptive_batch_rows
from blaze_tpu.plan import plan_pb2 as pb
from blaze_tpu.runtime import (
    compile_service, jit_cache, placement, resources, trace,
)
from blaze_tpu.runtime.executor import execute_plan
from blaze_tpu.runtime.memory import batch_nbytes, get_manager


_collective_lock = threading.Lock()

# Partition counts up to which a row's place in the grouped batch is found by
# counting: P running counts of one-hot flags, each a blocked scan; past it by
# a stable sort of the partition ids, whose cost does not grow with P. On a
# v5e at 2^21 rows the counts took 0.53, 2.2 and 7.6 ms at P = 4, 16 and 64,
# the sort and its inverting scatter 11.9 ms at each (PERF.md section 6):
# past 64 (Spark's default of 200 partitions, say) the sort is cheaper.
COUNTED_PARTITIONS = 64

# local_xchg cache key -> the planes its program moved by scatter ("ranked")
# and by gather ("gathered"): tallied when it is traced, added to
# compile_service.TELEMETRY at every dispatch
_PLANE_FORMS: dict = {}


def _ranks_by_count(pid: jax.Array, partitions: int) -> tuple:
    flags = pid[None, :] == jnp.arange(partitions, dtype=jnp.int32)[:, None]
    seen = jax.vmap(seg.running_count)(flags)   # (P, capacity), inclusive
    # a cumsum of P counts, not of rows
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(seen[:, -1], dtype=jnp.int32)])
    # a row's slot: its partition's start + the rows of its partition up to
    # it, less one; padding (pid P) aims past the end and drops
    dest = jnp.sum(jnp.where(flags, bounds[:-1, None] + seen - 1, 0),
                   axis=0, dtype=jnp.int32)
    return jnp.where(pid < partitions, dest, pid.shape[0]), bounds


def _ranks_by_sort(pid: jax.Array, partitions: int) -> tuple:
    iota = jnp.arange(pid.shape[0], dtype=jnp.int32)
    spid, perm = jax.lax.sort((pid, iota), num_keys=1, is_stable=True)
    bounds = jnp.searchsorted(spid, jnp.arange(partitions + 1,
                                               dtype=jnp.int32))
    # slot j holds row perm[j]: row perm[j] goes to slot j
    return rows_to_ranks(iota, perm), bounds.astype(jnp.int32)


def group_by_partition(batch: ColumnBatch, pid: jax.Array, partitions: int,
                       tally: Optional[dict] = None) -> tuple:
    """`batch`'s rows grouped by partition id, each partition's rows in the
    batch's order, and the int32 (P + 1,) bounds: partition p holds slots
    [bounds[p], bounds[p + 1]). `pid` is `partition_ids`' (padding P).

    A row's slot is its partition's start plus the number of earlier rows
    with its id, and the planes go there by `ColumnBatch.place_rows`
    (scatters of 32-bit words where the plane allows, a gather where not).
    Up to `COUNTED_PARTITIONS` the ranks are running counts and the program
    has no sort; past it they invert a stable sort's permutation. On a v5e
    a 2^21-row fact batch of three nullable 8-byte columns is grouped in
    128 ms, where a sort by partition id and a gather of every plane by
    its permutation took 247 ms (one process; PERF.md section 6)."""
    ranks = (_ranks_by_count if partitions <= COUNTED_PARTITIONS
             else _ranks_by_sort)
    dest, bounds = ranks(pid, partitions)
    return batch.place_rows(dest, batch.num_rows, tally), bounds


def live_nbytes(batch: ColumnBatch, nrows: int) -> int:
    """Bytes of a batch's live rows: batch_nbytes counts the padded
    capacity bucket."""
    return batch_nbytes(batch) * nrows // max(batch.capacity, 1)


def slice_layout(batch: ColumnBatch) -> tuple:
    """What two batches must share for their planes to be laid side by
    side: the columns' storage, validity present or not, string widths."""
    return (jax.tree.structure(batch.columns), batch.shape_key()[1:])


def _own_dictionary(c: Column) -> bool:
    if c.is_struct:
        return any(_own_dictionary(ch) for ch in c.data.children)
    return c.is_dict


def packable(batch: ColumnBatch) -> bool:
    """Every plane holds row r at index r and means the same in another
    batch of its layout: not so a list's elements, nor a dictionary
    column's codes, which index the batch's own dictionary."""
    return batch.row_aligned and not any(
        _own_dictionary(c) for c in batch.columns)


def pack_slices(slices: List[tuple], schema: Schema,
                device=None) -> ColumnBatch:
    """The live rows of `slices`, in order, as one batch at their total's
    capacity bucket. `slices` are (batch, live rows) with the rows as host
    integers, `packable` and of one `slice_layout`: nothing is pulled. On a
    host of several chips `device` is the chip they lie on, and the batch
    is made there.

    One program a group: every plane of every slice is written whole into
    the output at the running sum of live rows (`dynamic_update_slice`, the
    twin of `ColumnBatch.slice_rows`), in order, so that a slice's padding
    is overwritten by the slice after it; no plane is gathered. The offsets
    are traced, and the key holds capacities and layouts only."""
    batches = [b for b, _ in slices]
    starts = np.cumsum([0] + [n for _, n in slices]).astype(np.int32)
    cap = bucket_capacity(int(starts[-1]))
    key = ("exchange_pack", cap, tuple(schema.fields),
           tuple(b.shape_key() for b in batches))

    def make():
        def run(starts, *bs):
            def plane(*xs):
                # room for the last slice's padding past `cap`: an update
                # that passed the end would be moved back over live rows
                room = max(x.shape[0] for x in xs)
                out = jnp.zeros((cap + room,) + xs[0].shape[1:], xs[0].dtype)
                for i, x in enumerate(xs):
                    out = jax.lax.dynamic_update_slice_in_dim(
                        out, x, starts[i], axis=0)
                return out[:cap]

            cols = jax.tree.map(plane, *[b.columns for b in bs])
            return ColumnBatch(schema, cols, starts[-1], cap)

        return run

    with placement.on_device(device):
        return jit_cache.get_or_compile(key, make)(starts, *batches)


class KeptPartitions:
    """What an in-HBM exchange holds for its reduce tasks: per partition
    the kept batches with their live rows, in the order the rows came, and
    the bytes they pin on each chip.

    A reduce task runs its programs once a batch it is handed, so the
    slices the exchange cuts are packed as they are kept (`pack_slices`):
    consecutive slices of a partition join its open group until the next
    one's live rows would take the group past `adaptive_batch_rows`, the
    size a scan hands on; the group is then sealed into one batch and its
    slices dropped. Whole slices only; a group of one stays the batch it
    is; a slice that is not `packable`, or of another layout than the open
    group, does not share a group. `pinned` counts a group's slices and its
    packed batch while both are alive; `high_water` is the most any chip
    held at a time."""

    def __init__(self, schema: Schema, partitions: int, chips: int,
                 per_chip: int):
        self.parts: List[List[tuple]] = [[] for _ in range(partitions)]
        self.pinned = [0] * chips
        self.high_water = 0
        self.cut = 0        # non-empty slices kept
        self.packed = 0     # those of them that went into a packed batch
        self._schema, self._per_chip = schema, per_chip
        self._target = adaptive_batch_rows(schema)
        self._open: List[List[tuple]] = [[] for _ in range(partitions)]

    def _pin(self, p: int, nbytes: int) -> None:
        d = p // self._per_chip
        self.pinned[d] += nbytes
        self.high_water = max(self.high_water, self.pinned[d])

    def keep(self, p: int, b: ColumnBatch, nrows: int) -> None:
        self.cut += 1
        self._pin(p, batch_nbytes(b))
        if not packable(b):
            self.seal(p)
            self.parts[p].append((b, nrows))
            return
        group = self._open[p]
        if group and (slice_layout(b) != slice_layout(group[0][0])
                      or sum(n for _, n in group) + nrows > self._target):
            self.seal(p)
        self._open[p].append((b, nrows))

    def seal(self, p: int) -> None:
        """Close partition `p`'s open group: its one slice as it is, or
        its slices packed into one batch, where they lie."""
        group, self._open[p] = self._open[p], []
        if len(group) > 1:
            batch = pack_slices(
                group, self._schema,
                placement.device_of(group[0][0].columns)
                if len(self.pinned) > 1 else None)
            self._pin(p, batch_nbytes(batch))
            self._pin(p, -sum(batch_nbytes(b) for b, _ in group))
            self.packed += len(group)
            group = [(batch, sum(n for _, n in group))]
        self.parts[p].extend(group)

    def seal_all(self) -> None:
        for p in range(len(self.parts)):
            self.seal(p)


def _file_segments(files: List[tuple], partition: int, schema: Schema):
    """Partition `partition`'s rows of the batches an exchange sent through
    files, each (data, index) pair's segment in turn."""
    from blaze_tpu.ops.host_sort import host_supported
    from blaze_tpu.ops.shuffle import (
        read_shuffle_partition, read_shuffle_partition_host,
    )

    read = (read_shuffle_partition_host if host_supported(schema)
            else read_shuffle_partition)
    for data, index in files:
        yield from read(data, index, partition, schema)


class GroupedBatches:
    """What a one-chip in-HBM exchange of an adaptive plan (spark/aqe.py)
    holds for its reduce side until the stage that reads it is planned:
    each batch grouped by partition with its bounds, which the exchange
    pulled already, pinned as it came; and each partition's live bytes, the
    statistic the coalescing rule reads.

    The reduce tasks read ranges of partitions, and a range's partitions lie
    side by side in every grouped batch: `take` cuts one slice per (grouped
    batch, range), packs a range's slices as `KeptPartitions` packs a
    partition's, and drops each grouped batch once it is cut. Read by
    partition (a stage whose reads were not coalesced) it cuts the one
    partition each call, and serves the rows that went through files after
    the grouped ones."""

    def __init__(self, schema: Schema, partitions: int):
        self.schema = schema
        self.batches: List[tuple] = []      # (grouped batch, bounds)
        self.files: List[tuple] = []        # (data, index) past the budget
        self.pinned = [0]
        self.high_water = 0
        self.partition_bytes = np.zeros(partitions, np.int64)

    def add(self, batch: ColumnBatch, bounds: np.ndarray) -> None:
        nbytes = batch_nbytes(batch)
        self.batches.append((batch, bounds))
        self.pinned[0] += nbytes
        self.high_water = max(self.high_water, self.pinned[0])
        # live bytes as live_nbytes reckons them
        self.partition_bytes += (nbytes * np.diff(bounds).astype(np.int64)
                                 // max(batch.capacity, 1))

    def sizes(self) -> Optional[np.ndarray]:
        """Each partition's live bytes; None where rows went through files,
        whose bytes are not of the same measure."""
        return None if self.files else self.partition_bytes

    def _cut(self, ranges: List[tuple], drop: bool) -> List[List[tuple]]:
        from blaze_tpu.ops.common import slice_batch

        if self.batches is None:
            raise RuntimeError("an exchange read by ranges is read once")
        kept = KeptPartitions(self.schema, len(ranges), 1, len(ranges))
        for i, (batch, bounds) in enumerate(self.batches):
            for r, (s, e) in enumerate(ranges):
                n = int(bounds[e]) - int(bounds[s])
                if n:
                    kept.keep(r, slice_batch(batch, int(bounds[s]), n), n)
            if drop:    # the slices are copies: free the batch as it is cut
                self.batches[i] = None
        if drop:
            self.batches = None
        kept.seal_all()
        compile_service.note_exchange_kept(
            sum(len(part) for part in kept.parts),
            sum(n for part in kept.parts for _, n in part),
            kept.cut, kept.packed)
        return kept.parts

    def take(self, ranges: List[tuple]) -> List[List[ColumnBatch]]:
        """The batches a task reading range r = [start, end) of partitions
        is handed, for each range; once. No row went through files."""
        return [[b for b, _ in part] for part in self._cut(ranges, True)]

    def __call__(self, partition: int):
        for b, _ in self._cut([(partition, partition + 1)], False)[0]:
            yield b
        yield from _file_segments(self.files, partition, self.schema)


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
                           namespace: str = "", sup=None,
                           task_devices: Optional[list] = None,
                           by_ranges: bool = False) -> bool:
    """Execute one shuffle_map stage's exchange over the device mesh.

    STREAMS: each map-output batch is exchanged as it is produced — the
    staging footprint is bounded by one batch's capacity x P, never the
    whole stage (ref analog: the incremental sort-repartitioner,
    sort_repartitioner.rs:199-213). A batch whose skew overflows the
    per-partition staging quota is routed through the FILE path
    immediately — the already-exchanged batches are kept and map subplans
    never re-execute; the reduce-side provider serves mesh-received
    batches and file segments transparently.

    On several chips partition p's received rows stay on the chip that
    owns p (runtime/placement.py): they are cut out of that chip's own
    shard of the all_to_all's output, there, and the provider hands them
    to a consumer placed on that chip as they are: no exchanged row
    crosses the host. `task_devices` (one chip per map task, from the
    runner) says this stage's own tasks read such partitions: they run
    placed on `sup`'s pool, each on its chip, and what they produce
    enters the next all_to_all from where it lies, one batch per chip in
    lock step. Only the calling (driver) thread launches the collective
    program; task threads launch single-device programs only.

    What is kept for a partition is packed as it is kept
    (`KeptPartitions`): a reduce task is handed batches of up to the size a
    scan hands on, not one sliver a map-side batch. With `by_ranges` (an
    adaptive plan's exchange, whose reduce tasks read ranges of partitions
    decided when the consuming stage is planned) a one-chip exchange cuts
    nothing: it keeps each grouped batch with its bounds (`GroupedBatches`,
    the provider it registers), to be cut per range.

    `stats` receives what the stage did: `bytes` (live-row-scaled),
    `devices`, `host_bytes`, and what it holds for the reduce side:
    `pinned_bytes` (the most a chip held at a time), `slices` and
    `slice_rows` kept in HBM (`slices` are the batches a reduce task is
    handed: packed ones, and slices left as they were), `slices_cut` (the
    non-empty slices the exchange cut) and `slices_packed` (those of them
    that went into a packed batch), `file_batches` that left HBM.

    Returns False — with nothing registered, nothing executed — only when
    the stage can't ride the mesh at all (shape/keys/partition count).
    """
    import os
    import tempfile

    from jax.sharding import NamedSharding

    from blaze_tpu.config import conf
    from blaze_tpu.ops.basic import MemorySourceExec
    from blaze_tpu.ops.common import slice_batch
    from blaze_tpu.ops.shuffle import ShuffleWriterExec
    from blaze_tpu.plan import decode_plan
    from blaze_tpu.plan.from_proto import _partitioning
    from blaze_tpu.runtime import faults
    from blaze_tpu.runtime.executor import execute_stage_or_plan

    writer = stage_plan.shuffle_writer
    Pn = writer.partitioning.num_partitions
    devices = jax.devices()
    if Pn < 2:
        return False
    if conf.fault_injection_spec:
        faults.inject("exchange.stage")
    input_op = decode_plan(writer.input)
    key_idx = mesh_key_indices(writer, input_op.schema)
    if key_idx is None or not key_idx:
        return False
    if any(f.dtype.is_nested for f in input_op.schema.fields):
        return False  # variable element capacities can't stack on the mesh

    schema = input_op.schema
    # P > D (VERDICT r4 #7): device d OWNS the contiguous partition block
    # [d*k, (d+1)*k), k = ceil(P/D). With one device the "exchange" is
    # purely local grouping — partitions stay in HBM with no all_to_all
    # and no host round trip at all, where the file exchange would pull
    # every map output to the host and write it out.
    use_d, kpd = placement.layout(Pn, len(devices))
    mesh_devs = devices[:use_d]
    mesh = Mesh(np.array(mesh_devs), ("p",)) if use_d > 1 else None
    # (batch, live rows) per partition: the exchange hands the counts over
    kept = KeptPartitions(schema, Pn, use_d, kpd)
    grouped = (GroupedBatches(schema, Pn) if by_ranges and mesh is None
               else None)
    keep = kept.keep
    pinned = kept.pinned if grouped is None else grouped.pinned
    file_outputs: List[tuple] = [] if grouped is None else grouped.files
    # Exchanged partitions stay PINNED in HBM until the consuming stage
    # finishes, so the mesh path honors the memory budget, chip by chip:
    # once the bytes pinned on any chip pass half of a chip's budget, what
    # is left takes the file path (the reduce side reads both alike).
    budget = get_manager().total // 2

    def exchange_local(batch: ColumnBatch) -> bool:
        """Single-device exchange: group by partition id on device
        (`group_by_partition`), slice per partition; one host pull (the
        bounds) per macro-batch."""
        from blaze_tpu.parallel.shuffle import partition_ids

        key = ("local_xchg", Pn, tuple(key_idx), batch.shape_key())

        def make():
            def run(b):
                tally = {"ranked": 0, "gathered": 0}
                out = group_by_partition(b, partition_ids(b, key_idx, Pn),
                                         Pn, tally)
                _PLANE_FORMS[key] = tally
                return out

            return run

        # one `exchange` span per macro-batch: dispatch, the bounds pull
        # (where the host waits for the grouping) and the slicing
        with trace.span("exchange", transport="local", partitions=Pn,
                        capacity=batch.capacity) as sp:
            sb, bounds = jit_cache.get_or_compile(key, make)(batch)
            compile_service.note_exchange_planes(**_PLANE_FORMS.get(key, {}))
            bounds = pull_array(bounds, "exchange.local_bounds")
            if grouped is not None:
                grouped.add(sb, bounds)
            else:
                for p in range(Pn):
                    n = int(bounds[p + 1]) - int(bounds[p])
                    if n:
                        keep(p, slice_batch(sb, int(bounds[p]), n), n)
            sp.set(rows=int(bounds[Pn]) - int(bounds[0]))
        return True

    def deal_out(batch: ColumnBatch, n: int) -> list:
        """A batch that lies on one chip as one shard per mesh device:
        equal shares of its rows at their capacity bucket, cut where the
        batch lies and sent chip to chip."""
        per = max(1, -(-n // use_d))
        cap = bucket_capacity(per)
        shards = []
        with placement.on_device(placement.device_of(batch.columns)):
            for i, dev in enumerate(mesh_devs):
                rows = min(max(n - i * per, 0), per)
                cut = slice_batch(batch, i * per, rows, cap)
                shards.append((jax.device_put(cut, dev), rows))
        return shards

    def fit(b: ColumnBatch, cap: int) -> ColumnBatch:
        """`b` at capacity `cap` (no less than its live rows), where it
        lies: every shard of a round has the round's capacity."""
        if b.capacity == cap:
            return b
        key = ("mesh_fit", cap, tuple(schema.fields), b.shape_key())

        def make():
            def run(x):
                # no nested column rides the mesh: every plane is copied
                return x.slice_rows(0, cap, x.num_rows)

            return run

        with placement.on_device(placement.device_of(b.columns)):
            return jit_cache.get_or_compile(key, make)(b)

    sharding = NamedSharding(mesh, P("p")) if mesh is not None else None

    def exchange_round(shards: list) -> bool:
        """One all_to_all. `shards[d]` is (batch on mesh device d, its
        live rows), or None for a chip with nothing to send; the batches
        have one layout. Each partition's received rows are cut out of
        its owner's shard of the output, on that chip. False on quota
        overflow, with nothing kept."""
        rows = [0 if s is None else s[1] for s in shards]
        cap = bucket_capacity(max(rows))
        fitted = [None if s is None else fit(s[0], cap) for s in shards]
        some = next(b for b in fitted if b is not None)
        cols = []
        for dev, b in zip(mesh_devs, fitted):
            if b is None:  # an empty shard of the round's layout
                with placement.on_device(dev):
                    b = jax.device_put(jax.tree.map(jnp.zeros_like, some),
                                       dev)
            cols.append(b.columns)
        # quota: rows one device may send one OWNER device (k partitions)
        q = min(quota * kpd, cap) if quota else cap

        def glue(*xs):
            return jax.make_array_from_single_device_arrays(
                (use_d * xs[0].shape[0],) + xs[0].shape[1:], sharding,
                list(xs))

        key = ("mesh_xchg", Pn, use_d, cap, q, tuple(key_idx),
               some.shape_key())

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
        # one collective program is enqueued on all chips at a time: two
        # queries' drivers entering theirs in different orders on
        # different chips would deadlock
        with _collective_lock:
            out_cols, out_counts, overflow = run(
                jax.tree.map(glue, *cols),
                jax.device_put(np.asarray(rows, np.int32), sharding))
        if int(pull_array(overflow, "exchange.mesh_overflow").sum()) > 0:
            return False
        leaves, treedef = jax.tree.flatten(out_cols)
        if stats is not None:
            # devices the shard_map's output actually sits on
            stats["devices"] = len(leaves[0].devices())
        # (use_d, kpd)
        out_counts = pull_array(out_counts, "exchange.mesh_counts")
        recv_cap = use_d * q  # per-device received capacity
        local = [{sh.device: sh.data for sh in x.addressable_shards}
                 for x in leaves]
        for d, dev in enumerate(mesh_devs):
            got = int(out_counts[d].sum())
            if not got:
                continue
            with placement.on_device(dev):
                mine = ColumnBatch(
                    schema, treedef.unflatten([m[dev] for m in local]),
                    jnp.asarray(got, jnp.int32), recv_cap)
                off = 0
                for j in range(kpd):
                    p, nrows = d * kpd + j, int(out_counts[d, j])
                    if p < Pn and nrows:
                        # compact to the rows' own capacity bucket:
                        # retaining the full staging capacity per slice
                        # would pin O(batches * D^2 * q) padded rows in
                        # HBM across the stage
                        keep(p, slice_batch(mine, off, nrows), nrows)
                    off += nrows
        return True

    def spill_batch_to_file(batch: ColumnBatch, n: int) -> None:
        nonlocal work_dir
        if work_dir is None:
            work_dir = tempfile.mkdtemp(prefix="blaze_tpu_mesh_ovf_")
        i = len(file_outputs)
        data = os.path.join(work_dir, f"stage{stage_id}_meshovf{i}.data")
        index = os.path.join(work_dir, f"stage{stage_id}_meshovf{i}.index")
        op = ShuffleWriterExec(MemorySourceExec([batch], schema),
                               _partitioning(writer.partitioning),
                               data, index)
        # on a mesh these are the exchanged rows that do cross the host
        moved = live_nbytes(batch, n) if mesh is not None else 0
        with trace.span("exchange", transport="file", partitions=Pn,
                        capacity=batch.capacity, rows=n, host_bytes=moved):
            list(execute_plan(op, ExecContext(partition=0,
                                              num_partitions=1)))
        if stats is not None:
            stats["host_bytes"] = stats.get("host_bytes", 0) + moved
        file_outputs.append((data, index))

    def send(shards: list, whole: Optional[tuple] = None) -> None:
        """A round through the all_to_all, or through files where a chip
        is over its budget or the quota overflows (`whole`: the batch the
        shards were dealt out of, which then goes as one file)."""
        if max(pinned) <= budget:
            with trace.span("exchange", transport="mesh", partitions=Pn,
                            devices=use_d, host_bytes=0) as sp:
                if conf.trace_enabled:
                    live = [s for s in shards if s is not None]
                    sp.set(rows=sum(n for _, n in live),
                           bytes=sum(live_nbytes(b, n) for b, n in live),
                           capacity=max(b.capacity for b, _ in live))
                if exchange_round(shards):
                    return
        for s in shards if whole is None else [whole]:
            if s is not None and s[1]:
                spill_batch_to_file(*s)

    def map_task(ctx: ExecContext) -> list:
        """One map task's non-empty output batches with their rows."""
        op = decode_plan(writer.input)  # fresh operator state per task
        out = []
        for batch in execute_stage_or_plan(op, ctx):
            n = pull_rows(batch, "exchange.map_rows")
            if n:
                out.append((batch, n))
        return out

    if mesh is None or sup is None or task_devices is None or ntasks < 2:
        # map side: the tasks one after another on this thread, every
        # batch straight into the exchange as it is produced (whole-stage
        # single-dispatch where the subtree matches); on a mesh a batch
        # lies on one chip and is dealt out from there
        for task in range(ntasks):
            op = decode_plan(writer.input)
            for batch in execute_stage_or_plan(
                    op, ExecContext(partition=task, num_partitions=ntasks)):
                n = pull_rows(batch, "exchange.map_rows")
                if n == 0:
                    continue
                if mesh is not None:
                    send(deal_out(batch, n), (batch, n))
                elif pinned[0] > budget or not exchange_local(batch):
                    spill_batch_to_file(batch, n)
    else:
        # map side, placed: the tasks read partitions that lie on their
        # chips, so each runs there, on the supervisor's pool; their
        # batches then go through the all_to_all from where they lie, one
        # per chip and round
        from blaze_tpu.runtime.supervisor import TaskSpec

        outs = sup.run_tasks(("mesh_map", stage_id), [
            TaskSpec(what=f"mesh_map[{stage_id}:{t}]", attempt_fn=map_task,
                     partition=t, num_partitions=ntasks,
                     device=task_devices[t]) for t in range(ntasks)])
        queues: List[list] = [[] for _ in mesh_devs]
        for out in outs:
            for b, n in out:
                dev = placement.device_of(b.columns)
                if dev not in mesh_devs:  # a wider mesh fed this stage
                    dev = mesh_devs[dev.id % use_d]
                    b = jax.device_put(b, dev)
                queues[mesh_devs.index(dev)].append((b, n))
        for r in range(max(len(q) for q in queues)):
            # batches of one layout (columns' storage, validity, string
            # widths) share a round
            rounds: dict = {}
            for d, q in enumerate(queues):
                if r < len(q):
                    rounds.setdefault(slice_layout(q[r][0]),
                                      [None] * use_d)[d] = q[r]
            for shards in rounds.values():
                send(shards)

    kept.seal_all()
    recv_parts = kept.parts

    def provider(partition: int):
        # defaulted extra args would miscount as task-context params in
        # _call_provider's arity dispatch — close over state instead
        for b, _ in recv_parts[partition]:
            if mesh is not None:
                # The one place an exchanged batch is re-placed: a
                # consumer placed on the partition's owner takes it as it
                # lies, any other gets a copy, chip to chip; none goes
                # through the host. The span closes before the yield: a
                # span must never stay open across a generator's
                # suspension.
                here = placement.here()
                with trace.span("exchange", transport="place",
                                partitions=Pn, capacity=b.capacity,
                                partition=partition, device=here.id,
                                host_bytes=0):
                    b = placement.put(b, here)
            yield b
        yield from _file_segments(file_outputs, partition, schema)

    if stats is not None:
        # live-row-scaled logical bytes: batch_nbytes counts the padded
        # capacity bucket, which would bias the AQE threshold vs the file
        # path's on-disk measure
        total = sum(live_nbytes(b, n) for parts in recv_parts
                    for b, n in parts)
        total += sum(os.path.getsize(d) for d, _ in file_outputs)
        if grouped is not None:
            total += int(grouped.partition_bytes.sum())
        stats["bytes"] = int(total)
        # the most a chip held at a time, a sealed group's slices and its
        # packed batch both counted while both were alive
        stats["pinned_bytes"] = (kept.high_water if grouped is None
                                 else grouped.high_water)
        stats["slices"] = sum(len(parts) for parts in recv_parts)
        stats["slice_rows"] = sum(n for parts in recv_parts
                                  for _, n in parts)
        stats["slices_cut"] = kept.cut
        stats["slices_packed"] = kept.packed
        stats["file_batches"] = len(file_outputs)
    resources.put(f"{namespace}shuffle:{stage_id}",
                  provider if grouped is None else grouped)
    return True
