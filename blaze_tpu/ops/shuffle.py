"""Shuffle write/read operators: Spark-format .data/.index files, IPC
streams, RSS hooks.

Ref: datafusion-ext-plans shuffle_writer_exec.rs / rss_shuffle_writer_exec.rs
+ shuffle/{sort,bucket,single}_repartitioner.rs (write side) and
ipc_reader_exec.rs / ipc_writer_exec.rs (read + broadcast side), with the
file formats of SURVEY.md §2.6: one `.data` file of concatenated
per-partition zstd frames and a little-endian u64 offsets `.index` file
committed through Spark's IndexShuffleBlockResolver.

TPU-first redesign of the repartitioner: partition ids are computed on
device with the bit-exact Spark murmur3 kernel (exprs/hash.py), rows are
grouped per partition by ONE variadic sort (no per-partition array builders
or radix-sorted PI vectors), and the sorted batch is pulled to host once,
then sliced into per-partition frames (columnar/serde.py). The on-mesh
all_to_all variant lives in parallel/shuffle.py.
"""

from __future__ import annotations

import dataclasses
import inspect
import os
import time
from typing import Callable, Iterator, List

import jax
import jax.numpy as jnp
import numpy as np

from blaze_tpu.columnar import serde
from blaze_tpu.columnar.batch import ColumnBatch, pull_array, pull_rows
from blaze_tpu.columnar.types import Schema
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.compiler import compile_expr
from blaze_tpu.exprs.hash import SPARK_SHUFFLE_SEED, hash_columns, pmod
from blaze_tpu.ops.base import BatchStream, ExecContext, Operator, count_stream
from blaze_tpu.config import conf
from blaze_tpu.ops.join import sort_batch_by_keys
from blaze_tpu.runtime import jit_cache, monitor, resources

Array = jax.Array


def _call_provider(provider, ctx: ExecContext):
    """Invoke a registered resource provider with as much task context as
    its signature accepts: (partition, num_partitions) | (partition) | ().
    Arity is decided from the signature, not by retrying on TypeError —
    retries would mask genuine TypeErrors raised inside the provider and
    silently substitute partition-0 data."""
    if not callable(provider):
        return provider
    try:
        params = [p for p in inspect.signature(provider).parameters.values()
                  if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD,
                                p.VAR_POSITIONAL)]
        if any(p.kind == p.VAR_POSITIONAL for p in params):
            nargs = 2
        else:
            nargs = min(2, len(params))
    except (TypeError, ValueError):  # builtins without signatures
        nargs = 1
    if nargs == 2:
        return provider(ctx.partition, ctx.num_partitions)
    if nargs == 1:
        return provider(ctx.partition)
    return provider()


@dataclasses.dataclass(frozen=True)
class Partitioning:
    """Ref: pb.PhysicalHashRepartition (blaze.proto) — hash | single |
    round_robin over `num_partitions`."""
    kind: str                       # "hash" | "single" | "round_robin"
    num_partitions: int
    key_exprs: tuple = ()           # hash only: ir.Expr tuple

    def key(self) -> tuple:
        return (self.kind, self.num_partitions,
                tuple(e.key() for e in self.key_exprs))


def round_robin_start(task_partition: int, num_partitions: int) -> int:
    """Per-task starting position, restart-stable (Spark seeds a Random
    with the task's partitionId so retries land rows identically;
    we derive it from spark-murmur3 of the partition id — deterministic
    and well-spread, though not bit-identical to java.util.Random)."""
    from blaze_tpu.exprs.hash import hash_int32

    h = int(pull_array(
        hash_int32(jnp.asarray([task_partition], jnp.int32),
                   jnp.uint32(SPARK_SHUFFLE_SEED))[0], "shuffle.rr_start"))
    return h % num_partitions


def partition_and_sort(batch: ColumnBatch, part: Partitioning,
                       key_fns, row_offset=0, rr_start: int = 0) -> tuple:
    """(sorted batch grouped by partition id, per-partition counts).

    Round-robin rows get `(rr_start + row_offset + i) % P`: rr_start is
    the task-seeded position and row_offset the running row count across
    the task's batches, so a retried task assigns every row the same
    partition (Spark's restart-stable round robin)."""
    P = part.num_partitions
    mask = batch.row_mask()
    if part.kind == "hash":
        keys = [fn(batch) for fn in key_fns]
        h = hash_columns(keys, SPARK_SHUFFLE_SEED, row_mask=mask)
        pid = pmod(h, P)
    elif part.kind == "single":
        pid = jnp.zeros((batch.capacity,), jnp.int32)
    elif part.kind == "round_robin":
        base = jnp.asarray(row_offset, jnp.int64) + rr_start
        pid = ((base + jnp.arange(batch.capacity, dtype=jnp.int64))
               % P).astype(jnp.int32)
    else:
        raise ValueError(part.kind)
    pid = jnp.where(mask, pid, jnp.int32(P))  # padding last
    sorted_batch = sort_batch_by_keys(batch, [pid.astype(jnp.uint32)])
    spid = jnp.sort(pid)
    bounds = jnp.searchsorted(spid, jnp.arange(P + 1, dtype=jnp.int32))
    counts = bounds[1:] - bounds[:-1]
    return sorted_batch, counts


class ShuffleWriterExec(Operator):
    """Writes the Spark shuffle map output for this task's partition.

    Ref: shuffle_writer_exec.rs — consumes the child stream, produces an
    empty output stream; side effect is the committed .data/.index pair
    (parsed by BlazeShuffleWriterBase.scala:84-96 into partitionLengths).
    """

    def __init__(self, child: Operator, partitioning: Partitioning,
                 data_path: str, index_path: str) -> None:
        super().__init__([child])
        self.partitioning = partitioning
        self.data_path = data_path
        self.index_path = index_path
        if partitioning.kind == "hash":
            self._key_fns = [compile_expr(e, child.schema)
                             for e in partitioning.key_exprs]
        else:
            self._key_fns = []

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def plan_key(self) -> tuple:
        return ("shuffle_write", self.partitioning.key(),
                self.children[0].plan_key())

    def execute(self, ctx: ExecContext) -> BatchStream:
        from blaze_tpu.runtime import artifacts, memory as M

        # reclaim dead writers' .inprogress. temps before producing our own
        artifacts.sweep_orphans([os.path.dirname(self.data_path) or "."])
        state = _make_writer_state(self.partitioning.num_partitions,
                                   M.get_manager(ctx))
        keys_jit = not any(ir.contains_host_fn(e)
                           for e in self.partitioning.key_exprs)
        is_rr = self.partitioning.kind == "round_robin"
        rr = (round_robin_start(ctx.partition,
                                self.partitioning.num_partitions)
              if is_rr else 0)
        # rr keys the cache ONLY for round robin (hash/single programs
        # ignore it — per-task keys would recompile identical programs)
        key = ("shuffle_part", keys_jit, rr if is_rr else None,
               self.plan_key())
        row_offset = 0

        def write_out(job):
            # pool-side half of the map task: slice the partition-sorted
            # host batch into per-partition frames (compress) and push
            # them into the writer. state.push serializes on op_lock and
            # the sink has one worker, so push order == submit order.
            hb, counts = job
            offs = np.concatenate([[0], np.cumsum(counts)])
            for p in range(self.partitioning.num_partitions):
                if counts[p]:
                    state.push(p, serde.serialize_slice(
                        hb, int(offs[p]), int(offs[p + 1])))

        from blaze_tpu.ops.host_sort import host_nbytes
        from blaze_tpu.runtime import pipeline

        # overlap batch i's compress+write with batch i+1's
        # partition-split compute; inline (serial) when pipelining is off
        sink = pipeline.Sink(write_out, ctx=ctx, manager=M.get_manager(ctx),
                             name="shuffle_write")
        committed = False
        try:
            from blaze_tpu.runtime.executor import execute_stage_or_plan

            for batch in execute_stage_or_plan(self.children[0], ctx):
                ctx.check_running()
                n = pull_rows(batch, "shuffle.input_rows")
                if n == 0:
                    continue
                with self.metrics.timer():
                    fn = jit_cache.get_or_compile(
                        key + batch.shape_key(),
                        lambda: (lambda b, off: partition_and_sort(
                            b, self.partitioning, self._key_fns,
                            row_offset=off, rr_start=rr)),
                        jit=keys_jit)
                    sb, counts = fn(batch, jnp.asarray(row_offset,
                                                       jnp.int64))
                    row_offset += n
                    cap = max(batch.capacity, 1)
                    self.metrics.add(
                        "shuffle_logical_bytes",
                        M.batch_nbytes(batch) * n // cap)
                    hb = serde.to_host(sb)
                    sink.submit(
                        (hb, pull_array(counts, "shuffle.part_counts")),
                        host_nbytes(hb))
            # drain every pending frame (re-raising any pool-side error)
            # BEFORE the crash-atomic commit sees the buffers
            sink.close()
            t0 = time.perf_counter_ns()
            with self.metrics.timer():
                os.makedirs(os.path.dirname(self.data_path) or ".",
                            exist_ok=True)
                # crash-atomic: stage temps, fsync, rename data-then-index
                lengths = artifacts.commit_shuffle_pair(
                    state.commit, self.data_path, self.index_path)
            if conf.monitor_enabled:
                # map-output commit (fsync + rename) is the write half of
                # the critical path's shuffle_io term; the read half lands
                # in serde_decode (read_batch windows cover file reads)
                monitor.count_time("shuffle_io",
                                   time.perf_counter_ns() - t0)
            self.metrics.add("shuffle_bytes_written", int(sum(lengths)))
            self.metrics.add("spill_count", state.spill_chunks)
            committed = True
        finally:
            if not committed:
                sink.abort()
            state.close()
        return iter(())


def _make_writer_state(num_partitions: int, manager):
    """Choose the map-output writer backend: the C++ bn_shuffle_* writer
    (budgeted buffers, spill, native .data/.index commit — one Python loop
    fewer on the hot path) when the native library is loaded, else the
    Python buffers. Both honor the MemConsumer protocol and produce
    byte-identical files."""
    from blaze_tpu import native

    if native.available():
        try:
            return _NativeWriterState(num_partitions, manager)
        except Exception:  # noqa: BLE001 — never fail a query over this
            pass
    return _WriterBuffers(num_partitions, manager)


class _NativeWriterState:
    """MemConsumer adapter over native.NativeShuffleWriter (bn_shuffle_*)."""

    name = "shuffle_writer"

    def __init__(self, num_partitions: int, manager) -> None:
        from blaze_tpu import native
        from blaze_tpu.config import conf as _conf

        os.makedirs(_conf.spill_dir, exist_ok=True)
        self._w = native.NativeShuffleWriter(
            num_partitions, spill_dir=_conf.spill_dir,
            mem_budget=1 << 62)  # the MemManager drives spilling, not C++
        self.manager = manager
        self.spill_chunks = 0
        manager.register(self)

    def mem_used(self) -> int:
        return int(self._w.mem_used())

    def spill(self) -> int:
        before = self.mem_used()
        if before == 0:
            return 0
        self._w.spill()
        self.spill_chunks += 1
        return before - self.mem_used()

    def push(self, p: int, frame: bytes) -> None:
        if conf.monitor_enabled:
            monitor.count_copy("shuffle", len(frame))
        # op_lock: serialize against host-driven release() (bn_spill)
        with self.manager.op_lock:
            self._w.push(p, frame)
            self.manager.update_mem_used(self)

    def commit(self, data_path: str, index_path: str) -> List[int]:
        return list(self._w.commit(data_path, index_path))

    def close(self) -> None:
        self.manager.unregister(self)
        self._w.close()


class _WriterBuffers:
    """Per-partition frame buffers with host-file spill (ref the
    repartitioners' MemConsumer spill of sort_repartitioner.rs:199-213 —
    here frames are already serialized host bytes, so spilling appends them
    to a tempfile and commit replays them in partition order)."""

    name = "shuffle_writer"

    def __init__(self, num_partitions: int, manager) -> None:
        import tempfile

        from blaze_tpu.config import conf as _conf

        self.P = num_partitions
        self.buffers: List[List[bytes]] = [[] for _ in range(num_partitions)]
        self.bytes = 0
        self.manager = manager
        os.makedirs(_conf.spill_dir, exist_ok=True)
        self._spill_fp = None
        self._spill_segs: List[List[tuple]] = [[] for _ in
                                               range(num_partitions)]
        self.spill_chunks = 0
        manager.register(self)

    def mem_used(self) -> int:
        return self.bytes

    def spill(self) -> int:
        if self.bytes == 0:
            return 0
        import tempfile

        from blaze_tpu.config import conf as _conf

        if self._spill_fp is None:
            self._spill_fp = tempfile.TemporaryFile(dir=_conf.spill_dir)
        freed = self.bytes
        for p in range(self.P):
            for chunk in self.buffers[p]:
                off = self._spill_fp.tell()
                self._spill_fp.write(chunk)
                self._spill_segs[p].append((off, len(chunk)))
                self.spill_chunks += 1
            self.buffers[p] = []
        self.bytes = 0
        return freed

    def push(self, p: int, frame: bytes) -> None:
        if conf.monitor_enabled:
            monitor.count_copy("shuffle", len(frame))
        with self.manager.op_lock:
            self.buffers[p].append(frame)
            self.bytes += len(frame)
            self.manager.update_mem_used(self)

    def drain(self, p: int):
        for off, ln in self._spill_segs[p]:
            self._spill_fp.seek(off)
            yield self._spill_fp.read(ln)
        for chunk in self.buffers[p]:
            yield chunk

    def commit(self, data_path: str, index_path: str) -> List[int]:
        lengths = []
        with open(data_path, "wb") as f:
            for p in range(self.P):
                start = f.tell()
                for chunk in self.drain(p):
                    f.write(chunk)
                lengths.append(f.tell() - start)
        offsets = np.concatenate([[0], np.cumsum(lengths)]).astype("<u8")
        with open(index_path, "wb") as f:
            f.write(offsets.tobytes())
        return lengths

    def close(self) -> None:
        self.manager.unregister(self)
        if self._spill_fp is not None:
            self._spill_fp.close()


class RssPartitionWriterBase:
    """Ref: Shims.scala:204-208 RssPartitionWriterBase — push interface for
    remote shuffle services."""

    def write(self, partition_id: int, payload: bytes) -> None:
        raise NotImplementedError

    def flush(self) -> None:
        pass


class RssShuffleWriterExec(ShuffleWriterExec):
    """Ref: rss_shuffle_writer_exec.rs — same repartitioning, pushes frames
    to an RSS writer resource instead of committing local files."""

    def __init__(self, child: Operator, partitioning: Partitioning,
                 rss_resource_id: str) -> None:
        super().__init__(child, partitioning, data_path="", index_path="")
        self.rss_resource_id = rss_resource_id

    def plan_key(self) -> tuple:
        return ("rss_shuffle_write", self.partitioning.key(),
                self.children[0].plan_key())

    def execute(self, ctx: ExecContext) -> BatchStream:
        P = self.partitioning.num_partitions
        writer: RssPartitionWriterBase = resources.get(self.rss_resource_id)
        keys_jit = not any(ir.contains_host_fn(e)
                           for e in self.partitioning.key_exprs)
        is_rr = self.partitioning.kind == "round_robin"
        rr = (round_robin_start(ctx.partition,
                                self.partitioning.num_partitions)
              if is_rr else 0)
        key = ("shuffle_part", keys_jit, rr if is_rr else None,
               self.plan_key())
        row_offset = 0
        for batch in self.children[0].execute(ctx):
            ctx.check_running()
            n = pull_rows(batch, "shuffle.input_rows")
            if n == 0:
                continue
            with self.metrics.timer():
                fn = jit_cache.get_or_compile(
                    key + batch.shape_key(),
                    lambda: (lambda b, off: partition_and_sort(
                        b, self.partitioning, self._key_fns,
                        row_offset=off, rr_start=rr)),
                    jit=keys_jit)
                sb, counts = fn(batch, jnp.asarray(row_offset, jnp.int64))
                row_offset += n
                hb = serde.to_host(sb)
                counts = pull_array(counts, "shuffle.part_counts")
                offs = np.concatenate([[0], np.cumsum(counts)])
                for p in range(P):
                    if counts[p]:
                        frame = serde.serialize_slice(
                            hb, int(offs[p]), int(offs[p + 1]))
                        if conf.monitor_enabled:
                            monitor.count_copy("shuffle", len(frame))
                        writer.write(p, frame)
        writer.flush()
        return iter(())


def read_shuffle_partition(data_path: str, index_path: str, partition: int,
                           schema: Schema) -> Iterator[ColumnBatch]:
    """Reduce-side local read of one partition's frames (the FileSegment
    zero-copy path of BlazeBlockStoreShuffleReaderBase, SURVEY.md §2.6).
    The segment is fetched + checksum-verified through
    artifacts.fetch_segment — a corrupt map output is quarantined and
    repaired by lineage re-execution before a single frame decodes."""
    import io

    from blaze_tpu.runtime import artifacts

    blob = artifacts.fetch_segment(data_path, index_path, partition)
    # one decompressor for the whole partition: zstd context setup costs
    # per .decompress() call dominate small frames
    dctx = serde.zstandard.ZstdDecompressor()
    f = io.BytesIO(blob)
    while True:
        b = serde.read_batch(f, schema, dctx=dctx)
        if b is None:
            break
        yield b


def read_shuffle_partition_host(data_path: str, index_path: str,
                                partition: int, schema: Schema):
    """Same fetch, decoded only to HOST numpy frames (serde.HostBatch):
    IpcReaderExec coalesces them into one macro-batch upload instead of
    paying a device decode per frame."""
    import io

    from blaze_tpu.runtime import artifacts

    blob = artifacts.fetch_segment(data_path, index_path, partition)
    dctx = serde.zstandard.ZstdDecompressor()
    f = io.BytesIO(blob)
    while True:
        hb = serde.read_batch_host(f, schema, dctx=dctx)
        if hb is None:
            break
        yield hb


class IpcReaderExec(Operator):
    """Ref: ipc_reader_exec.rs — pulls serialized segments from a registered
    provider (shuffle reader / broadcast) and decodes them to batches."""

    def __init__(self, schema: Schema, resource_id: str,
                 num_partitions: int = 1) -> None:
        super().__init__([])
        self._schema = schema
        self.resource_id = resource_id
        self.num_partitions = num_partitions

    @property
    def schema(self) -> Schema:
        return self._schema

    def plan_key(self) -> tuple:
        return ("ipc_reader", tuple(self._schema.names()))

    def execute(self, ctx: ExecContext) -> BatchStream:
        def gen():
            from blaze_tpu.ops import host_sort
            from blaze_tpu.ops.common import adaptive_target_bytes

            # the node's num_partitions is authoritative: it is the count
            # the stream was WRITTEN with (providers that fan work out by
            # partition — e.g. the fallback scan split — must see it even
            # when the local ctx defaults to 1)
            eff_ctx = ctx
            if self.num_partitions and \
                    self.num_partitions != ctx.num_partitions:
                eff_ctx = dataclasses.replace(
                    ctx, num_partitions=self.num_partitions)
            from blaze_tpu.runtime import memory as M, pipeline

            source = _call_provider(resources.get(self.resource_id),
                                    eff_ctx)
            # read-side readahead: the provider's fetch+decompress (e.g.
            # shuffle_manager.get_reader_host decoding partition frames)
            # runs ahead on the I/O pool, charged against the budget,
            # while this thread coalesces/uploads the current macro-batch
            source = pipeline.prefetch(source, ctx=ctx,
                                       manager=M.get_manager(ctx),
                                       name="shuffle_read")
            # host-level coalescing: serialized frames decode to numpy and
            # accumulate toward the macro-batch byte target, then upload
            # ONCE — a per-frame upload+dispatch is a host round trip
            # each. Device-resident items (the mesh exchange path) pass
            # through unchanged.
            hsup = host_sort.host_supported(self._schema)
            target = adaptive_target_bytes()
            pending: list = []
            pending_bytes = 0

            def flush():
                nonlocal pending, pending_bytes
                if pending:
                    hb = host_sort.host_concat(pending)
                    pending, pending_bytes = [], 0
                    yield host_sort.host_to_device(hb)

            def absorb(hb):
                nonlocal pending_bytes
                pending.append(hb)
                pending_bytes += host_sort.host_nbytes(hb)

            try:
                for seg in source:
                    ctx.check_running()
                    if isinstance(seg, ColumnBatch):
                        yield from flush()
                        yield seg
                    elif isinstance(seg, serde.HostBatch):
                        absorb(seg)
                    elif isinstance(seg, (bytes, bytearray, memoryview)):
                        # no bytes(seg): a memoryview from the mmap
                        # shuffle path decodes straight from the mapped
                        # file (serde reads it via the buffer protocol)
                        if hsup:
                            absorb(serde.deserialize_batch_host(
                                seg, self._schema))
                        else:
                            yield serde.deserialize_batch(seg,
                                                          self._schema)
                    else:  # file-like
                        if hsup:
                            for hb in serde.read_batches_host(seg,
                                                              self._schema):
                                absorb(hb)
                                if pending_bytes >= target:
                                    yield from flush()
                        else:
                            for b in serde.read_batches(seg, self._schema):
                                yield b
                    if pending_bytes >= target:
                        yield from flush()
                yield from flush()
            finally:
                # providers may hand back a pipelined readahead stream
                # (shuffle_manager.get_reader_host): quiesce its producer
                # and release reservations even when this task dies
                # mid-stream (kill, speculation loss, downstream error)
                close = getattr(source, "close", None)
                if close is not None:
                    close()

        return count_stream(self, gen())


class FfiReaderExec(Operator):
    """Ref: ffi_reader_exec.rs — pulls Arrow arrays from a registered
    export iterator (the ConvertToNative row->columnar ingestion path,
    ConvertToNativeBase.scala:59-98). The provider yields pyarrow
    RecordBatches (the C-data crossing is pyarrow's) or ready ColumnBatches.
    """

    def __init__(self, schema: Schema, export_resource_id: str) -> None:
        super().__init__([])
        self._schema = schema
        self.export_resource_id = export_resource_id

    @property
    def schema(self) -> Schema:
        return self._schema

    def plan_key(self) -> tuple:
        return ("ffi_reader", tuple(self._schema.names()))

    def execute(self, ctx: ExecContext) -> BatchStream:
        def gen():
            from blaze_tpu.columnar.arrow_io import batch_from_arrow

            source = _call_provider(resources.get(self.export_resource_id),
                                    ctx)
            for item in source:
                ctx.check_running()
                if isinstance(item, ColumnBatch):
                    yield item
                else:
                    yield batch_from_arrow(item, schema=self._schema)

        return count_stream(self, gen())


class IpcWriterExec(Operator):
    """Ref: ipc_writer_exec.rs — serializes the child stream into
    length-prefixed frames pushed to a registered consumer (broadcast
    collect path, NativeBroadcastExchangeBase.scala:175-184)."""

    def __init__(self, child: Operator, consumer_resource_id: str) -> None:
        super().__init__([child])
        self.consumer_resource_id = consumer_resource_id

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def plan_key(self) -> tuple:
        return ("ipc_writer", self.children[0].plan_key())

    def execute(self, ctx: ExecContext) -> BatchStream:
        consumer: Callable[[bytes], None] = resources.get(
            self.consumer_resource_id)
        total = 0
        for batch in self.children[0].execute(ctx):
            ctx.check_running()
            if pull_rows(batch, "ipc.input_rows") == 0:
                continue
            with self.metrics.timer():
                buf = serde.serialize_batch(batch)
            consumer(buf)
            total += len(buf)
        self.metrics.add("ipc_bytes_written", total)
        return iter(())
