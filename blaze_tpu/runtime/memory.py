"""Memory manager: budgeted consumers with fair-share spilling.

Ref: datafusion-ext-plans common/memory_manager.rs — a global registry of
MemConsumers (sort, agg tables, repartitioners); over-budget growing
consumers either spill themselves (when holding > 1/8 of their fair share)
or ask others to free memory (:194-323, 16MB min trigger :26) — and the
spill sink of common/onheap_spill.rs (JVM-heap pages on executors, tempfiles
on the driver / in tests :26-75).

TPU translation (SURVEY.md §5.2): the budget models HBM for device-resident
operator state; "spilling" moves batches to host files in the compact zstd
frame format (columnar/serde.py — same format as the reference's spill
serde). Execution here is single-threaded per task, so the condvar wait
protocol degenerates: an over-budget update first asks the LARGEST other
consumer to spill, then self-spills (mirroring the fair-share decision
without the blocking path).
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
import weakref
import zlib
from typing import BinaryIO, Iterator, List, Optional

from blaze_tpu.columnar import serde
from blaze_tpu.columnar.batch import ColumnBatch
from blaze_tpu.columnar.types import Schema
from blaze_tpu.config import conf
from blaze_tpu.runtime import monitor, trace

class MemConsumer:
    """Spillable operator state (ref MemConsumer trait)."""

    name: str = "consumer"

    def mem_used(self) -> int:
        return 0

    def spill(self) -> int:
        """Release memory; returns bytes freed."""
        return 0


# share of the device's memory the manager budgets (ref:
# spark.blaze.memoryFraction=0.7, BASELINE.md set-up)
_DEVICE_MEMORY_FRACTION = 0.7


def _device_budget() -> int:
    """conf.memory_budget=0: derive the budget from the device. The CPU
    test platform reports no limit and keeps a fixed 1 GiB; an
    accelerator that reports none is an error — a guessed budget makes
    sort/agg spill and the in-HBM exchange divert to files silently."""
    import jax

    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return 1 << 30
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            "memory_stats()['bytes_limit']; set conf.memory_budget")
    return int(limit * _DEVICE_MEMORY_FRACTION)


class MemManager:
    def __init__(self, total: Optional[int] = None) -> None:
        self.total = total or conf.memory_budget or _device_budget()
        self._consumers: List[MemConsumer] = []
        self._lock = threading.Lock()
        # serializes consumer-STATE mutation against host-driven spills
        # (bn_spill runs on a host thread while a task thread may be
        # mid-add on the same consumer): consumers hold it while adding
        # state, release() holds it while spilling. RLock so a task
        # thread's own add -> update_mem_used -> spill chain re-enters.
        self.op_lock = threading.RLock()
        self.spill_count = 0
        self.spilled_bytes = 0
        # host spill pages (SpillFile frames buffered but not yet synced
        # to disk) tracked SEPARATELY from _consumers: they count toward
        # the budget but must not join the fair_share() denominator —
        # a spill file is a sink, not a spillable consumer. Weak refs so
        # tracking never keeps a dropped file (and its tempfile) alive.
        self._spill_files: List[weakref.ref] = []
        self.host_spill_bytes = 0
        self.host_spill_files = 0
        # bytes held by in-flight pipelined batches (runtime/pipeline.py)
        # between production on an I/O pool thread and consumption. Like
        # spill pages, these count toward the budget but are NOT a
        # MemConsumer: they cannot be spilled (the consumer is about to
        # use them), so joining the registry would stall the
        # update_mem_used spill-selection loop on an unspillable
        # "largest consumer". Over-budget pipelines stop producing
        # instead (backpressure in PrefetchStream._over_budget_locked).
        self.pipeline_reserved = 0
        # high-water mark of mem_used(): observed at every consumer
        # growth (update_mem_used) and by the monitor sampler; reset at
        # query start so per-query roll-ups report peak_mem_bytes
        self.peak_used = 0
        # -- multi-tenant quota ledger (runtime/service.py) --
        # conf.tenant_quota_spec carves per-tenant ceilings out of the
        # budget; consumers and pipeline reservations are tagged with the
        # registering thread's tenant (trace context). Empty quotas =
        # single-tenant fast path: one dict-emptiness check per update.
        self._quotas: dict = {}
        self._tenant_of: dict = {}          # id(consumer) -> tenant id
        self._tenant_pipeline: dict = {}    # tenant id -> reserved bytes

    # -- registry --
    def register(self, consumer: MemConsumer) -> None:
        tid = trace.current_context().get("tenant_id", "")
        with self._lock:
            self._consumers.append(consumer)
            if tid:
                self._tenant_of[id(consumer)] = tid

    def unregister(self, consumer: MemConsumer) -> None:
        with self._lock:
            if consumer in self._consumers:
                self._consumers.remove(consumer)
            self._tenant_of.pop(id(consumer), None)

    def track_spill(self, sf: "SpillFile") -> None:
        with self._lock:
            self._spill_files.append(weakref.ref(sf))
        self.host_spill_files += 1

    def untrack_spill(self, sf: "SpillFile") -> None:
        with self._lock:
            self._spill_files = [r for r in self._spill_files
                                 if r() is not None and r() is not sf]

    def _live_spill_files(self) -> List["SpillFile"]:
        with self._lock:
            live = [(r, r()) for r in self._spill_files]
            self._spill_files = [r for r, sf in live if sf is not None]
            return [sf for _, sf in live if sf is not None]

    def _consumers_snapshot(self) -> List[MemConsumer]:
        # registry snapshot: supervisor pool threads register/unregister
        # concurrently with accounting walks over the list
        with self._lock:
            return list(self._consumers)

    # -- accounting --
    def mem_used(self) -> int:
        consumed = sum(c.mem_used() for c in self._consumers_snapshot())
        with self._lock:
            reserved = self.pipeline_reserved
        return consumed + self.spill_pages_pending() + reserved

    def observe_peak(self) -> int:
        """mem_used() with high-water-mark tracking. NOT called from
        paths holding self._lock (mem_used walks the registry under it)."""
        used = self.mem_used()
        if used > self.peak_used:
            self.peak_used = used
        return used

    def reset_peak(self) -> None:
        self.peak_used = 0

    def reserve_pipeline(self, nbytes: int) -> None:
        """Charge an in-flight pipelined batch against the budget (and
        the reserving thread's tenant ledger when quotas are active)."""
        with self._lock:
            self.pipeline_reserved += int(nbytes)
            if self._quotas:
                tid = trace.current_context().get("tenant_id", "")
                if tid:
                    self._tenant_pipeline[tid] = \
                        self._tenant_pipeline.get(tid, 0) + int(nbytes)

    def release_pipeline(self, nbytes: int) -> None:
        with self._lock:
            self.pipeline_reserved -= int(nbytes)
            if self._quotas:
                tid = trace.current_context().get("tenant_id", "")
                if tid and tid in self._tenant_pipeline:
                    self._tenant_pipeline[tid] -= int(nbytes)

    def spill_pages_pending(self) -> int:
        """Bytes written to tracked spill files but not yet synced to
        disk — host buffer pages the budget must account for."""
        return sum(sf.pending_bytes for sf in self._live_spill_files())

    def flush_spill_pages(self) -> int:
        """Sync every tracked spill file's buffered frames to disk;
        returns the pending bytes released back to the budget."""
        freed = 0
        for sf in self._live_spill_files():
            freed += sf.flush_pages()
        if freed > 0:
            trace.event("spill_pages_flush", freed_bytes=freed)
        return freed

    def fair_share(self) -> int:
        with self._lock:
            n = max(len(self._consumers), 1)
        return self.total // n

    # -- tenant quotas --
    def set_tenant_quotas(self, spec: Optional[dict]) -> None:
        """Install per-tenant ceilings from conf.tenant_quota_spec: int
        values are bytes, floats in (0, 1] are fractions of the budget.
        None/{} clears quotas (single-tenant fast path)."""
        quotas: dict = {}
        for tid, v in (spec or {}).items():
            if isinstance(v, float) and 0 < v <= 1:
                quotas[tid] = int(self.total * v)
            else:
                quotas[tid] = int(v)
        with self._lock:
            self._quotas = quotas
            self._tenant_pipeline = {}

    def tenant_quota(self, tenant: str) -> Optional[int]:
        with self._lock:
            return self._quotas.get(tenant)

    def _tenant_consumers(self, tenant: str) -> List[MemConsumer]:
        with self._lock:
            return [c for c in self._consumers
                    if self._tenant_of.get(id(c), "") == tenant]

    def tenant_used(self, tenant: str) -> int:
        used = sum(c.mem_used() for c in self._tenant_consumers(tenant))
        with self._lock:
            return used + self._tenant_pipeline.get(tenant, 0)

    def tenant_usage(self) -> dict:
        """{tenant: bytes in use} over every tenant with tagged state or
        a declared quota — the Prometheus per-tenant gauge source."""
        with self._lock:
            tids = set(self._quotas) | set(self._tenant_of.values()) \
                | set(self._tenant_pipeline)
        return {tid: self.tenant_used(tid) for tid in sorted(tids)}

    def update_mem_used(self, updater: MemConsumer) -> None:
        """Called by a consumer after growing; triggers spills if needed.

        Decision mirrors memory_manager.rs:236-323: over budget, a grower
        holding more than 1/8 of its fair share self-spills, otherwise the
        largest other consumer is asked first (the reference's 16MB
        min-trigger floor is intentionally not applied — tiny budgets must
        force spills, which its own fuzztests also rely on).
        """
        used = self.observe_peak()
        with self._lock:
            tenant = (self._tenant_of.get(id(updater), "")
                      if self._quotas else "")
            quota = self._quotas.get(tenant)
        if quota:
            # quota enforcement BEFORE the global check: an over-quota
            # tenant sheds its OWN working set (grower first, then its
            # largest same-tenant sibling) — it can never reach across
            # and evict another tenant's state
            t_over = self.tenant_used(tenant) - quota
            if t_over > 0:
                trace.event("tenant_over_quota", tenant_id=tenant,
                            over_bytes=t_over, quota_bytes=quota)
                freed = updater.spill()
                self._note_spill(freed)
                t_over -= freed
                while t_over > 0:
                    sibs = sorted(
                        (c for c in self._tenant_consumers(tenant)
                         if c is not updater and c.mem_used() > 0),
                        key=lambda c: -c.mem_used())
                    if not sibs:
                        break
                    freed = sibs[0].spill()
                    self._note_spill(freed)
                    if freed <= 0:
                        break
                    t_over -= freed
                used = self.mem_used()
        if used <= self.total:
            return
        # cheapest reclaim first: sync buffered spill pages to disk —
        # accounting then matches the consumer-only view, so consumer
        # spill decisions are unchanged when no pages were pending
        used -= self.flush_spill_pages()
        if used <= self.total:
            return
        over = used - self.total
        share = self.fair_share()
        if updater.mem_used() > share // 8:
            freed = updater.spill()
            self._note_spill(freed)
            over -= freed
        while over > 0:
            # with quotas active the grower's spill pressure stays inside
            # its own tenant while that tenant still has spillable state;
            # cross-tenant eviction is the last resort before OOM
            others = sorted((c for c in self._consumers_snapshot()
                             if c is not updater and c.mem_used() > 0),
                            key=lambda c: -c.mem_used())
            if tenant:
                with self._lock:
                    same = [c for c in others
                            if self._tenant_of.get(id(c), "") == tenant]
                if same:
                    others = same
            if not others:
                if updater.mem_used() > 0:
                    freed = updater.spill()
                    self._note_spill(freed)
                    if freed <= 0:
                        break
                    over -= freed
                    continue
                break
            freed = others[0].spill()
            self._note_spill(freed)
            if freed <= 0:
                break
            over -= freed

    def _note_spill(self, freed: int) -> None:
        if freed > 0:
            self.spill_count += 1
            self.spilled_bytes += freed
            trace.event("spill", spill_bytes=freed)

    def release(self, bytes_needed: int,
                tenant: Optional[str] = None) -> int:
        """Host-driven reclamation (ref OnHeapSpillManager.scala:61-144:
        Spark's memory manager can force executor spill state to disk
        under heap pressure; the C ABI exposes this as bn_spill so the
        embedding layer can reclaim without killing the task). Spills
        the largest consumers first until `bytes_needed` is freed; a
        consumer that yields nothing is skipped, not a stop condition
        (smaller spillable consumers behind it must still drain).
        `tenant` scopes the sweep to one tenant's consumers — the
        degradation ladder's force-spill rung passes the failing query's
        tenant so its recovery can't evict other tenants' working sets.
        Returns bytes actually freed."""
        freed = 0
        with self.op_lock:
            with self._lock:
                candidates = sorted(
                    (c for c in self._consumers
                     if not tenant
                     or self._tenant_of.get(id(c), "") == tenant),
                    key=lambda c: -c.mem_used())
            for c in candidates:
                if freed >= bytes_needed:
                    break
                if c.mem_used() <= 0:
                    continue
                got = c.spill()
                self._note_spill(got)
                if got > 0:
                    freed += got
            if freed < bytes_needed:
                freed += self.flush_spill_pages()
        trace.event("mem_release", requested_bytes=bytes_needed,
                    freed_bytes=freed)
        return freed


# built at first use, not at import: the default budget reads the
# device, and importing the package must not initialize a backend
_global: Optional[MemManager] = None
_global_lock = threading.Lock()


def get_manager(ctx=None) -> MemManager:
    global _global
    if ctx is not None and getattr(ctx, "mem_manager", None) is not None:
        return ctx.mem_manager
    with _global_lock:
        if _global is None:
            _global = MemManager()
        return _global


def init(total: int) -> MemManager:
    """Ref: MemManager::init(overhead x memoryFraction), exec.rs:68-71."""
    global _global
    with _global_lock:
        _global = mgr = MemManager(total)
    return mgr


def close_all_quietly(closeables, what: str) -> None:
    """Close every item best-effort. Cleanup paths run during exception
    unwinding (§5.3 double-fault contract): one failing close must
    neither mask the original query error nor stop the remaining
    closes — failures are logged and swallowed."""
    import logging

    for c in closeables:
        try:
            c.close()
        except Exception:  # noqa: BLE001 — see contract above
            logging.getLogger(__name__).warning(
                "%s close failed during cleanup", what, exc_info=True)


class SpillFile:
    """A sequence of serialized batches in a host tempfile (ref FileSpill,
    onheap_spill.rs:26-75; format = the zstd batch frames)."""

    def __init__(self, schema: Schema, dir: Optional[str] = None,
                 manager: Optional[MemManager] = None) -> None:
        self.schema = schema
        d = dir or conf.spill_dir
        os.makedirs(d, exist_ok=True)
        # pid-tagged name: runtime/artifacts.sweep_orphans reclaims
        # spill files whose owning process died mid-task
        fd, self.path = tempfile.mkstemp(
            prefix=f"blz{os.getpid()}-", suffix=".spill", dir=d)
        self._fp: Optional[BinaryIO] = os.fdopen(fd, "w+b")
        self.bytes_written = 0
        self.num_batches = 0
        # frames written but not yet synced to disk: host buffer pages
        # that count against the owning manager's budget
        self.pending_bytes = 0
        # per-frame (offset, crc32) recorded at write time: a spill
        # never outlives its process, so the checksums live here rather
        # than in a footer; read()/read_host() verify the file against
        # them before a single frame decodes
        self._frame_crcs: list = []
        self._quarantined: list = []
        self._manager = manager
        if manager is not None:
            manager.track_spill(self)

    def write(self, batch: ColumnBatch) -> int:
        from blaze_tpu.runtime import faults

        t0 = time.perf_counter_ns()
        if conf.fault_injection_spec:
            faults.inject("spill.write")
        t1 = time.perf_counter_ns()
        # serialize outside the spill window (it bills serde_encode);
        # the spill term is the injected stall + the file write itself
        buf = serde.serialize_batch(batch)
        t2 = time.perf_counter_ns()
        if conf.artifact_checksums:
            self._frame_crcs.append((self.bytes_written, zlib.crc32(buf)))
        self._fp.write(buf)
        n = len(buf)
        self.bytes_written += n
        self.num_batches += 1
        self.pending_bytes += n
        if self._manager is not None:
            self._manager.host_spill_bytes += n
        if conf.monitor_enabled:
            monitor.count_copy("spill", n)
            monitor.count_time("spill", (t1 - t0) +
                               (time.perf_counter_ns() - t2))
        return n

    def flush_pages(self) -> int:
        """Sync buffered frames to disk; returns pending bytes released."""
        freed = self.pending_bytes
        if self._fp is not None and freed:
            self._fp.flush()
            os.fsync(self._fp.fileno())
        self.pending_bytes = 0
        return freed

    def _verify_frames(self) -> None:
        """Re-read verification against the write-time frame crcs (the
        spill never outlives the process, so in-memory checksums are the
        whole-file digest). A mismatch quarantines the file and raises
        CorruptArtifactError — retryable: the task's retry rebuilds its
        spill from the input stream, there is no lineage to repair."""
        from blaze_tpu.runtime import artifacts, faults

        if not conf.artifact_checksums:
            return
        faults.maybe_corrupt("corrupt.spill", self.path)
        self._fp.seek(0)
        try:
            frames, _crc = artifacts.walk_frames(self._fp)
            ok = frames == self._frame_crcs
        except ValueError:
            ok = False
        if not ok:
            qpath = artifacts.note_corruption(
                self.path, "spill frame checksum mismatch")
            if qpath:
                self._quarantined.append(qpath)
            raise faults.CorruptArtifactError(
                f"spill checksum mismatch in {self.path} (quarantined)")

    def read(self) -> Iterator[ColumnBatch]:
        from blaze_tpu.runtime import faults, pipeline

        t0 = time.perf_counter_ns()
        if conf.fault_injection_spec:
            faults.inject("spill.read")
        self.flush_pages()
        self._verify_frames()
        self._fp.seek(0)
        if conf.monitor_enabled:
            # the whole file is about to be re-read; counted up front
            # (the lazy prefetch below consumes every frame). The frame
            # reads themselves bill serde_decode; spill gets the fsync.
            monitor.count_copy("spill", self.bytes_written)
            monitor.count_time("spill", time.perf_counter_ns() - t0)
        # read+decompress frames ahead on the I/O pool; the k-way merge
        # consumer interleaves many runs, and each run's readahead is
        # charged against the budget so merges can't silently re-inflate
        # the memory the spill was supposed to shed
        return pipeline.prefetch(serde.read_batches(self._fp, self.schema),
                                 manager=self._manager, name="spill_read")

    def read_host(self):
        """Frames as host numpy batches (serde.HostBatch) — the spill
        merge consumes runs host-side (ops/host_sort.py)."""
        from blaze_tpu.runtime import faults, pipeline

        t0 = time.perf_counter_ns()
        if conf.fault_injection_spec:
            faults.inject("spill.read")
        self.flush_pages()
        self._verify_frames()
        self._fp.seek(0)
        if conf.monitor_enabled:
            monitor.count_copy("spill", self.bytes_written)
            monitor.count_time("spill", time.perf_counter_ns() - t0)
        return pipeline.prefetch(
            serde.read_batches_host(self._fp, self.schema),
            manager=self._manager, name="spill_read")

    def close(self) -> None:
        if self._fp is not None:
            self._fp.close()
            self._fp = None
            self.pending_bytes = 0
            if self._manager is not None:
                self._manager.untrack_spill(self)
            try:
                os.unlink(self.path)
            except OSError:
                pass
            # a quarantined spill is ephemeral evidence: the retry that
            # follows rebuilds the data, so closing reclaims it (a
            # shuffle pair's quarantine, by contrast, is kept)
            for q in self._quarantined:
                try:
                    os.unlink(q)
                except OSError:
                    pass
            self._quarantined = []

    def __del__(self):
        self.close()


def batch_nbytes(batch: ColumnBatch) -> int:
    """Device-memory estimate of a batch (capacity-based, validity incl.)."""
    total = 0
    for c in batch.columns:
        total += _col_nbytes(c)
    return total


def _col_nbytes(c) -> int:
    from blaze_tpu.columnar.batch import (
        DictData, ListData, StringData, StructData,
    )

    n = 0
    if isinstance(c.data, DictData):
        # encoded resident form: codes + the small dictionary (NOT the
        # expanded (capacity, width) matrix — that is the point)
        n += (4 * c.data.codes.shape[0] + c.data.dict_bytes.size
              + 4 * c.data.dict_lengths.shape[0])
    elif isinstance(c.data, StringData):
        n += c.data.bytes.size + 4 * c.data.lengths.shape[0]
    elif isinstance(c.data, ListData):
        n += 4 * c.data.offsets.shape[0] + _col_nbytes(c.data.elements)
    elif isinstance(c.data, StructData):
        n += sum(_col_nbytes(ch) for ch in c.data.children)
    else:
        n += c.data.size * c.data.dtype.itemsize
    if c.validity is not None:
        n += c.validity.shape[0]
    return n
