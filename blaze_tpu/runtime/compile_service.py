"""Compile service: shape canonicalization, persistent manifest, pre-warm.

The engine's cold wall-clock is dominated by first-ever-shape XLA compiles:
every (operator, key-count, dtype-mix, capacity) combination is its own jit
program, and before this module nothing pre-warmed, bounded, or even
recorded the shape population.  This subsystem owns that population
end-to-end (the step from ad-hoc `jit_cache.get_or_compile` calls to a
managed compile service; cf. Flare's compile-amortization argument and
SystemML's dedicated fusion-plan layer in PAPERS.md):

* **Canonicalization policy** — program shapes are already bucketed to
  power-of-two capacities (`batch.bucket_capacity`); above
  `conf.canonical_pow2_limit` the service collapses buckets further onto
  power-of-FOUR rungs, halving the size axis of the shape space for the
  large capacities where compiles are the most expensive.  Sort kernels,
  join build sides, agg collapse inputs and whole-stage batch *counts*
  route through it (`canonical_batch` / `canonical_batch_count`).  Rows
  between the natural bucket and the canonical rung are padding
  (masked everywhere by `row_mask`); the overhead is counted in
  `canonicalization_waste_rows`.

* **Shape registry + manifest** — every jit-cache event (hit / miss /
  compile + wall time) is recorded per cache key, together with enough
  host-side metadata to *replay* sort-kernel shapes from scratch.  The
  registry persists as JSON next to the persistent XLA cache dir,
  versioned by an engine/config fingerprint: a manifest written by one
  process warms another.

* **Pre-warm driver** — ``python -m blaze_tpu.runtime.compile_service
  --warm`` (or ``make warm``) replays (1) the manifest's recorded sort
  shapes and (2) the TPC-DS catalogue's enumerated (query, join-mode)
  cells into the persistent XLA cache ahead of traffic, with progress
  logging and a ``--budget-seconds`` cap.

* **Telemetry** — a process-global `MetricsSet` with
  compile_count / compile_ns / cache_hits / cache_misses /
  canonicalization_waste_rows / stage_attempts / stage_compiled /
  agg_pallas_traces / agg_xla_traces / seg_scan_reductions /
  seg_scatter_reductions / filter_masks_carried / filter_compactions /
  exchange_planes_ranked / exchange_planes_gathered /
  slice_copies / slice_gathers / aqe_reduce_tasks and
  the derived whole_stage_coverage_pct,
  exported as an extra `MetricNode` child by `executor.metric_tree` and
  as a summary line by `tracing.metric_report`.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from blaze_tpu.config import conf
from blaze_tpu.runtime import jit_cache, trace
from blaze_tpu.runtime.metrics import MetricNode, MetricsSet

# --------------------------------------------------------------------------
# telemetry
# --------------------------------------------------------------------------

TELEMETRY = MetricsSet()
# MetricsSet seeds operator-centric counters; the service's set is its own
# namespace, so start clean (reset() clears under the set's lock).
TELEMETRY.reset()

_COUNTERS = (
    "compile_count", "compile_ns", "cache_hits", "cache_misses",
    "canonicalization_waste_rows", "stage_attempts", "stage_compiled",
    "agg_pallas_traces", "agg_xla_traces",
    "seg_scan_reductions", "seg_scatter_reductions",
    "seg_sums", "seg_int_sums",
    "filter_masks_carried", "filter_compactions",
    "exchange_slices_kept", "exchange_rows_kept",
    "exchange_slices_cut", "exchange_slices_packed",
    "exchange_planes_ranked", "exchange_planes_gathered",
    "slice_copies", "slice_gathers",
    "aqe_reduce_tasks",
)
for _c in _COUNTERS:
    TELEMETRY.values[_c] = 0
TELEMETRY.values["whole_stage_coverage_pct"] = 0


def telemetry_node() -> MetricNode:
    """The service metrics as a MetricNode (appended by metric_tree).

    handler stays None: embedding layers that set a handler on the *root*
    only (the common pattern) see an inert extra child; layers that walk
    the tree and install handlers everywhere get the compile counters.
    """
    return MetricNode(TELEMETRY, [])


def _coverage_update() -> None:
    # read-modify-write of two counters: hold the set's lock for the
    # whole derivation so a concurrent add() can't interleave
    with TELEMETRY._lock:
        att = TELEMETRY.values.get("stage_attempts", 0)
        if att:
            TELEMETRY.values["whole_stage_coverage_pct"] = round(
                100 * TELEMETRY.values.get("stage_compiled", 0) / att)


def note_stage_attempt() -> None:
    TELEMETRY.add("stage_attempts", 1)
    _coverage_update()


def note_stage_compiled() -> None:
    TELEMETRY.add("stage_compiled", 1)
    _coverage_update()


def note_agg_trace(pallas: bool) -> None:
    """ops/mxu_agg traced one grouped-accumulate program: the Pallas
    kernel, or the portable XLA formulation."""
    TELEMETRY.add("agg_pallas_traces" if pallas else "agg_xla_traces", 1)


def note_seg_reductions(scan: int = 0, scatter: int = 0, sums: int = 0,
                        int_sums: int = 0) -> None:
    """ops/agg dispatched one agg_collapse program whose per-group
    reductions (ops/segment) were built as `scan` scans and `scatter`
    scatters; `sums` of them add numbers (flags counted aside), `int_sums`
    of those over an integer array, as seg_sum saw its argument: the type
    money was summed in, which an exact comparison of the answers cannot
    tell (a double adds SF1's cents exactly too)."""
    TELEMETRY.add("seg_scan_reductions", scan)
    TELEMETRY.add("seg_scatter_reductions", scatter)
    TELEMETRY.add("seg_sums", sums)
    TELEMETRY.add("seg_int_sums", int_sums)


def note_filter_batches(carried: int = 0, compacted: int = 0) -> None:
    """A FilterExec's verdict on one batch was dispatched: `compacted` by
    the filter's own program (`fused.filter…`, which moves every kept row
    of every plane to its rank), `carried` as a mask inside the work batch
    of the partial aggregate it feeds, whose collapse sorts the dropped
    rows behind the kept ones and moves nothing twice (ops/agg)."""
    TELEMETRY.add("filter_masks_carried", carried)
    TELEMETRY.add("filter_compactions", compacted)


def note_exchange_kept(slices: int, rows: int, cut: int,
                       packed: int) -> None:
    """A shuffle_map stage's in-HBM exchange (parallel/stage_exchange, local
    and mesh transports alike) kept `slices` batches holding `rows` live
    rows for its reduce tasks: rows / slices is what one program of a reduce
    task is handed. It had cut `cut` non-empty per-partition slices, and
    `packed` of them went into a batch packed of several; the others were
    kept as they were cut."""
    TELEMETRY.add("exchange_slices_kept", slices)
    TELEMETRY.add("exchange_rows_kept", rows)
    TELEMETRY.add("exchange_slices_cut", cut)
    TELEMETRY.add("exchange_slices_packed", packed)


def note_exchange_planes(ranked: int = 0, gathered: int = 0) -> None:
    """The one-chip exchange dispatched one `local_xchg` program, which
    moved a batch's planes (each column's data and validity) into
    partition order: `ranked` by scatters to the rows' ranks, `gathered`
    by a gather (parallel/stage_exchange.group_by_partition)."""
    TELEMETRY.add("exchange_planes_ranked", ranked)
    TELEMETRY.add("exchange_planes_gathered", gathered)


def note_aqe_tasks(tasks: int) -> None:
    """A stage of an adaptive plan launches `tasks` reduce tasks, each over
    a coalesced range of shuffle partitions (spark/aqe.py)."""
    TELEMETRY.add("aqe_reduce_tasks", tasks)


def note_slice(copies: bool) -> None:
    """One call of `ops.common.slice_batch`: the rows were cut out by
    contiguous copies of every plane (`ColumnBatch.slice_rows`), or, for
    a batch with a list column, gathered by `take`."""
    TELEMETRY.add("slice_copies" if copies else "slice_gathers", 1)


def telemetry_summary() -> str:
    """One-line counter summary for metric_report ('' when idle)."""
    v = TELEMETRY.snapshot()  # pool threads add() concurrently
    if not (v.get("compile_count") or v.get("cache_hits")
            or v.get("cache_misses")):
        return ""
    return ("compile_service: compiles={compile_count} "
            "compile_ms={ms:.1f} hits={cache_hits} misses={cache_misses} "
            "waste_rows={canonicalization_waste_rows} "
            "stage_coverage={whole_stage_coverage_pct}%".format(
                ms=v.get("compile_ns", 0) / 1e6,
                **{c: v.get(c, 0) for c in
                   _COUNTERS + ("whole_stage_coverage_pct",)}))


@contextlib.contextmanager
def task_scope(metrics: MetricsSet):
    """Attribute service-counter deltas inside the scope to `metrics`.

    Per-task accounting: operators (or the local runner) wrap a task body
    and receive compile_count / compile_ns / cache_hits /
    canonicalization_waste_rows deltas under the same names.
    """
    before = TELEMETRY.snapshot()
    try:
        yield metrics
    finally:
        after = TELEMETRY.snapshot()
        for k in _COUNTERS:
            d = after.get(k, 0) - before.get(k, 0)
            if d:
                metrics.add(k, d)


# --------------------------------------------------------------------------
# canonicalization policy
# --------------------------------------------------------------------------

def canonical_capacity(n: int) -> int:
    """Canonical capacity bucket for `n` rows.

    Up to conf.canonical_pow2_limit this is the plain power-of-two bucket
    (identical shapes to an unbucketed engine run, so small/test workloads
    are byte-for-byte unchanged).  Above the limit, buckets collapse onto
    power-of-four rungs anchored at the limit: 2^14, 2^16, 2^18, ... —
    each rung absorbs two pow2 buckets, halving the large end of the
    shape space where compiles are slowest.

    The rungs stop at the full macro-batch capacity,
    bucket_capacity(conf.max_batch_rows): from there up the bucket is its
    own rung.  That capacity is no data-dependent shape — a scan emits it
    for every batch but its tail, so a program that sees it runs once per
    macro-batch, and padding it doubles that program's device work on
    every batch to save one compile per program kind.
    """
    from blaze_tpu.columnar.batch import bucket_capacity

    cap = bucket_capacity(n)
    limit = int(conf.canonical_pow2_limit)
    if (not conf.enable_compile_canonicalization or cap <= limit
            or limit <= 0 or cap >= bucket_capacity(conf.max_batch_rows)):
        return cap
    base_exp = limit.bit_length() - 1
    exp = cap.bit_length() - 1
    if (exp - base_exp) % 2:
        exp += 1
    return 1 << exp


def canonical_batch_count(n: int) -> int:
    """Canonical rung for a whole-stage batch *count* (the scan length
    axis of stage program shapes): exact up to 2, power-of-two above."""
    if not conf.enable_compile_canonicalization or n <= 2:
        return n
    r = 4
    while r < n:
        r <<= 1
    return r


def canonical_batch(batch, kind: str, raw_rows: Optional[int] = None):
    """Repad `batch` to its canonical capacity rung (no-op when already
    canonical, disabled, or the schema is nested — list element storage
    is compacted per batch and cannot be index-repadded safely).

    The repad itself is one tiny cached gather program; rows added are
    engine padding (masked by row_mask) and are charged to
    canonicalization_waste_rows.
    """
    import jax.numpy as jnp

    cap = int(batch.capacity)
    new_cap = canonical_capacity(cap)
    if new_cap == cap:
        _REGISTRY.note_canonical(kind, cap, cap, raw_rows)
        return batch
    if any(f.dtype.is_nested or f.dtype.wide_decimal for f in batch.schema):
        return batch

    def make():
        def pad(b):
            idx = jnp.minimum(jnp.arange(new_cap, dtype=jnp.int32),
                              b.capacity - 1)
            return b.take(idx, b.num_rows)
        return pad

    fn = jit_cache.get_or_compile(
        ("canon_pad", new_cap, batch.shape_key()), make)
    out = fn(batch)
    TELEMETRY.add("canonicalization_waste_rows", new_cap - cap)
    _REGISTRY.note_canonical(kind, cap, new_cap, raw_rows)
    return out


def pad_batch_list(batches: tuple, kind: str = "stage") -> tuple:
    """Pad a uniform-shape batch tuple to its canonical count rung with
    zero-row copies of batches[0] (identical shape_key; every mask path
    sees num_rows=0, so probe/accumulate/compact treat them as empty)."""
    n = len(batches)
    rung = canonical_batch_count(n)
    if rung == n:
        return batches
    pad = batches[0].with_num_rows(0)
    TELEMETRY.add("canonicalization_waste_rows",
                  (rung - n) * int(batches[0].capacity))
    _REGISTRY.note_canonical(kind + "_count", n, rung, None)
    return batches + (pad,) * (rung - n)


# --------------------------------------------------------------------------
# shape registry + manifest
# --------------------------------------------------------------------------

_REPLAYABLE_KINDS = frozenset((
    "BOOLEAN", "INT8", "INT16", "INT32", "INT64", "FLOAT32", "FLOAT64",
    "STRING", "BINARY", "DATE", "TIMESTAMP", "DECIMAL",
))

MANIFEST_VERSION = 1
_RAW_SHAPE_CAP = 4096  # bound per-kind raw-shape sets in the manifest


def fingerprint() -> str:
    """Engine/config fingerprint versioning the manifest: entries recorded
    under one engine version / platform / shape-relevant config must not
    warm a differently-shaped engine."""
    import hashlib

    import jax

    import blaze_tpu

    payload = {
        "engine": blaze_tpu.__version__,
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "x64": bool(jax.config.jax_enable_x64),
        "min_capacity": conf.min_capacity,
        "min_string_width": conf.min_string_width,
        "batch_size": conf.batch_size,
        "dense_agg_range": conf.dense_agg_range,
        "float_sum_digit_planes": conf.float_sum_digit_planes,
        "canonicalization": conf.enable_compile_canonicalization,
        "canonical_pow2_limit": conf.canonical_pow2_limit,
        "max_batch_rows": conf.max_batch_rows,
    }
    blob = json.dumps(payload, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def default_manifest_path() -> Optional[str]:
    """Manifest lives in the resolved persistent-cache directory.

    Resolution order: BLAZE_TPU_COMPILE_MANIFEST env ("off" disables),
    else `<jax_compilation_cache_dir>/compile_manifest.json`, else (the
    uncached CPU test platform) the checkout's `.jax_cache` so `--warm`
    runs have a stable home there too.
    """
    env = os.environ.get("BLAZE_TPU_COMPILE_MANIFEST", "")
    if env == "off":
        return None
    if env:
        return env
    import jax

    import blaze_tpu

    return os.path.join(jax.config.jax_compilation_cache_dir
                        or blaze_tpu._DEFAULT_CACHE_DIR,
                        "compile_manifest.json")


class ShapeRegistry:
    """In-process record of every jit-cache key seen: kind, hit/miss
    counts, first-call compile time, source, and (for sort kernels) a
    host-reconstructible replay payload.  Thread-safe; serializes to the
    manifest JSON."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.entries: Dict[str, Dict[str, Any]] = {}
        # kind -> {"raw": set(caps), "canonical": set(caps), "raw_rows": set}
        self.canonical: Dict[str, Dict[str, set]] = {}
        self.dirty = False

    # -- jit_cache observer protocol -----------------------------------
    def observe(self, event: str, key, ns: int) -> None:
        kind = jit_cache.kind_of(key)
        kid = repr(key)
        with self._lock:
            e = self.entries.get(kid)
            if e is None:
                e = self.entries[kid] = {
                    "kind": kind, "source": kind, "hits": 0, "misses": 0,
                    "compile_ns": 0, "replay": None,
                }
            if event == "hit":
                e["hits"] += 1
                TELEMETRY.add("cache_hits", 1)
            elif event == "miss":
                e["misses"] += 1
                TELEMETRY.add("cache_misses", 1)
            elif event == "compiled":
                e["compile_ns"] += int(ns)
                TELEMETRY.add("compile_count", 1)
                TELEMETRY.add("compile_ns", int(ns))
            self.dirty = True
        # after the registry lock: the trace log has its own lock and
        # events inherit the calling thread's query/stage/task context
        if event == "compiled":
            trace.event("compile_compiled", op_kind=kind,
                        compile_ns=int(ns))
        elif event in ("hit", "miss"):
            trace.event(f"compile_{event}", op_kind=kind)

    # -- canonicalization accounting -----------------------------------
    def note_canonical(self, kind: str, raw_cap: int, canon_cap: int,
                       raw_rows: Optional[int]) -> None:
        with self._lock:
            c = self.canonical.setdefault(
                kind, {"raw": set(), "canonical": set(), "raw_rows": set()})
            if len(c["raw"]) < _RAW_SHAPE_CAP:
                c["raw"].add(int(raw_cap))
            c["canonical"].add(int(canon_cap))
            if raw_rows is not None and len(c["raw_rows"]) < _RAW_SHAPE_CAP:
                c["raw_rows"].add(int(raw_rows))
            self.dirty = True

    def attach_replay(self, key, payload: Dict[str, Any],
                      source: str) -> None:
        kind = jit_cache.kind_of(key)
        kid = repr(key)
        with self._lock:
            e = self.entries.setdefault(kid, {
                "kind": kind, "source": source,
                "hits": 0, "misses": 0, "compile_ns": 0, "replay": None,
            })
            e["source"] = source
            if e["replay"] is None:
                e["replay"] = payload
            self.dirty = True

    # -- stats ----------------------------------------------------------
    def shape_reduction(self) -> Dict[str, Dict[str, int]]:
        """Per-kind distinct raw vs canonical shape counts (the ≥4x
        acceptance metric reads raw row-count space vs canonical caps)."""
        out = {}
        with self._lock:
            for kind, c in self.canonical.items():
                out[kind] = {
                    "raw_capacities": len(c["raw"]),
                    "raw_rowcounts": len(c["raw_rows"]),
                    "canonical_capacities": len(c["canonical"]),
                }
        return out

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            per_kind: Dict[str, Dict[str, int]] = {}
            for e in self.entries.values():
                k = per_kind.setdefault(
                    e["kind"], {"programs": 0, "compile_ns": 0,
                                "hits": 0, "misses": 0})
                k["programs"] += 1
                k["compile_ns"] += e["compile_ns"]
                k["hits"] += e["hits"]
                k["misses"] += e["misses"]
        return {"programs": sum(v["programs"] for v in per_kind.values()),
                "per_kind": per_kind,
                "shape_reduction": self.shape_reduction()}

    # -- persistence -----------------------------------------------------
    def to_manifest(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "version": MANIFEST_VERSION,
                "fingerprint": fingerprint(),
                "entries": {k: dict(v) for k, v in self.entries.items()},
                "canonical": {
                    kind: {ax: sorted(vals) for ax, vals in c.items()}
                    for kind, c in self.canonical.items()},
            }

    def merge_manifest(self, doc: Dict[str, Any]) -> int:
        """Merge a loaded manifest; returns entries merged (0 on version
        or fingerprint mismatch — a differently-configured engine's
        shapes must not be replayed here)."""
        if not isinstance(doc, dict) or doc.get("version") != MANIFEST_VERSION:
            return 0
        if doc.get("fingerprint") != fingerprint():
            return 0
        n = 0
        with self._lock:
            for kid, e in (doc.get("entries") or {}).items():
                cur = self.entries.get(kid)
                if cur is None:
                    self.entries[kid] = dict(e)
                else:
                    cur["hits"] += e.get("hits", 0)
                    cur["misses"] += e.get("misses", 0)
                    cur["compile_ns"] = max(cur["compile_ns"],
                                            e.get("compile_ns", 0))
                    if cur["replay"] is None:
                        cur["replay"] = e.get("replay")
                n += 1
            for kind, c in (doc.get("canonical") or {}).items():
                mine = self.canonical.setdefault(
                    kind,
                    {"raw": set(), "canonical": set(), "raw_rows": set()})
                for ax in ("raw", "canonical", "raw_rows"):
                    mine[ax].update(c.get(ax, ()))
        return n

    def load(self, path: Optional[str] = None) -> int:
        path = path or default_manifest_path()
        if not path or not os.path.exists(path):
            return 0
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return 0
        return self.merge_manifest(doc)

    def persist(self, path: Optional[str] = None) -> Optional[str]:
        path = path or default_manifest_path()
        if not path:
            return None
        doc = self.to_manifest()
        if not doc["entries"] and not doc["canonical"]:
            return None
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + ".tmp.%d" % os.getpid()
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            return None
        with self._lock:
            self.dirty = False
        return path


_REGISTRY = ShapeRegistry()


def registry() -> ShapeRegistry:
    return _REGISTRY


def _observer(event: str, key, ns: int) -> None:
    try:
        _REGISTRY.observe(event, key, ns)
    except Exception:
        pass  # telemetry must never break the compile hot path


jit_cache.set_observer(_observer)


# --------------------------------------------------------------------------
# sort-shape recording + replay
# --------------------------------------------------------------------------

def record_sort_shape(key, batch, specs) -> None:
    """Record a host-reconstructible payload for a sort-kernel key.

    `sorted_batch_jit` keys are deliberately plan-independent
    (specs + shape_key), so a manifest entry is enough to rebuild an
    equivalent batch from scratch in a fresh process and replay the
    compile into the persistent XLA cache.
    """
    try:
        cols = []
        for f, c in zip(batch.schema, batch.columns):
            k = f.dtype.kind.name
            if k not in _REPLAYABLE_KINDS or f.dtype.wide_decimal:
                return  # host-fallback / nested shapes are not replayable
            col = {"name": f.name, "kind": k, "nullable": bool(f.nullable),
                   "valid": c.validity is not None}
            if f.dtype.kind.name == "DECIMAL":
                col["precision"] = f.dtype.precision
                col["scale"] = f.dtype.scale
            if k in ("STRING", "BINARY"):
                col["width"] = int(c.data.width)
            cols.append(col)
        payload = {
            "type": "sort", "capacity": int(batch.capacity),
            "specs": [[int(s.col), bool(s.asc), bool(s.nulls_first)]
                      for s in specs],
            "cols": cols,
        }
        _REGISTRY.attach_replay(key, payload, "ops/sort.sorted_batch_jit")
    except Exception:
        pass


def _rebuild_sort_batch(payload: Dict[str, Any]):
    import numpy as np

    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import ColumnBatch

    cap = int(payload["capacity"])
    fields, data, validity = [], {}, {}
    for i, col in enumerate(payload["cols"]):
        kind = T.TypeKind[col["kind"]]
        if kind == T.TypeKind.DECIMAL:
            dt = T.decimal(col.get("precision", 18), col.get("scale", 0))
        else:
            dt = T.DataType(kind)
        name = col.get("name") or f"c{i}"
        fields.append(T.Field(name, dt, col.get("nullable", True)))
        if kind in (T.TypeKind.STRING, T.TypeKind.BINARY):
            w = int(col.get("width", conf.min_string_width))
            # one max-width value pins the width bucket; vary the rest so
            # the sort is not degenerate
            data[name] = ["x" * w] + ["k%04d" % (j % 97)
                                     for j in range(1, cap)]
        elif kind == T.TypeKind.BOOLEAN:
            data[name] = (np.arange(cap) % 2).astype(bool)
        else:
            data[name] = (np.arange(cap) % 251).astype(dt.np_dtype())
        if col.get("valid"):
            validity[name] = (np.arange(cap) % 5 != 0)
    schema = T.Schema(fields)
    return ColumnBatch.from_numpy(data, schema, capacity=cap,
                                  validity=validity or None)


def replay_entry(entry: Dict[str, Any]) -> bool:
    """Re-trigger the compile recorded in a manifest entry (sort kernels
    only for now).  Returns True when a replay ran."""
    payload = entry.get("replay")
    if not payload or payload.get("type") != "sort":
        return False
    from blaze_tpu.ops.sort import SortSpec, sorted_batch_jit

    batch = _rebuild_sort_batch(payload)
    specs = [SortSpec(c, a, nf) for c, a, nf in payload["specs"]]
    out = sorted_batch_jit(batch, specs)
    # touch the result so the dispatch (and with it the XLA compile into
    # the persistent cache) actually completes before the next item
    out.column(0)
    return True


# --------------------------------------------------------------------------
# pre-warm driver
# --------------------------------------------------------------------------

class _Budget:
    def __init__(self, seconds: Optional[float]) -> None:
        self.t0 = time.monotonic()
        self.seconds = seconds

    def spent(self) -> float:
        return time.monotonic() - self.t0

    def exhausted(self) -> bool:
        return self.seconds is not None and self.spent() >= self.seconds


def warm(manifest_path: Optional[str] = None,
         queries: Optional[List[str]] = None,
         rows: int = 20_000,
         modes: Tuple[str, ...] = ("bhj", "smj"),
         budget_seconds: Optional[float] = None,
         skip_catalogue: bool = False,
         num_partitions: int = 4,
         progress=print) -> Dict[str, Any]:
    """Replay manifest shapes + the TPC-DS catalogue into the caches.

    Phase 1 rebuilds every replayable manifest entry (sort kernels) and
    re-runs its compile; phase 2 executes the catalogue's enumerated
    (query, mode) cells end-to-end, populating the persistent XLA cache
    with every stage/join/agg program those plans touch.  Honors
    `budget_seconds` between items.
    """
    import tempfile

    budget = _Budget(budget_seconds)
    stats = {"replayed_shapes": 0, "skipped_shapes": 0, "cells_run": 0,
             "cells_failed": 0, "stopped_early": False, "seconds": 0.0}

    manifest_path = manifest_path or default_manifest_path()
    merged = _REGISTRY.load(manifest_path)
    progress(f"[warm] manifest: {manifest_path or '(disabled)'} "
             f"({merged} entries)")

    for kid, entry in sorted(_REGISTRY.entries.items()):
        if budget.exhausted():
            stats["stopped_early"] = True
            break
        try:
            if replay_entry(entry):
                stats["replayed_shapes"] += 1
                progress(f"[warm] shape {entry['kind']} "
                         f"cap={entry['replay']['capacity']} "
                         f"({budget.spent():.1f}s)")
            else:
                stats["skipped_shapes"] += 1
        except Exception as e:  # a stale shape must not kill the warm run
            stats["skipped_shapes"] += 1
            progress(f"[warm] shape replay failed ({e!r})")

    if not skip_catalogue and not stats["stopped_early"]:
        from blaze_tpu.spark import tpcds
        from blaze_tpu.spark.local_runner import run_plan

        with tempfile.TemporaryDirectory(prefix="blaze_warm_") as td:
            paths, frames = tpcds.generate_tables(td, rows=rows)
            for name, mode in tpcds.warm_cells(queries, modes):
                if budget.exhausted():
                    stats["stopped_early"] = True
                    break
                t0 = time.monotonic()
                try:
                    plan, _oracle = tpcds.QUERIES[name](paths, frames, mode)
                    run_plan(plan, num_partitions=num_partitions)
                    stats["cells_run"] += 1
                    progress(f"[warm] {name}/{mode} rows={rows} "
                             f"{time.monotonic() - t0:.1f}s "
                             f"(total {budget.spent():.1f}s)")
                except Exception as e:
                    stats["cells_failed"] += 1
                    progress(f"[warm] {name}/{mode} FAILED: {e!r}")

    saved = _REGISTRY.persist(manifest_path)
    stats["seconds"] = round(budget.spent(), 2)
    stats["manifest"] = saved or manifest_path
    stats["telemetry"] = TELEMETRY.snapshot()
    stats["shape_reduction"] = _REGISTRY.shape_reduction()
    progress(f"[warm] done: {stats['replayed_shapes']} shapes, "
             f"{stats['cells_run']} cells in {stats['seconds']}s"
             + (" (budget hit)" if stats["stopped_early"] else ""))
    return stats


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="blaze_tpu.runtime.compile_service",
        description="Pre-warm the persistent compile caches from the "
                    "shape manifest and the TPC-DS catalogue.")
    p.add_argument("--warm", action="store_true",
                   help="run the pre-warm driver (the only verb for now)")
    p.add_argument("--manifest", default=None,
                   help="manifest path (default: next to the XLA cache)")
    p.add_argument("--queries", default=None,
                   help="comma-separated catalogue queries (default: all)")
    p.add_argument("--rows", type=int, default=20_000,
                   help="catalogue scale in rows per table (default 20000)")
    p.add_argument("--modes", default="bhj,smj",
                   help="join modes to enumerate (default bhj,smj)")
    p.add_argument("--budget-seconds", type=float, default=None,
                   help="stop starting new items past this many seconds")
    p.add_argument("--skip-catalogue", action="store_true",
                   help="replay manifest shapes only")
    p.add_argument("--num-partitions", type=int, default=4)
    p.add_argument("--json-out", default=None,
                   help="write the warm stats JSON here")
    args = p.parse_args(argv)

    if not args.warm:
        p.error("nothing to do: pass --warm")
    queries = args.queries.split(",") if args.queries else None
    stats = warm(manifest_path=args.manifest, queries=queries,
                 rows=args.rows,
                 modes=tuple(m for m in args.modes.split(",") if m),
                 budget_seconds=args.budget_seconds,
                 skip_catalogue=args.skip_catalogue,
                 num_partitions=args.num_partitions)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(stats, f, indent=1, sort_keys=True, default=str)
    return 0


if __name__ == "__main__":  # pragma: no cover - thin shim
    import sys

    # re-import under the canonical module name so the registry/observer
    # the engine uses is the same object this CLI reads
    from blaze_tpu.runtime import compile_service as _cs

    sys.exit(_cs.main())
