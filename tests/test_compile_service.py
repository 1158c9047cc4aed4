"""Compile service (runtime/compile_service.py): shape canonicalization,
manifest round-trip, pre-warm driver, and compile telemetry export."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from blaze_tpu.columnar import ColumnBatch, Schema, Field, FLOAT32, INT64
from blaze_tpu.config import conf
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.ir import BinOp, col
from blaze_tpu.ops.basic import FilterExec, MemorySourceExec
from blaze_tpu.ops.sort import SortSpec, sorted_batch_jit
from blaze_tpu.runtime import compile_service as cs
from blaze_tpu.runtime import jit_cache
from blaze_tpu.runtime.executor import collect, metric_tree

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCHEMA = Schema([Field("x", INT64)])


def _subprocess_env(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _sort_kernel_keys():
    return {k for k, e in cs.registry().entries.items()
            if e["kind"] == "sort_kernel"}


# ---------------------------------------------------------------------------
# canonicalization policy
# ---------------------------------------------------------------------------

def test_canonical_capacity_policy():
    limit = conf.canonical_pow2_limit
    # at or below the limit: identical to the plain pow2 bucket
    assert cs.canonical_capacity(100) == 1024  # min_capacity floor
    assert cs.canonical_capacity(limit) == limit
    assert cs.canonical_capacity(limit - 1) == limit
    # above: power-of-four rungs anchored at the limit
    assert cs.canonical_capacity(limit + 1) == limit * 4
    assert cs.canonical_capacity(limit * 2) == limit * 4
    assert cs.canonical_capacity(limit * 4) == limit * 4
    assert cs.canonical_capacity(limit * 8) == limit * 16
    # count rungs: exact up to 2, pow2 above
    assert [cs.canonical_batch_count(n) for n in (1, 2, 3, 4, 5, 9)] == \
        [1, 2, 4, 4, 8, 16]
    old = conf.enable_compile_canonicalization
    conf.enable_compile_canonicalization = False
    try:
        assert cs.canonical_capacity(limit * 2) == limit * 2
        assert cs.canonical_batch_count(5) == 5
    finally:
        conf.enable_compile_canonicalization = old


def test_same_rung_shares_one_sort_program(rng):
    """Two raw sizes in one canonical rung compile ONE sort kernel (the
    second is a cache hit) and sort correctly despite the padding."""
    limit = conf.canonical_pow2_limit
    n1, n2 = limit + limit // 4, limit * 2  # buckets 2x/4x -> same rung
    before_keys = _sort_kernel_keys()
    waste0 = cs.TELEMETRY["canonicalization_waste_rows"]
    outs = []
    for n in (n1, n2):
        data = rng.integers(0, 1 << 40, n).astype(np.int64)
        b = ColumnBatch.from_numpy({"x": data}, SCHEMA)
        sb = sorted_batch_jit(b, [SortSpec(0)])
        assert sb.capacity == cs.canonical_capacity(n)
        got = np.asarray(sb.columns[0].data)[:int(sb.num_rows)]
        np.testing.assert_array_equal(got, np.sort(data))
        outs.append(sb)
    new_keys = _sort_kernel_keys() - before_keys
    assert len(new_keys) == 1, new_keys  # one program for both sizes
    (kid,) = new_keys
    assert cs.registry().entries[kid]["hits"] >= 1
    # padding the smaller size was charged as waste
    assert cs.TELEMETRY["canonicalization_waste_rows"] > waste0


def test_sort_correct_at_bucket_boundaries(rng):
    """±1 row around the canonicalization limit: values identical to
    numpy regardless of which rung the batch lands on."""
    limit = conf.canonical_pow2_limit
    for n in (limit - 1, limit, limit + 1):
        data = rng.standard_normal(n)
        schema = Schema([Field("v", FLOAT32)])
        b = ColumnBatch.from_numpy({"v": data.astype(np.float32)}, schema)
        sb = sorted_batch_jit(b, [SortSpec(0)])
        got = np.asarray(sb.columns[0].data)[:int(sb.num_rows)]
        np.testing.assert_array_equal(got, np.sort(data.astype(np.float32)))


# The power-of-four rungs stop at the full macro-batch capacity,
# bucket_capacity(conf.max_batch_rows): (max_batch_rows, rows, rung).
# None = the default (2^21).  1 << 17 sits an odd number of doublings above
# the limit (as 2^21 does), 1 << 18 an even number, 100_000 is no power of
# two (its bucket, 2^17, is the bound).
_MACRO_CASES = [
    (None, 1 << 19, 1 << 20), (None, 1 << 20, 1 << 20),
    (None, 1 << 21, 1 << 21), (None, (1 << 21) + 1, 1 << 22),
    (None, 1 << 22, 1 << 22), (None, 1 << 23, 1 << 23),
    (1 << 17, 1 << 15, 1 << 16), (1 << 17, 1 << 16, 1 << 16),
    (1 << 17, 1 << 17, 1 << 17), (1 << 17, (1 << 17) + 1, 1 << 18),
    (1 << 17, 1 << 18, 1 << 18), (1 << 17, 1 << 19, 1 << 19),
    (1 << 18, 1 << 17, 1 << 18), (1 << 18, 1 << 18, 1 << 18),
    (1 << 18, 1 << 19, 1 << 19),
    (100_000, 1 << 15, 1 << 16), (100_000, 100_000, 1 << 17),
    (100_000, 1 << 19, 1 << 19),
]


@pytest.mark.parametrize("max_rows,n,want", _MACRO_CASES)
def test_canonical_capacity_round_the_macro_batch(monkeypatch, max_rows, n,
                                                  want):
    assert conf.max_batch_rows == 1 << 21
    assert conf.canonical_pow2_limit == 1 << 14
    if max_rows is not None:
        monkeypatch.setattr(conf, "max_batch_rows", max_rows)
    assert cs.canonical_capacity(n) == want


def _canon_pad_calls():
    return sum(e["hits"] + e["misses"]
               for e in cs.registry().entries.values()
               if e["kind"] == "canon_pad")


def test_full_macro_batch_reaches_the_collapse_unpadded(rng, monkeypatch):
    """A partial agg over one batch at the full macro-batch capacity: the
    collapse runs at the batch's own capacity, nothing is repadded, and
    the groups equal pandas'."""
    import pandas as pd

    from blaze_tpu.columnar import FLOAT64
    from blaze_tpu.ops.agg import AggCall, AggExec, AggMode

    n = conf.canonical_pow2_limit * 2  # 2^15: on no power-of-four rung
    monkeypatch.setattr(conf, "max_batch_rows", n)
    monkeypatch.setattr(conf, "enable_stage_compiler", False)
    schema = Schema([Field("k", INT64), Field("v", FLOAT64)])
    k = rng.integers(0, 900, n).astype(np.int64)
    v = rng.random(n) * 10 - 5
    batch = ColumnBatch.from_numpy({"k": k, "v": v}, schema)
    assert batch.capacity == n

    capacities = []
    collapse = AggExec._collapse

    def spy(self, batches, raw_input):
        out = collapse(self, batches, raw_input)
        capacities.append((sum(b.capacity for b in batches), out.capacity))
        return out

    monkeypatch.setattr(AggExec, "_collapse", spy)
    calls = [AggCall("sum", (col("v"),), FLOAT64, "s"),
             AggCall("count", (col("v"),), INT64, "c"),
             AggCall("avg", (col("v"),), FLOAT64, "a")]
    agg = AggExec(MemorySourceExec([batch], schema), [col("k")], ["k"],
                  calls, AggMode.PARTIAL)
    waste0 = cs.TELEMETRY["canonicalization_waste_rows"]
    pads0 = _canon_pad_calls()
    out = collect(agg)
    assert cs.TELEMETRY["canonicalization_waste_rows"] == waste0
    assert _canon_pad_calls() == pads0
    assert capacities == [(n, n)]  # one collapse, state at the input's size

    rows = int(out.num_rows)
    got = pd.DataFrame({
        "k": np.asarray(out.columns[0].data)[:rows],
        "s": np.asarray(out.columns[1].data)[:rows],
        "c": np.asarray(out.columns[3].data)[:rows],
    }).sort_values("k").reset_index(drop=True)
    want = (pd.DataFrame({"k": k, "v": v}).groupby("k")["v"]
            .agg(["sum", "count"]).reset_index())
    np.testing.assert_array_equal(got["k"], want["k"])
    np.testing.assert_array_equal(got["c"], want["count"])
    np.testing.assert_allclose(got["s"], want["sum"], rtol=1e-9)


def _yz(y, schema):
    return ColumnBatch.from_numpy(
        {"y": y, "z": np.zeros(len(y), np.float32)}, schema)


def test_below_the_macro_batch_rungs_still_share_a_sort_program(
        rng, monkeypatch):
    """With the bound lowered to 2^17, 2^15- and 2^16-bucket inputs still
    meet on the 2^16 rung (one sort program, waste charged), and a batch
    at 2^17 sorts at 2^17, not 2^18."""
    monkeypatch.setattr(conf, "max_batch_rows", 1 << 17)
    # a layout no other case of this file sorts: its programs are new here
    schema = Schema([Field("y", INT64), Field("z", FLOAT32)])
    before = _sort_kernel_keys()
    waste0 = cs.TELEMETRY["canonicalization_waste_rows"]
    for n in ((1 << 14) + 5, 1 << 16):
        data = rng.integers(0, 1 << 40, n).astype(np.int64)
        sb = sorted_batch_jit(_yz(data, schema), [SortSpec(0)])
        assert sb.capacity == 1 << 16
        np.testing.assert_array_equal(
            np.asarray(sb.columns[0].data)[:n], np.sort(data))
    assert len(_sort_kernel_keys() - before) == 1
    assert cs.TELEMETRY["canonicalization_waste_rows"] == waste0 + (1 << 15)

    data = rng.integers(0, 1 << 40, 1 << 17).astype(np.int64)
    sb = sorted_batch_jit(_yz(data, schema), [SortSpec(0)])
    assert sb.capacity == 1 << 17
    np.testing.assert_array_equal(np.asarray(sb.columns[0].data),
                                  np.sort(data))
    assert len(_sort_kernel_keys() - before) == 2
    assert cs.TELEMETRY["canonicalization_waste_rows"] == waste0 + (1 << 15)


def test_stage_batch_count_padding_matches_streaming(rng):
    """A 3-batch chain stage (padded to the 4 rung) returns exactly the
    streaming engine's rows."""
    batches = [ColumnBatch.from_numpy(
        {"x": rng.integers(0, 100, 64).astype(np.int64)}, SCHEMA)
        for _ in range(3)]

    def run():
        flt = FilterExec(MemorySourceExec(list(batches), SCHEMA),
                         [ir.Binary(BinOp.GE, col("x"),
                                    ir.Literal(INT64, 50))])
        out = collect(flt)
        return np.asarray(out.columns[0].data)[:int(out.num_rows)]

    staged = run()
    old = conf.enable_stage_compiler
    conf.enable_stage_compiler = False
    try:
        streamed = run()
    finally:
        conf.enable_stage_compiler = old
    np.testing.assert_array_equal(np.sort(staged), np.sort(streamed))


# ---------------------------------------------------------------------------
# telemetry export
# ---------------------------------------------------------------------------

def test_compile_metrics_in_metric_tree():
    b = ColumnBatch.from_numpy({"x": np.arange(32, dtype=np.int64)}, SCHEMA)
    flt = FilterExec(MemorySourceExec([b], SCHEMA),
                     [ir.Binary(BinOp.GE, col("x"), ir.Literal(INT64, 0))])
    collect(flt)
    node = metric_tree(flt)
    seen = {}

    def install(n):
        n.handler = lambda k, v: seen.__setitem__(k, v)
        for c in n.children:
            install(c)

    install(node)
    node.push()
    for key in ("compile_count", "compile_ns", "cache_hits",
                "cache_misses", "canonicalization_waste_rows",
                "whole_stage_coverage_pct"):
        assert key in seen, key
    assert seen["cache_hits"] + seen["cache_misses"] > 0


def test_task_scope_attributes_deltas():
    from blaze_tpu.runtime.metrics import MetricsSet

    ms = MetricsSet()
    with cs.task_scope(ms):
        b = ColumnBatch.from_numpy(
            {"x": np.arange(16, dtype=np.int64)}, SCHEMA)
        flt = FilterExec(MemorySourceExec([b], SCHEMA),
                         [ir.Binary(BinOp.GE, col("x"),
                                    ir.Literal(INT64, 8))])
        collect(flt)
    assert ms["cache_hits"] + ms["cache_misses"] > 0


# ---------------------------------------------------------------------------
# warm-then-cold hit rate (in-process cold simulation)
# ---------------------------------------------------------------------------

def test_warm_then_cold_hit_rate(rng):
    """Replaying recorded sort shapes into a cleared jit cache makes the
    subsequent workload call a pure cache hit."""
    n = conf.canonical_pow2_limit * 2 + 17
    data = rng.integers(0, 1 << 20, n).astype(np.int64)
    b = ColumnBatch.from_numpy({"x": data}, SCHEMA)
    sorted_batch_jit(b, [SortSpec(0)])  # record the shape

    replayable = [e for e in cs.registry().entries.values()
                  if e["replay"] and e["kind"] == "sort_kernel"]
    assert replayable, "sort shape must have a replay payload"

    jit_cache.clear()  # "cold process": compiled programs gone
    replayed = sum(cs.replay_entry(e) for e in replayable)
    assert replayed >= 1

    st0 = jit_cache.stats()
    sb = sorted_batch_jit(b, [SortSpec(0)])  # the workload call
    st1 = jit_cache.stats()
    assert st1["hits"] == st0["hits"] + 1
    assert st1["misses"] == st0["misses"]
    got = np.asarray(sb.columns[0].data)[:int(sb.num_rows)]
    np.testing.assert_array_equal(got, np.sort(data))


# ---------------------------------------------------------------------------
# manifest round-trip + warm driver (across processes)
# ---------------------------------------------------------------------------

CHILD_RECORD = """
import numpy as np
from blaze_tpu.columnar import ColumnBatch, Schema, Field, FLOAT32
from blaze_tpu.ops.sort import SortSpec, sorted_batch_jit
from blaze_tpu.runtime import compile_service as cs
b = ColumnBatch.from_numpy(
    dict(y=np.random.default_rng(7).standard_normal(1500).astype(np.float32)),
    Schema([Field("y", FLOAT32)]))
sorted_batch_jit(b, [SortSpec(0, False, False)])
path = cs.registry().persist("@MANIFEST@")
assert path, "manifest must persist"
"""


def test_manifest_roundtrip_across_processes(tmp_path):
    """A manifest persisted by one process loads (fingerprint match) and
    replays in another."""
    manifest = str(tmp_path / "compile_manifest.json")
    r = subprocess.run(
        [sys.executable, "-c", CHILD_RECORD.replace("@MANIFEST@", manifest)],
        env=_subprocess_env(tmp_path), capture_output=True, text=True,
        timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]

    with open(manifest) as f:
        doc = json.load(f)
    assert doc["fingerprint"] == cs.fingerprint()

    reg = cs.ShapeRegistry()
    assert reg.merge_manifest(doc) > 0
    replays = [e for e in reg.entries.values() if e["replay"]]
    assert replays, "sort shape must round-trip with its replay payload"
    assert cs.replay_entry(replays[0])


def test_warm_driver_mini_catalogue(tmp_path):
    """`--warm` over a 3-query mini-catalogue: all cells run, the
    manifest lands next to the cache, stats JSON carries telemetry."""
    manifest = str(tmp_path / "m.json")
    stats_out = str(tmp_path / "warm_stats.json")
    r = subprocess.run(
        [sys.executable, "-m", "blaze_tpu.runtime.compile_service",
         "--warm", "--queries", "q01,q03,q06", "--rows", "400",
         "--modes", "bhj", "--manifest", manifest,
         "--json-out", stats_out, "--budget-seconds", "600",
         "--num-partitions", "2"],
        env=_subprocess_env(tmp_path), capture_output=True, text=True,
        timeout=580)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    with open(stats_out) as f:
        stats = json.load(f)
    assert stats["cells_run"] == 3 and stats["cells_failed"] == 0, stats
    assert stats["telemetry"]["compile_count"] > 0
    assert os.path.exists(manifest)
    with open(manifest) as f:
        doc = json.load(f)
    assert doc["entries"], "warm run must record compiled shapes"
