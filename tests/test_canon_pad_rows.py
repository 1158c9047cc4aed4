"""The benchmark's `canon_pad_rows` reader (benchmarks/metrics/
canon_pad_rows.py) on hand-built runs and on the program's own counter, and
its entry in BENCHMARK.json."""

import importlib.util
import json
import os

import numpy as np
import pytest

from blaze_tpu.columnar import ColumnBatch, Field, INT64, Schema
from blaze_tpu.config import conf
from blaze_tpu.runtime import compile_service as cs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def read():
    path = os.path.join(REPO, "benchmarks", "metrics", "canon_pad_rows.py")
    spec = importlib.util.spec_from_file_location("m_canon_pad_rows", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


QUERY = {"seconds": 1.0, "spans": None, "query": "q"}


@pytest.mark.parametrize("telemetry,queries,want", [
    # the parent in sf1_q06core_agg: one 2^21 -> 2^22 pad a query
    ({"canonicalization_waste_rows": 7 * (1 << 21), "cache_hits": 161}, 7,
     float(1 << 21)),
    # a window that never added to the counter: the snapshot lacks the key
    ({"cache_hits": 161}, 7, 0.0),
    ({}, 3, 0.0),
    ({"canonicalization_waste_rows": 0}, 14, 0.0),
    # a pad every other query
    ({"canonicalization_waste_rows": 3 * 4096}, 6, 2048.0),
    # no query finished: nothing to divide by
    ({"canonicalization_waste_rows": 4096}, 0, None),
])
def test_reader_divides_the_windows_waste_by_its_queries(read, telemetry,
                                                         queries, want):
    run = {"window": [QUERY] * queries, "profiled": [QUERY] * 3,
           "telemetry": telemetry}
    assert read(run) == want


def test_reader_follows_the_programs_counter(read, monkeypatch):
    """Deltas of compile_service.TELEMETRY as benchmarks/harness/loop.py
    takes them: a batch repadded to its rung shows, one at the full
    macro-batch capacity does not."""
    schema = Schema([Field("x", INT64)])
    limit = conf.canonical_pow2_limit
    batch = ColumnBatch.from_numpy(
        {"x": np.arange(limit * 2, dtype=np.int64)}, schema)

    def window(fn):
        tel0 = cs.TELEMETRY.snapshot()
        fn()
        tel1 = cs.TELEMETRY.snapshot()
        return {"window": [QUERY], "profiled": [], "telemetry": {
            k: v - tel0.get(k, 0) for k, v in tel1.items()
            if isinstance(v, (int, float))}}

    padded = window(lambda: cs.canonical_batch(batch, "sort_kernel"))
    assert read(padded) == limit * 2  # 2^15 -> 2^16
    monkeypatch.setattr(conf, "max_batch_rows", limit * 2)
    unpadded = window(lambda: cs.canonical_batch(batch, "sort_kernel"))
    assert read(unpadded) == 0


def test_manifest_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "canon_pad_rows"]
    assert entry == {
        "name": "canon_pad_rows", "unit": "rows", "better": "lower",
        "source": "program_counter", "layer": "compile",
        "moves": "query_s.p50"}  # no `workloads` key: every cell reports it
