"""The benchmark's `seg_scan_share` reader (benchmarks/metrics/
seg_scan_share.py) on hand-built runs, and its entry in BENCHMARK.json. The
program's side of the counters is held by tests/test_agg.py."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def read():
    path = os.path.join(REPO, "benchmarks", "metrics", "seg_scan_share.py")
    spec = importlib.util.spec_from_file_location("m_seg_scan_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("telemetry, want", [
    ({"seg_scan_reductions": 924, "seg_scatter_reductions": 0}, 100.0),
    ({"seg_scan_reductions": 30, "seg_scatter_reductions": 10}, 75.0),
    ({"seg_scan_reductions": 0, "seg_scatter_reductions": 8}, 0.0),
    # a window that dispatched no agg_collapse, and a program without the
    # counters (the parent of PR 31): nothing to report, and no raise
    ({"seg_scan_reductions": 0, "seg_scatter_reductions": 0}, None),
    ({"cache_hits": 308}, None),
])
def test_read(read, telemetry, want):
    assert read({"window": [], "telemetry": telemetry}) == want


def test_manifest_entry():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "seg_scan_share"]
    cells = entry.pop("workloads")  # the cells whose queries collapse
    assert entry == {
        "name": "seg_scan_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "streaming ops",
        "moves": "query_s.p50"}
    assert cells[:4] == ["sf10_q03_bhj", "sf1_q06core_agg", "sf1_q03_nobhj",
                         "sf1_q03_nobhj_x4"]
