"""`tpcds_sf10_nobhj`, its cell `sf10_q03_nobhj` and the three metrics that
came with them (PR 34): the configuration against its broadcast twin, the
entries in the manifest, each reader over a recorded state (a value where the
spans and counters are there, None where they are not, no entry for a cell
outside its list), and the cell rehearsed on the CPU with the metrics on its
traced line. A rehearsal's numbers are the CPU's: a count is checked, never a
time. No child process.

The file's name sorts it last, for test_y_decimal_cell.py's reason."""

import json
import os

import pytest

from harness.registry import Registry

CELL, CONFIG, TWIN = "sf10_q03_nobhj", "tpcds_sf10_nobhj", "tpcds_sf10_bhj"
LISTS = {
    "exchange_pinned_GB": ["sf10_q03_nobhj", "sf1_q03_nobhj"],
    "exchange_slice_rows": ["sf10_q03_nobhj", "sf1_q03_nobhj"],
    "dispatches_per_query": ["sf10_q03_nobhj", "sf1_q03_nobhj",
                             "sf10_q03_bhj"],
}


@pytest.fixture(scope="module")
def reg():
    return Registry()


def _span(kind, **attrs):
    return {"type": "span", "kind": kind, "ts": 0, "dur": 1000,
            "attrs": attrs}


def _query(spans):
    return {"query": "q03", "seconds": 1.0, "spans": spans}


# a query as the program records it since PR 34: two map stages with what
# they pinned, a result stage, five calls of cached programs
RECORDED = [
    _span("query"),
    _span("stage", stage_kind="shuffle_map", transport="mesh", bytes=900,
          pinned_bytes=1_200_000_000),
    _span("stage", stage_kind="shuffle_map", transport="mesh", bytes=90,
          pinned_bytes=4_000_000),
    _span("stage", stage_kind="result"),
] + [_span("dispatch", what="slice")] * 5
# the parent's: the same spans without the attribute, and no counter
PARENTS = [_span("query"),
           _span("stage", stage_kind="shuffle_map", transport="mesh",
                 bytes=900),
           _span("dispatch", what="slice")]


def _state(window, telemetry):
    return {"window": window, "profiled": [], "telemetry": telemetry}


def test_the_configuration_is_its_twin_with_the_join_arm_turned(reg):
    config, twin = reg.data("configs", CONFIG), reg.data("configs", TWIN)
    assert config["name"] == CONFIG
    assert config["settings"]["join"] == "sort_merge"
    assert twin["settings"]["join"] == "broadcast"
    assert len(config["source"]) <= 200 and "-1" in config["source"]
    for cfg in (config, twin):
        for key in ("name", "source", "deployment"):
            del cfg[key]
        del cfg["settings"]["join"]
    assert config == twin       # tables, guarantees, reduced, assumed


def test_the_manifest_names_the_configuration_and_the_cell(reg):
    (entry,) = [c for c in reg.manifest["configs"] if c["name"] == CONFIG]
    twin = next(c for c in reg.manifest["configs"] if c["name"] == TWIN)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert os.path.exists(os.path.join(os.path.dirname(reg.dir),
                                       entry["file"]))
    config = reg.data("configs", CONFIG)
    assert entry["reduced"] == twin["reduced"] == list(config["reduced"])
    assert entry["source"] == config["source"]
    why = reg.cell(CELL)["why"]
    assert reg.cell(CELL) == {"name": CELL, "config": CONFIG,
                              "traffic": "q03_loop1", "chips": 1, "why": why}
    assert 0 < len(why) <= 200
    # the twin on the arm runs the same traffic: same query, same literals
    assert reg.cell("sf10_q03_bhj")["traffic"] == reg.cell(CELL)["traffic"]
    # what the cell cannot report: lists that are accepted entries
    reported = [m["name"] for m in reg.metrics(CELL, "per_layer")]
    for name in ("exchange_s", "seg_scan_share", "mesh_exchange_s"):
        assert name not in reported
    for name in ("hbm_roofline_share", "device_idle_share", "peak_hbm_GB",
                 "shuffle_map_stage_s", "compiles_in_window"):
        assert name in reported
    assert [m["name"] for m in reg.metrics(CELL, "end_to_end")] == [
        "setup_s", "query_s.p50"]


@pytest.mark.parametrize("name", sorted(LISTS))
def test_each_metric_lists_the_cells_that_have_something_to_read(reg, name):
    (entry,) = [m for m in reg.manifest["per_layer"] if m["name"] == name]
    assert entry["workloads"] == LISTS[name]
    assert entry["moves"] == "query_s.p50"
    assert entry["layer"] in {m["layer"] for m in reg.manifest["per_layer"]
                              if m["name"] not in LISTS}
    for cell in reg.manifest["workloads"]:
        listed = name in [m["name"]
                          for m in reg.metrics(cell["name"], "per_layer")]
        assert listed == (cell["name"] in LISTS[name])


def test_filter_mask_share_is_found_by_name(reg):
    """test_z_filter_mask_share.py's case on the entry, which reads it as
    `per_layer[-1]` and is an expected failure since this PR appended three
    (benchmarks/conftest.py): the same assertions, the entry found by name."""
    from test_z_filter_mask_share import CELLS

    (entry,) = [m for m in reg.manifest["per_layer"]
                if m["name"] == "filter_mask_share"]
    assert entry == {
        "name": "filter_mask_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "whole-stage and agg",
        "moves": "query_s.p50", "workloads": CELLS}
    for cell in CELLS:
        assert "filter_mask_share" in [
            m["name"] for m in reg.metrics(cell, "per_layer")]
        assert reg.cell(cell)["chips"] == 1
    for cell in ("sf1_q03_nobhj", "sf1_q03_nobhj_x4", CELL):
        assert "filter_mask_share" not in [
            m["name"] for m in reg.metrics(cell, "per_layer")]
    assert entry["layer"] in {m["layer"] for m in reg.manifest["per_layer"]
                              if m is not entry}
    # what this PR appended comes after every entry the benchmark had
    names = [m["name"] for m in reg.manifest["per_layer"]]
    assert names[names.index("filter_mask_share") + 1:] == [
        "exchange_pinned_GB", "exchange_slice_rows", "dispatches_per_query"]


@pytest.mark.parametrize("name, state, want", [
    ("exchange_pinned_GB", _state([_query(RECORDED)] * 3, {}), 1.2),
    ("exchange_pinned_GB", _state([_query(PARENTS)] * 3, {}), None),
    ("exchange_pinned_GB", _state([_query(None)], {}), None),
    ("exchange_slice_rows", _state([], {"exchange_slices_kept": 304,
                                        "exchange_rows_kept": 31_008_000}),
     102_000.0),
    ("exchange_slice_rows", _state([], {}), None),
    ("exchange_slice_rows", _state([], {"exchange_slices_kept": 0,
                                        "exchange_rows_kept": 0}), None),
    ("dispatches_per_query", _state(
        [_query(RECORDED), _query(PARENTS), _query(RECORDED)], {}), 5),
    ("dispatches_per_query", _state([_query([_span("query")])], {}), None),
    ("dispatches_per_query", _state([_query(None)] * 2, {}), None),
])
def test_a_reader_over_a_recorded_state(reg, name, state, want):
    assert reg.module("metrics", name).read(state) == want


def test_the_greatest_stage_of_a_query_and_the_median_of_the_queries(reg):
    def pinned(*stages):
        return _query([_span("stage", stage_kind="shuffle_map",
                             pinned_bytes=b) for b in stages])

    state = _state([pinned(1e9, 3e9), pinned(2e9)], {})
    state["profiled"] = [pinned(5e8, 1e9)]
    assert reg.module("metrics", "exchange_pinned_GB").read(state) == 2.0


@pytest.fixture(scope="module")
def rehearsal():
    from test_y_decimal_cell import _rehearse

    return _rehearse(CELL, 2147483659, 200_000)


def test_the_cell_rehearses_correct(rehearsal):
    line = json.loads(rehearsal[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4        # a window's query and three profiled
    assert line["device"]["platform"] == "cpu"
    assert not [ln for ln in rehearsal if "FAILED" in ln]
    counters = json.loads(next(
        ln for ln in rehearsal if "last query's counters" in ln
    ).split("counters: ", 1)[1])
    assert counters["mesh_stages"] == 5 and counters["file_stages"] == 0
    assert counters["broadcast_stages"] == 0 and counters["spill_count"] == 0


def test_the_three_metrics_are_on_its_traced_line(rehearsal):
    metrics = json.loads(rehearsal[-1])["metrics"]
    assert metrics["compiles_in_window"]["value"] == 0
    # 200,000 fact rows in one batch: four slices of ~50,000 rows, two
    # 8,192-slot dimension batches, ~15,000 joined rows: the mean slice is
    # thousands of rows, and the fact stage pins no less than its live
    # bytes: three 8-byte columns, two of them with a validity byte
    assert metrics["exchange_slice_rows"]["unit"] == "rows"
    assert 1_000 < metrics["exchange_slice_rows"]["value"] < 200_000
    assert metrics["exchange_pinned_GB"]["unit"] == "GB"
    assert 200_000 * 26 / 1e9 <= metrics["exchange_pinned_GB"]["value"] < 0.1
    assert metrics["dispatches_per_query"]["unit"] == "count"
    assert metrics["dispatches_per_query"]["value"] >= 50
    for name in ("exchange_s", "seg_scan_share", "device_idle_share",
                 "hbm_roofline_share", "peak_hbm_GB"):
        assert name not in metrics      # a closed list; no device on a CPU
