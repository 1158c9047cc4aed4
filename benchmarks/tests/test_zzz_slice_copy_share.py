"""`slice_copy_share` (PR 35): its reader over the counters a window can hand
it, its entry in the manifest found by name, and `sf10_q03_nobhj` rehearsed
on the CPU with the metric on its traced line. A rehearsal's numbers are the
CPU's: a count is checked, never a time. No child process.

The file's name sorts it last, for test_y_decimal_cell.py's reason.

Appending the entry made one more accepted assertion by position untrue: the
last of test_zz_sf10_nobhj_cell.py::test_filter_mask_share_is_found_by_name
(what follows `filter_mask_share` is PR 34's three entries and nothing else).
No PR but a `benchmark` one may edit that file or benchmarks/conftest.py, so
that case fails from this PR on; every other assertion it makes is made here,
on entries found by name."""

import json

import pytest

from harness.registry import Registry

NAME = "slice_copy_share"
CELLS = ["sf10_q03_nobhj", "sf1_q03_nobhj", "sf1_q03_nobhj_x4"]


@pytest.fixture(scope="module")
def reg():
    return Registry()


@pytest.mark.parametrize("telemetry, want", [
    ({}, None),                                     # the parent: no counter
    ({"slice_copies": 0, "slice_gathers": 0}, None),
    ({"slice_copies": 912}, 100.0),                 # three queries of 304
    ({"slice_copies": 912, "slice_gathers": 0}, 100.0),
    ({"slice_gathers": 64}, 0.0),                   # lists in every batch
    ({"slice_copies": 3, "slice_gathers": 1}, 75.0),
])
def test_the_reader_over_a_windows_counters(reg, telemetry, want):
    run = {"window": [], "profiled": [], "telemetry": telemetry}
    assert reg.module("metrics", NAME).read(run) == want


def test_the_entry_is_found_by_name_and_lists_the_sort_merge_cells(reg):
    (entry,) = [m for m in reg.manifest["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "exchange and stages",
        "moves": "query_s.p50", "workloads": CELLS}
    assert entry["layer"] in {m["layer"] for m in reg.manifest["per_layer"]
                              if m is not entry}
    for cell in reg.manifest["workloads"]:
        listed = NAME in [m["name"]
                          for m in reg.metrics(cell["name"], "per_layer")]
        assert listed == (cell["name"] in CELLS)
    # appended after every entry the benchmark had (a later PR's entries
    # come after it: no position from the end is asserted)
    names = [m["name"] for m in reg.manifest["per_layer"]]
    assert names.index(NAME) > names.index("dispatches_per_query") == 24


def test_filter_mask_share_by_name_without_the_position(reg):
    """test_zz_sf10_nobhj_cell.py::test_filter_mask_share_is_found_by_name
    less its last assertion (this file's docstring)."""
    from test_z_filter_mask_share import CELLS as FILTER_CELLS

    (entry,) = [m for m in reg.manifest["per_layer"]
                if m["name"] == "filter_mask_share"]
    assert entry == {
        "name": "filter_mask_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "whole-stage and agg",
        "moves": "query_s.p50", "workloads": FILTER_CELLS}
    for cell in FILTER_CELLS:
        assert "filter_mask_share" in [
            m["name"] for m in reg.metrics(cell, "per_layer")]
    for cell in CELLS:
        assert "filter_mask_share" not in [
            m["name"] for m in reg.metrics(cell, "per_layer")]
    names = [m["name"] for m in reg.manifest["per_layer"]]
    assert names[names.index("filter_mask_share") + 1:][:3] == [
        "exchange_pinned_GB", "exchange_slice_rows", "dispatches_per_query"]


def test_the_sf10_nobhj_rehearsal_copies_every_slice():
    """Every column q3 exchanges is fixed-width, so every cut is a copy;
    the counters PR 34 brought read what they read before."""
    from test_y_decimal_cell import _rehearse

    line = json.loads(_rehearse("sf10_q03_nobhj", 2147483659, 200_000)[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert metrics[NAME] == {"value": 100.0, "unit": "%"}
    assert metrics["compiles_in_window"]["value"] == 0
    for name in ("exchange_pinned_GB", "exchange_slice_rows",
                 "dispatches_per_query", "shuffle_map_stage_s"):
        assert metrics[name]["value"] > 0
