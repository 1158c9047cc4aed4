"""`filter_mask_share` (PR 33): its reader over the counters a window can
hand it, its entry in the manifest, and the SF10 q06core cell rehearsed on the
CPU with the metric on its traced line. A rehearsal's numbers are the CPU's:
a count is checked, never a time. No child process.

The file's name sorts it last, for test_y_decimal_cell.py's reason."""

import json

import pytest

from harness.registry import Registry

CELLS = ["sf1_q06core_agg", "sf10_q06core_agg", "sf1_q06core_agg_dec",
         "sf10_q03_bhj"]


@pytest.fixture(scope="module")
def reg():
    return Registry()


@pytest.mark.parametrize("telemetry, want", [
    ({}, None),                                     # the parent: no counter
    ({"filter_masks_carried": 0, "filter_compactions": 0}, None),
    ({"filter_masks_carried": 0, "filter_compactions": 28}, 0.0),   # q3
    ({"filter_masks_carried": 56, "filter_compactions": 0}, 100.0),
    ({"filter_masks_carried": 3, "filter_compactions": 1}, 75.0),
])
def test_the_reader_over_a_windows_counters(reg, telemetry, want):
    run = {"window": [], "profiled": [], "telemetry": telemetry}
    assert reg.module("metrics", "filter_mask_share").read(run) == want


def test_the_entry_names_the_cells_that_filter(reg):
    entry = reg.manifest["per_layer"][-1]
    assert entry == {
        "name": "filter_mask_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "whole-stage and agg",
        "moves": "query_s.p50", "workloads": CELLS}
    for cell in CELLS:
        assert "filter_mask_share" in [
            m["name"] for m in reg.metrics(cell, "per_layer")]
        assert reg.cell(cell)["chips"] == 1
    for cell in ("sf1_q03_nobhj", "sf1_q03_nobhj_x4"):
        assert "filter_mask_share" not in [
            m["name"] for m in reg.metrics(cell, "per_layer")]
    layers = {m["layer"] for m in reg.manifest["per_layer"][:-1]}
    assert entry["layer"] in layers


def test_the_sf10_q06core_rehearsal_carries_every_mask():
    """200,000 rows keep SF10's 102,000 item keys, past the whole-stage
    path's dense range, so the map stage streams as it does on the chip:
    scan -> filter -> partial agg, the filter's mask in the collapse."""
    from test_y_decimal_cell import _rehearse

    line = json.loads(_rehearse("sf10_q06core_agg", 2147483659, 200_000)[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["filter_mask_share"] == {"value": 100.0,
                                                    "unit": "%"}
    assert line["metrics"]["compiles_in_window"]["value"] == 0
