"""`exchange_pack_share` (PR 37): its reader over the counters a window can
hand it, its entry in the manifest found by name, and `sf10_q03_nobhj`
rehearsed on the CPU with the metric on its traced line. A rehearsal's numbers
are the CPU's: counts are checked, never a time. No child process. No position
from the end of a list is asserted.

The file's name sorts it last, for test_y_decimal_cell.py's reason."""

import json

import pytest

from harness.registry import Registry

NAME = "exchange_pack_share"
CELLS = ["sf10_q03_nobhj", "sf1_q03_nobhj", "sf1_q03_nobhj_x4"]


@pytest.fixture(scope="module")
def reg():
    return Registry()


@pytest.mark.parametrize("telemetry, want", [
    ({}, None),                                     # the parent: no counter
    ({"exchange_slices_kept": 912}, None),          # the parent's own two
    ({"exchange_slices_cut": 0, "exchange_slices_packed": 0}, None),
    ({"exchange_slices_cut": 3}, 0.0),              # one slice a partition
    ({"exchange_slices_cut": 12, "exchange_slices_packed": 0}, 0.0),
    ({"exchange_slices_cut": 912, "exchange_slices_packed": 888},
     100 * 888 / 912),
    ({"exchange_slices_cut": 40, "exchange_slices_packed": 40}, 100.0),
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
    # appended after every entry the benchmark had
    names = [m["name"] for m in reg.manifest["per_layer"]]
    assert names.index(NAME) > names.index("stage_self_share") == 29


def test_the_sf10_nobhj_rehearsal_packs_what_its_joins_are_handed():
    """At rehearsal rows every scan is one batch, so three exchanges cut a
    partition one slice and pack nothing; the date join's four tasks hand
    on a batch each, and a partition's four slices of them are packed into
    one (16 of a query's slices), as are the item join's few groups where
    a partition got more than one: over half of some 35 slices a query."""
    from test_y_decimal_cell import _rehearse

    line = json.loads(_rehearse("sf10_q03_nobhj", 2147483693, 200_000)[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert metrics[NAME]["unit"] == "%"
    assert 100 * 16 / 44 < metrics[NAME]["value"] < 100
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["slice_copy_share"]["value"] == 100.0
    for name in ("exchange_pinned_GB", "exchange_slice_rows",
                 "dispatches_per_query", "shuffle_map_stage_s"):
        assert metrics[name]["value"] > 0
