"""`exchange_rank_share`: its reader over the counters a window can
hand it, its entry in the manifest found by name, and `sf1_q03_nobhj`
rehearsed on the CPU with the metric on its traced line. A rehearsal's numbers
are the CPU's: counts are checked, never a time. No child process. No position
from the end of a list is asserted.

The file's name sorts it last, for test_y_decimal_cell.py's reason."""

import json

import pytest

from harness.registry import Registry

NAME = "exchange_rank_share"
CELLS = ["sf10_q03_bhj", "sf1_q06core_agg", "sf1_q03_nobhj",
         "sf10_q06core_agg", "sf1_q06core_agg_dec", "sf10_q03_nobhj"]


@pytest.fixture(scope="module")
def reg():
    return Registry()


@pytest.mark.parametrize("telemetry, want", [
    ({}, None),                                     # the parent: no counter
    ({"exchange_slices_cut": 47}, None),            # the parent's own
    ({"exchange_planes_ranked": 0, "exchange_planes_gathered": 0}, None),
    ({"exchange_planes_ranked": 56}, 100.0),
    ({"exchange_planes_gathered": 9}, 0.0),
    ({"exchange_planes_ranked": 4, "exchange_planes_gathered": 2},
     100 * 4 / 6),
    ({"exchange_planes_ranked": 61, "exchange_planes_gathered": 39}, 61.0),
])
def test_the_reader_over_a_windows_counters(reg, telemetry, want):
    run = {"window": [], "profiled": [], "telemetry": telemetry}
    assert reg.module("metrics", NAME).read(run) == want


def test_the_entry_is_found_by_name_and_lists_the_one_chip_cells(reg):
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
        assert listed == (cell["name"] in CELLS) == (cell["chips"] == 1)
    # appended after every entry the benchmark had
    names = [m["name"] for m in reg.manifest["per_layer"]]
    assert names.index(NAME) > names.index("exchange_pack_share") == 30


def test_the_sf1_nobhj_rehearsal_ranks_the_fact_tables_integers():
    """Every exchange of the sort-merge plan runs `local_xchg`: the fact
    side's two nullable longs are ranked (four planes) and its double
    gathered with its validity (two), the dimension sides' and the partial
    aggregate's integers ranked, their strings and double sums gathered:
    some planes each way."""
    from test_y_decimal_cell import _rehearse

    line = json.loads(_rehearse("sf1_q03_nobhj", 2147483699, 50_000)[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert metrics[NAME]["unit"] == "%"
    assert 50 < metrics[NAME]["value"] < 100
    assert metrics["compiles_in_window"]["value"] == 0
