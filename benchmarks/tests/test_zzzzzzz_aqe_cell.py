"""The cell `sf10_q03_nobhj_p200` (Spark's 200 shuffle partitions under AQE
partition coalescing): its configuration, cell and per-layer entry in
the manifest found by name, its query module beside q03's, and its
reader over the spans a window can hand it. No child
process; no position from the end of a list is asserted.

The file's name sorts it last, for test_y_decimal_cell.py's reason."""

import json
import os

import pytest

from harness.registry import Registry

CELL, CONFIG = "sf10_q03_nobhj_p200", "tpcds_sf10_nobhj_p200"


@pytest.fixture(scope="module")
def reg():
    return Registry()


def test_the_configuration_is_the_twins_at_spark_defaults(reg):
    mine, twin = reg.data("configs", CONFIG), reg.data(
        "configs", "tpcds_sf10_nobhj")
    for key in ("generator", "scale_factor", "tables", "parquet",
                "guarantees"):
        assert mine[key] == twin[key]
    assert mine["settings"] == {
        **twin["settings"], "exchange_width": 200,
        "aqe": {"advisory_partition_bytes": 64 << 20}}
    assert sorted(mine["reduced"]) == ["money_type", "parquet_columns"]
    assert mine["assumed"][:len(twin["assumed"])] == twin["assumed"]
    (entry,) = [c for c in reg.manifest["configs"] if c["name"] == CONFIG]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert set(entry["reduced"]) == set(mine["reduced"])
    assert entry["source"] == mine["source"] and len(entry["source"]) <= 200
    assert os.path.exists(os.path.join(os.path.dirname(reg.dir),
                                       entry["file"]))


def test_the_cell_runs_q3_under_an_adaptive_root_on_one_chip(reg):
    cell = reg.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "q03_aqe_loop1", 1)
    assert len(cell["why"]) <= 200
    (entry,) = reg.data("traffic", cell["traffic"])["mix"]
    assert entry["query"] == "q03_aqe"
    assert entry["params"] == reg.data("traffic", "q03_loop1")["mix"][0][
        "params"]
    q03, aqe = reg.module("queries", "q03"), reg.module("queries", "q03_aqe")
    assert aqe.SCAN_COLUMNS == q03.SCAN_COLUMNS
    assert aqe.ORDER_KEYS == q03.ORDER_KEYS


@pytest.mark.parametrize("name, unit, better, source", [
    ("reduce_tasks_per_query", "count", "lower", "program_span")])
def test_the_entries_are_found_by_name(reg, name, unit, better, source):
    (entry,) = [m for m in reg.manifest["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": "exchange and stages",
                     "moves": "query_s.p50", "workloads": [CELL]}
    names = [m["name"] for m in reg.manifest["per_layer"]]
    assert names.index(name) > names.index("exchange_rank_share")


def _aqe(tasks):
    return {"kind": "aqe", "attrs": {"tasks": tasks, "partitions": 200}}


@pytest.mark.parametrize("window, want", [
    ([], None),                                        # nothing ran
    ([{"spans": None}], None),                         # tracing off
    ([{"spans": [{"kind": "stage", "attrs": {}}]}], None),  # no aqe span
    ([{"spans": [_aqe(12), _aqe(1), _aqe(1)]}], 14),
    ([{"spans": [_aqe(12), _aqe(1)]}, {"spans": [_aqe(13), _aqe(2)]},
      {"spans": [_aqe(14), _aqe(1)]}], 15),            # a median of sums
])
def test_reduce_tasks_per_query(reg, window, want):
    run = {"window": window, "profiled": [], "telemetry": {}}
    assert reg.module("metrics", "reduce_tasks_per_query").read(run) == want


def test_the_rehearsal_carries_both_metrics():
    from test_y_decimal_cell import _rehearse

    line = json.loads(_rehearse(CELL, 2400420003, 50_000)[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["reduce_tasks_per_query"]["value"] == 3
