"""Fast CPU cases for the benchmark's own code (python -m pytest
benchmarks/tests -q). No topology call, no child process."""

import json
import os
import re
import shutil

import numpy as np
import pandas as pd
import pytest

import trace_reduce
from harness import compare, contract, evidence, loop, traffic
from harness.registry import BENCH_DIR, Registry

REPO = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def reg():
    return Registry()


def test_manifest_keeps_the_contracts_form(reg):
    m = reg.manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51
    ends = {e["name"] for e in m["end_to_end"]}
    assert "setup_s" in ends
    for entry in m["configs"] + m["workloads"] + m["end_to_end"] \
            + m["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
        for key in ("why", "layer", "source"):
            text = entry.get(key)
            assert text is None or (
                0 < len(text) <= 200 and "\n" not in text and "\t" not in text)
    for metric in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(metric["unit"]), metric["unit"]
        assert metric["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(
            BENCH_DIR, "metrics", metric["name"] + ".py"))
    for metric in m["end_to_end"]:
        assert set(metric) - {"workloads"} == {
            "name", "unit", "better", "bound", "source"}
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in m["per_layer"]:
        assert set(metric) - {"workloads"} == {
            "name", "unit", "better", "source", "layer", "moves"}
        assert metric["moves"] in ends
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(m["workloads"]) // 2)
    used = {w["config"] for w in m["workloads"]}
    for config in m["configs"]:
        assert config["name"] in used
        on_disk = json.load(open(os.path.join(REPO, config["file"])))
        assert on_disk["source"] == config["source"]
        assert set(config["reduced"]) == set(on_disk["reduced"])
        for key in ("assumed", "guarantees", "tables", "settings"):
            assert on_disk[key]
        assert on_disk["guarantees"]["float_rtol_reason"]
    for cell in m["workloads"]:
        assert cell["chips"] in (1, 4)
        mix = reg.data("traffic", cell["traffic"])
        for entry in mix["mix"]:
            assert os.path.exists(os.path.join(
                BENCH_DIR, "queries", entry["query"] + ".py"))


def test_a_cell_is_added_as_files_only(tmp_path):
    """A later PR's cell, configuration, query and per-layer metric: new
    files and manifest entries, no edit to run.py or harness/."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "fixtures"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    config = json.load(open(bench / "configs" / "tpcds_sf1_nobhj.json"))
    config["name"] = "tpcds_sf1_p200"
    config["settings"]["exchange_width"] = 200
    (bench / "configs" / "tpcds_sf1_p200.json").write_text(json.dumps(config))
    (bench / "traffic" / "count_loop2.json").write_text(json.dumps({
        "loop": "closed", "clients": 2, "mix": [
            {"query": "count_star", "weight": 3, "params": {}},
            {"query": "q06core", "weight": 1,
             "params": {"min_price": {"choice": [50.0, 100.0]}}}]}))
    (bench / "queries" / "count_star.py").write_text(
        "SCAN_COLUMNS = {'store_sales': {'ss_item_sk': 8}}\n"
        "ORDER_KEYS = None\n"
        "def reference(frames, config, params):\n"
        "    return len(frames['store_sales'])\n")
    (bench / "metrics" / "queries_done.x.py").write_text(
        "def read(run):\n    return len(run['window'])\n")
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    manifest["configs"].append({"name": "tpcds_sf1_p200"})
    manifest["workloads"].append({
        "name": "sf1_count_p200", "config": "tpcds_sf1_p200",
        "traffic": "count_loop2", "chips": 1, "why": "a test"})
    manifest["per_layer"].append({
        "name": "queries_done.x", "unit": "count",
        "workloads": ["sf1_count_p200"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    reg = Registry(str(bench))
    cell = reg.cell("sf1_count_p200")
    assert reg.data("configs", cell["config"])["settings"][
        "exchange_width"] == 200
    mix = reg.data("traffic", cell["traffic"])
    draws = traffic.schedule(mix, seed=1)
    first = [next(draws) for _ in range(8)]
    assert sorted(q for q, _ in first[:4]) == ["count_star"] * 3 + ["q06core"]
    assert reg.module("queries", "count_star").reference(
        {"store_sales": [1, 2]}, {}, {}) == 2
    names = [m["name"] for m in reg.metrics("sf1_count_p200", "per_layer")]
    assert "queries_done.x" in names and "plan_ms" in names
    assert "queries_done.x" not in [
        m["name"] for m in reg.metrics("sf10_q03_bhj", "per_layer")]
    assert reg.module("metrics", "queries_done.x").read(
        {"window": [1, 2, 3]}) == 3
    assert all(p.read_bytes() == data for p, data in before.items())


def test_traffic_gives_every_seed_the_same_work_in_another_order():
    mix = {"loop": "closed", "mix": [
        {"query": "a", "weight": 2, "params": {"k": 1}},
        {"query": "b", "weight": 1, "params": {"k": {"choice": [1, 2, 3]}}}]}
    passes = []
    for seed in (7, 2 ** 31 + 11):
        draws = traffic.schedule(mix, seed)
        run = [next(draws) for _ in range(30)]
        assert run == [next(d) for d in [traffic.schedule(mix, seed)]
                       for _ in range(30)]
        for i in range(0, 30, 3):
            assert sorted(q for q, _ in run[i:i + 3]) == ["a", "a", "b"]
        assert {p["k"] for q, p in run if q == "b"} <= {1, 2, 3}
        passes.append(run)
    assert passes[0] != passes[1]


@pytest.mark.parametrize("name", ["tpcds_sf10_bhj", "tpcds_sf1_nobhj"])
def test_generator_is_seeded_and_keeps_the_domains(reg, name, tmp_path):
    config = reg.data("configs", name)
    generate = reg.module("datagen", config["generator"]).generate
    for d in ("a", "b", "c"):
        (tmp_path / d).mkdir()
    paths, frames = generate(config, 2 ** 31 + 5, str(tmp_path / "a"), 20000)
    _, again = generate(config, 2 ** 31 + 5, str(tmp_path / "b"), 20000)
    _, other = generate(config, 6, str(tmp_path / "c"), 20000)
    for table in frames:
        pd.testing.assert_frame_equal(frames[table], again[table])
    assert not frames["store_sales"].equals(other["store_sales"])
    tables = config["tables"]
    ss, it, dd = frames["store_sales"], frames["item"], frames["date_dim"]
    assert len(it) == tables["item"]["rows"] and len(dd) == 73049
    assert len(ss) == 20000
    assert dd.d_date_sk.iloc[0] == 2415022
    first = dd[dd.d_date_sk == 2415022 + 36160].iloc[0]   # 1999-01-04
    assert (first.d_year, first.d_moy) == (1999, 1)
    sold = dd.set_index("d_date_sk").loc[ss.ss_sold_date_sk.dropna()]
    assert sold.d_year.min() == 1998 and sold.index.max() <= 2452642
    assert ss.ss_item_sk.between(1, len(it)).all()
    assert not ss.ss_item_sk.isna().any()
    for column in ("ss_sold_date_sk", "ss_sales_price", "ss_ext_sales_price"):
        assert 0.03 < ss[column].isna().mean() < 0.06
    assert it.i_manufact_id.between(1, 1000).all()
    assert it.groupby("i_brand_id").i_brand.nunique().max() == 1
    assert it.i_brand.str.match(r"^[a-z ]+ #\d+$").all()
    import pyarrow.parquet as pq
    for table, path in paths.items():
        assert pq.read_schema(path).names == list(tables[table]["columns"])
    assert pq.read_table(paths["store_sales"]).column(
        "ss_sold_date_sk").null_count == int(ss.ss_sold_date_sk.isna().sum())


@pytest.mark.parametrize("cell_name,rows", [
    ("sf10_q03_bhj", 150000), ("sf1_q06core_agg", 40000),
    ("sf1_q03_nobhj", 150000)])
def test_each_cells_query_equals_its_reference(reg, cell_name, rows, tmp_path):
    import jax

    cell_entry = dict(reg.cell(cell_name))
    # the program spreads an exchange over every device it sees
    cell_entry["chips"] = len(jax.devices())
    cell = loop.Cell(reg, cell_entry, 11, str(tmp_path), rows)
    query, params = next(traffic.schedule(cell.traffic, 11))
    done = cell.run_query(query, params)
    assert done["refused"] == []
    assert len(cell.reference(query, params)) > 0
    assert cell.scan_bytes(query) > 8 * rows
    # and the comparison can fail: another month is another answer
    other = cell.queries[query].reference(
        cell.frames, cell.config,
        {k: v + 1 for k, v in params.items()})
    assert compare.diff(other, cell.reference(query, params), 1e-9,
                        cell.queries[query].ORDER_KEYS) is not None


def test_compare_orders_nulls_and_tolerance():
    want = pd.DataFrame({"k": [1, 2, 3], "v": [1.0, np.nan, 3.0],
                         "s": ["a", "b", "c"]})
    got = pd.DataFrame({"k": [3, 1, 2], "v": [3.0 * (1 + 1e-12), 1.0, None],
                        "s": [b"c", b"a", b"b"]})
    assert compare.diff(got, want, 1e-9) is not None
    assert compare.diff(got, want, 1e-9, order_keys=["k"]) is None
    got.loc[0, "v"] = 3.0 * (1 + 1e-7)
    assert "column v" in compare.diff(got, want, 1e-9, order_keys=["k"])
    assert "row count" in compare.diff(got.head(2), want, 1e-9)


@pytest.mark.parametrize("run_info,expect", [
    ({"mesh_stages": 1, "mesh_devices": 1, "retries": 0}, []),
    ({"ladder_rung": 3}, ["ladder_rung=3"]),
    ({"errors.OOM": 1, "task_fallbacks": 2},
     ["errors.OOM=1", "task_fallbacks=2"]),
    ({"spill_count": 2}, ["spill_count=2"]),
    ({"mesh_stages": 1, "mesh_devices": 1, "file_stages": 1},
     ["file_stages=1"]),
])
def test_a_fallback_a_spill_or_a_file_exchange_is_refused(run_info, expect):
    assert evidence.refusals(run_info, chips=1, exchange_width=4) == expect


def test_an_exchange_on_too_few_devices_is_refused():
    on_one = {"mesh_stages": 3, "mesh_devices": 1}
    assert evidence.refusals(on_one, chips=1, exchange_width=4) == []
    assert evidence.refusals(on_one, chips=4, exchange_width=4) == [
        "mesh_devices=1 (want 4)"]


def test_contract_line_has_exactly_the_contracts_keys():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 5, "extra": 1}
    line = json.loads(contract.contract_line(
        True, 9, 0, {"setup_s": {"value": 1.5, "unit": "s"}}, device))
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    device.update(busy_s=1.0, window_s=2.0)
    ops = [[f"op{i}", 1.0] for i in range(12)]
    line = json.loads(contract.contract_line(False, 9, 2, {}, device, {
        "device_ops": ops, "idle_gaps": ops, "per_device": []}))
    assert set(line["device"]) >= {"busy_s", "window_s"}
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert len(line["breakdown"]["device_ops"]) == 10
    assert line["correct"] is False and line["failed"] == 2


def test_trace_reducer_on_the_recorded_v5e_trace():
    """fixtures/tiny_v5e.xplane.pb (fixtures/record.py): three 35.5 us
    steps, the first of which the device clock puts 1.1 ms before the first
    host annotation, so two count. Values read by hand from the dump."""
    r = trace_reduce.reduce_trace(os.path.join(
        BENCH_DIR, "fixtures", "tiny_v5e.xplane.pb"))
    assert r["queries"] == 3 and len(r["per_device"]) == 1
    assert r["window_s"] == pytest.approx(0.086995166, abs=1e-9)
    assert r["busy_s"] == pytest.approx(70.996e-6, abs=1e-9)
    assert r["per_device"][0]["idle_share"] == pytest.approx(
        1 - 70.996e-6 / 0.086995166)
    assert r["device_ops"][0][0] == \
        "jit__lambda(17575888338393728085)/%fusion"
    assert r["device_ops"][0][1] == pytest.approx(70.963e-6, abs=1e-9)
    assert r["idle_gaps"][0][1] == pytest.approx(0.032793545, abs=1e-9)
    assert sum(g[1] for g in r["idle_gaps"]) <= r["window_s"]
    assert r["idle_gaps"][1][0] == "bench_query_0"


def test_merge_is_a_union():
    assert trace_reduce._merge([(5, 7), (0, 2), (1, 3), (3, 4), (6, 6.5)]) \
        == [[0, 4], [5, 7]]


@pytest.mark.parametrize("env,rows", [("cpu", 0), ("", 1000)])
def test_a_rehearsal_cannot_pass_for_a_measurement(monkeypatch, env, rows):
    """JAX_PLATFORMS=cpu without --rehearse-rows, or the reverse, is refused
    before jax is touched."""
    monkeypatch.setenv("JAX_PLATFORMS", env)
    with pytest.raises(SystemExit):
        loop._select_platform(rows)
    assert os.environ["JAX_PLATFORMS"] == env


def test_readers_return_nothing_where_there_is_nothing_to_read(reg):
    empty = {"window": [{"seconds": 1.0, "spans": None, "query": "q"}] * 3,
             "profiled": [], "reduction": None, "telemetry": {},
             "memory_peak_bytes": None, "peaks": None, "chips": 1,
             "scan_bytes": {"q": 1}, "setup_s": 2.0,
             "warm_first_query_s": 1.0}
    got = {m["name"]: reg.module("metrics", m["name"]).read(empty)
           for m in reg.manifest["per_layer"] + reg.manifest["end_to_end"]}
    assert got["setup_s"] == 2.0 and got["query_s.p50"] == 1.0
    for name in ("plan_ms", "shuffle_map_stage_s", "whole_stage_share",
                 "peak_hbm_GB", "hbm_roofline_share", "device_idle_share"):
        assert got[name] is None
    assert reg.module("metrics", "query_s.p90").read(empty) is None
    with pytest.raises(SystemExit):
        reg.peaks("TPU v99")
    assert reg.peaks("TPU v5 lite")["hbm_GBps"] == 819.0
