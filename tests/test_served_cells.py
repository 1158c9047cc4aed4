"""The benchmark's one-chip cells on the CPU, at rehearsal rows: each cell's
own plan (benchmarks/queries) over its own generator's tables through
run_plan equals its plain reference with nothing refused
(benchmarks/harness/evidence.py), and once warm a query compiles nothing:
the `compiles_in_window` contract of BENCHMARK.json; traced, every
shuffle_map stage records the driver's `wait` spans and the four readers of
them give numbers. The cells, their configurations and their traffic are read
from the manifest."""

import importlib.util
import json
import os

import jax
import pytest

from blaze_tpu.config import conf
from blaze_tpu.runtime import compile_service, trace
from blaze_tpu.spark.local_runner import run_plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
ROWS = 50_000
SEED = 11


def _load(rel: str):
    spec = importlib.util.spec_from_file_location(
        "sc_" + rel.replace("/", "_").replace(".", "_"),
        os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*rel: str):
    with open(os.path.join(*rel)) as fh:
        return json.load(fh)


ONE_CHIP = {w["name"]: w for w in _json(REPO, "BENCHMARK.json")["workloads"]
            if w["chips"] == 1}


def test_the_manifest_has_the_one_chip_cells_this_file_names():
    assert set(ONE_CHIP) == {"sf10_q03_bhj", "sf1_q06core_agg",
                             "sf1_q03_nobhj", "sf10_q06core_agg",
                             "sf1_q06core_agg_dec", "sf10_q03_nobhj",
                             "sf10_q03_nobhj_p200"}


@pytest.fixture(scope="module")
def cells(tmp_path_factory):
    """name -> one query of the cell: fresh plan through run_plan, the
    differences from the reference (None = equal) and the refusals."""
    compare, evidence = _load("harness/compare.py"), _load("harness/evidence.py")
    tables = {}

    def cell(name: str):
        config = _json(BENCH, "configs", ONE_CHIP[name]["config"] + ".json")
        (entry,) = _json(BENCH, "traffic",
                         ONE_CHIP[name]["traffic"] + ".json")["mix"]
        query = _load(f"queries/{entry['query']}.py")
        if config["name"] not in tables:
            tables[config["name"]] = _load(
                f"datagen/{config['generator']}.py").generate(
                    config, SEED,
                    str(tmp_path_factory.mktemp(config["name"])), ROWS)
        paths, frames = tables[config["name"]]
        settings, params = config["settings"], entry["params"]

        def run():
            info: dict = {}
            got = compare.to_frame(run_plan(
                query.plan(paths, config, params),
                num_partitions=settings["exchange_width"],
                mesh_exchange=settings["mesh_exchange"], run_info=info))
            want = query.reference(frames, config, params)
            assert len(want) > 0
            wrong = compare.diff(got, want,
                                 config["guarantees"]["float_rtol"],
                                 query.ORDER_KEYS)
            return wrong, evidence.refusals(info, 1,
                                            settings["exchange_width"])

        return run

    return cell


@pytest.fixture
def one_chip(monkeypatch):
    """Show the program one of the eight virtual devices, as the cell's
    machine does."""
    real = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a, **k: real(*a, **k)[:1])


@pytest.mark.parametrize("name", ["sf10_q03_bhj", "sf1_q06core_agg",
                                  "sf10_q06core_agg", "sf1_q06core_agg_dec",
                                  "sf10_q03_nobhj", "sf10_q03_nobhj_p200"])
def test_cell_equals_its_reference_and_nothing_is_refused(
        cells, one_chip, name):
    wrong, refused = cells(name)()
    assert wrong is None
    assert refused == []


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_a_warm_query_compiles_nothing(cells, one_chip, name):
    run = cells(name)
    for _ in range(2):      # the benchmark's two warm-up queries
        assert run() == (None, [])
    before = compile_service.TELEMETRY.snapshot().get("compile_count", 0)
    assert before > 0       # the counter counts: the warm-ups compiled
    assert run() == (None, [])
    assert compile_service.TELEMETRY.snapshot().get(
        "compile_count", 0) == before


WAIT_READERS = ("host_wait_s", "blocking_waits_per_query",
                "wait_ready_share", "stage_self_share")
# the one-chip cells the four readers' closed lists were accepted with
# (stage_self_share reads no four-chip cell); a cell added since is read by
# them only once a benchmark PR widens the lists
WAIT_READ_CELLS = ("sf10_q03_bhj", "sf1_q06core_agg", "sf1_q03_nobhj",
                   "sf10_q06core_agg", "sf1_q06core_agg_dec",
                   "sf10_q03_nobhj")


def test_the_wait_readers_lists_hold_the_cells_they_were_accepted_with():
    manifest = {m["name"]: m
                for m in _json(REPO, "BENCHMARK.json")["per_layer"]}
    for r in WAIT_READERS:
        assert set(WAIT_READ_CELLS) <= set(manifest[r]["workloads"])


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_traced_every_map_stage_records_waits_and_the_readers_read_them(
        cells, one_chip, name):
    saved = conf.trace_enabled
    trace.reset()
    conf.trace_enabled = True
    try:
        assert cells(name)() == (None, [])
        spans = [r for r in trace.TRACE.snapshot() if r["type"] == "span"]
    finally:
        conf.trace_enabled = saved
        trace.reset()
    stages = [s for s in spans if s["kind"] == "stage"
              and s["attrs"]["stage_kind"] == "shuffle_map"]
    waits = [s for s in spans if s["kind"] == "wait"]
    assert stages and waits
    by_id = {s["id"]: s for s in spans}
    for st in stages:
        mine = [w for w in waits if w.get("stage_id") == st["stage_id"]]
        assert mine, f"stage {st['stage_id']} recorded no wait"
        for w in mine:
            assert type(w["attrs"]["ready"]) is bool and w["attrs"]["site"]
            assert w["query_id"] == st["query_id"]
            assert by_id[w["parent"]]["kind"] != "query"
            assert st["ts"] <= w["ts"] <= st["ts"] + st["dur"]
    # a wait is never a direct child of the query span: query_self_share
    # reads as before
    query = next(s for s in spans if s["kind"] == "query")
    assert all(w["parent"] != query["id"] for w in waits)
    run = {"window": [{"spans": spans}], "profiled": []}
    values = {r: _load(f"metrics/{r}.py").read(run) for r in WAIT_READERS}
    assert values["host_wait_s"] > 0
    assert values["blocking_waits_per_query"] >= 0
    assert 0 <= values["wait_ready_share"] <= 100
    assert 0 < values["stage_self_share"] < 100
    # on a run from before the span the four are silent
    old = {"window": [{"spans": [s for s in spans if s["kind"] != "wait"]}],
           "profiled": []}
    assert [_load(f"metrics/{r}.py").read(old)
            for r in WAIT_READERS] == [None] * 4


SORT_MERGE = ("sf10_q03_nobhj", "sf1_q03_nobhj")
ADAPTIVE = ("sf10_q03_nobhj_p200",)
PACK_COUNTERS = ("exchange_slices_cut", "exchange_slices_packed",
                 "exchange_slices_kept")


@pytest.mark.parametrize("name", sorted(ONE_CHIP))
def test_only_the_sort_merge_plans_pack_what_their_joins_are_handed(
        cells, one_chip, name):
    """A join stage's tasks each hand on a batch, and the next exchange
    cuts every one into a slice a partition: the task that reads a
    partition is handed them as one batch and probes once. Where every
    exchange carries one batch (the broadcast and the aggregate plans) a
    partition's one slice is left as it lies and no pack program runs."""
    width = _json(BENCH, "configs", ONE_CHIP[name]["config"] + ".json")[
        "settings"]["exchange_width"]
    saved = conf.trace_enabled
    trace.reset()
    conf.trace_enabled = True
    before = compile_service.TELEMETRY.snapshot()
    try:
        assert cells(name)() == (None, [])
        spans = [r for r in trace.TRACE.snapshot() if r["type"] == "span"]
    finally:
        conf.trace_enabled = saved
        trace.reset()
    after = compile_service.TELEMETRY.snapshot()
    cut, packed, kept = (after[k] - before.get(k, 0) for k in PACK_COUNTERS)
    programs = [(s["attrs"]["program"], s.get("stage_id")) for s in spans
                if s["kind"] == "dispatch"]
    packs = [stage for program, stage in programs
             if program == "exchange_pack"]
    if name in ADAPTIVE:
        # reduce tasks read ranges of partitions (an `aqe` span a stage):
        # a range's slices, one a map batch, are packed into one batch
        # where they fit one, and the item join probes once a range
        ranges = {s["stage_id"]: s["attrs"]["tasks"] for s in spans
                  if s["kind"] == "aqe"}
        assert sorted(ranges) == [2, 4, 5]
        assert all(packs.count(st) <= ranges[st] for st in set(packs))
        assert kept == cut - packed + len(packs) and cut > 0
        probes = [stage for program, stage in programs
                  if program == "join_match"]
        assert probes.count(4) == ranges[4] < width
        return
    if name not in SORT_MERGE:
        assert not packs and packed == 0 and kept == cut > 0
        return
    # the two join stages (2 and 4) exchange their tasks' outputs: the
    # date join's, a pack a partition; the item join's few groups (three
    # at these rows) where a partition got more than one
    assert packs.count(2) == width and set(packs) <= {2, 4}
    assert packed >= width * width and kept == cut - packed + len(packs)
    # the item join's tasks probe once each, not once a date-join task
    probes = [stage for program, stage in programs if program == "join_match"]
    assert probes.count(4) == width < width * width
