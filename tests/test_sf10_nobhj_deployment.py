"""What the deployment tpcds_sf10_nobhj is, at a small size on the CPU: the
published q3 with both joins as exchange + sort-merge over a fact table that
arrives in many macro-batches (SF10's fourteen; here 50,000 rows cut into
2,048-row batches), on one device. The answer is the reference's and the
broadcast arm's; the exchange says what it cut, packed and kept for its
reduce tasks (TELEMETRY exchange_slices_cut / exchange_slices_packed /
exchange_slices_kept / exchange_rows_kept) and pinned (the
`stage` span's pinned_bytes); and a stage pushed past its memory
budget goes through files, which the benchmark's evidence refuses: the
configuration's guarantee is checked, not assumed."""

import importlib.util
import json
import os

import jax
import pytest

from blaze_tpu.config import conf
from blaze_tpu.runtime import compile_service, memory, trace
from blaze_tpu.spark.local_runner import run_plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
ROWS, BATCH_ROWS, SEED, WIDTH = 50_000, 2_048, 11, 4
PARAMS = {"month": 11, "manufact": 128}
COUNTERS = ("exchange_slices_kept", "exchange_rows_kept",
            "exchange_slices_cut", "exchange_slices_packed")


def _load(rel: str):
    spec = importlib.util.spec_from_file_location(
        "nb_" + rel.replace("/", "_").replace(".", "_"),
        os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", name + ".json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def deployment(tmp_path_factory):
    """The configuration's tables at ROWS fact rows and `run(config)`: one
    q3 from a fresh plan on one device with tracing on -> its frame,
    run_info, spans and the two counters' deltas. Knobs are restored after
    the module."""
    compare, q03 = _load("harness/compare.py"), _load("queries/q03.py")
    evidence = _load("harness/evidence.py")
    config = _config("tpcds_sf10_nobhj")
    paths, frames = _load(f"datagen/{config['generator']}.py").generate(
        config, SEED, str(tmp_path_factory.mktemp("sf10_nobhj")), ROWS)
    real = jax.devices
    with pytest.MonkeyPatch.context() as mp:
        # one chip, as the cell's machine shows the program
        mp.setattr(jax, "devices", lambda *a, **k: real(*a, **k)[:1])
        # a scan's batch is max(batch_size, min(target, max_batch_rows))
        mp.setattr(conf, "max_batch_rows", BATCH_ROWS)
        mp.setattr(conf, "batch_size", BATCH_ROWS)
        mp.setattr(conf, "trace_enabled", True)

        def run(cfg: dict) -> dict:
            info: dict = {}
            before = compile_service.TELEMETRY.snapshot()
            got = compare.to_frame(run_plan(
                q03.plan(paths, cfg, PARAMS), num_partitions=WIDTH,
                mesh_exchange=cfg["settings"]["mesh_exchange"],
                run_info=info))
            after = compile_service.TELEMETRY.snapshot()
            return {
                "frame": got, "info": info,
                "spans": [r for r in trace.query_records(info["query_id"])
                          if r.get("type") == "span"],
                "kept": {k: after[k] - before.get(k, 0) for k in COUNTERS}}

        yield {"config": config, "frames": frames, "run": run,
               "want": q03.reference(frames, config, PARAMS),
               "diff": lambda got, want: compare.diff(
                   got, want, config["guarantees"]["float_rtol"],
                   q03.ORDER_KEYS),
               "refusals": lambda info: evidence.refusals(info, 1, WIDTH)}


@pytest.fixture(scope="module")
def served(deployment):
    return deployment["run"](deployment["config"])


def _map_stages(spans: list) -> list:
    """(stage span attrs, its `exchange` spans), by stage id."""
    stages = sorted((s for s in spans if s["kind"] == "stage"
                     and s["attrs"]["stage_kind"] == "shuffle_map"),
                    key=lambda s: s["stage_id"])
    return [(s["attrs"], [x for x in spans if x["kind"] == "exchange"
                          and x["stage_id"] == s["stage_id"]])
            for s in stages]


def test_the_configuration_is_its_broadcast_twin_but_for_the_join():
    twin, config = _config("tpcds_sf10_bhj"), _config("tpcds_sf10_nobhj")
    assert config["settings"]["join"] == "sort_merge"
    assert twin["settings"]["join"] == "broadcast"
    for cfg in (twin, config):
        for key in ("name", "source", "deployment"):
            cfg.pop(key)
        cfg["settings"].pop("join")
    assert config == twin


def test_sort_merge_q3_over_many_batches_equals_its_reference(
        deployment, served):
    assert len(deployment["want"]) > 0
    assert deployment["diff"](served["frame"], deployment["want"]) is None
    assert deployment["refusals"](served["info"]) == []
    assert served["info"]["mesh_stages"] == 5
    assert served["info"]["broadcast_stages"] == 0


def test_it_equals_the_broadcast_arm_on_the_same_tables(deployment, served):
    twin = deployment["run"](_config("tpcds_sf10_bhj"))
    assert twin["info"]["broadcast_stages"] == 2
    assert deployment["refusals"](twin["info"]) == []
    # the comparison wants its reference side's text as str
    want = twin["frame"].assign(
        brand=[b.decode() for b in twin["frame"]["brand"]])
    assert deployment["diff"](served["frame"], want) is None
    assert list(served["frame"].columns) == list(twin["frame"].columns)


def test_the_counters_say_what_each_exchange_kept(deployment, served):
    ss, dd, it = (deployment["frames"][t]
                  for t in ("store_sales", "date_dim", "item"))
    in_month = dd.d_date_sk[dd.d_moy == PARAMS["month"]]
    # the rows that enter the first four exchanges, in stage order: the
    # whole fact table (null keys too), the month's dates, the fact rows
    # sold on them, the manufacturer's items; the fifth carries the four
    # join tasks' partial groups
    entered = [len(ss), len(in_month),
               int(ss.ss_sold_date_sk.isin(in_month).sum()),
               int((it.i_manufact_id == PARAMS["manufact"]).sum())]
    stages = _map_stages(served["spans"])
    # one `exchange` span a batch, with the rows it took in
    rows = [sum(x["attrs"]["rows"] for x in exchanges)
            for _, exchanges in stages]
    assert rows[:4] == entered
    groups = len(deployment["want"])
    assert groups < 100     # the limit cut nothing: these are the groups,
    # and a group's items may lie in every one of the join's partitions
    assert groups <= rows[4] <= WIDTH * groups
    assert all(attrs["transport"] == "mesh" for attrs, _ in stages)
    # every row that entered an exchange was kept for a reduce task
    assert served["kept"]["exchange_rows_kept"] == sum(rows)
    # the fact scan came in at least SF10's fourteen batches less one, of
    # ~2,000 rows over 1,800 dates and the null key: none of its slices is
    # empty; a later batch is kept as one to WIDTH non-empty slices
    batches = [len(exchanges) for _, exchanges in stages]
    assert batches[0] == -(-ROWS // BATCH_ROWS) >= 13
    cut = served["kept"]["exchange_slices_cut"]
    assert (WIDTH * batches[0] + sum(batches[1:]) <= cut
            <= WIDTH * sum(batches))
    # a partition's slices are packed up to the rows a scan hands on (here
    # BATCH_ROWS): a group holds no more, and two groups in a row hold more
    # between them, so a stage of r rows leaves a partition's share of
    # r / BATCH_ROWS to 2 r / BATCH_ROWS + WIDTH batches
    kept = served["kept"]["exchange_slices_kept"]
    assert (sum(-(-r // BATCH_ROWS) for r in rows) <= kept
            <= sum(2 * r // BATCH_ROWS + WIDTH for r in rows) < cut)
    # the fact table's hundred slices went into packed batches, but for a
    # partition's odd one out at the end; a kept batch is a slice left as
    # it was cut or a batch packed of two or more
    packed = served["kept"]["exchange_slices_packed"]
    assert WIDTH * (batches[0] - 1) <= packed <= cut
    assert packed >= 2 * (kept - (cut - packed)) > 0


def test_a_stage_pins_at_least_the_live_bytes_it_kept(served):
    for attrs, _ in _map_stages(served["spans"]):
        # `bytes` scales a slice by its live rows, `pinned_bytes` counts
        # its capacity: what the budget check compares
        assert attrs["pinned_bytes"] >= attrs["bytes"] > 0
    fact = _map_stages(served["spans"])[0][0]
    # three nullable 8-byte columns: a value and a validity byte a row
    assert fact["bytes"] >= ROWS * 3 * 8
    assert fact["pinned_bytes"] < memory.get_manager().total // 2


def test_past_the_budget_the_stage_takes_files_and_is_refused(
        deployment, served):
    """The exchange's budget (half the manager's total) forced under one
    fact batch's slices: what follows the first batch leaves HBM through
    files. The answer stands; the guarantee does not."""
    manager = memory.get_manager()
    total = manager.total
    one_batch = _map_stages(served["spans"])[0][0]["pinned_bytes"] \
        // -(-ROWS // BATCH_ROWS)
    manager.total = one_batch   # budget = half of it
    try:
        forced = deployment["run"](deployment["config"])
    finally:
        manager.total = total
    assert deployment["diff"](forced["frame"], deployment["want"]) is None
    assert forced["info"]["file_stages"] >= 1
    assert any(r.startswith("file_stages=")
               for r in deployment["refusals"](forced["info"]))
    # the first fact batch was kept, the others were not
    assert 0 < forced["kept"]["exchange_rows_kept"] < ROWS
    assert (forced["kept"]["exchange_slices_cut"]
            < served["kept"]["exchange_slices_cut"] - WIDTH * 12)
    assert (forced["kept"]["exchange_slices_kept"]
            < served["kept"]["exchange_slices_kept"])
    fact = _map_stages(forced["spans"])[0][1]
    assert [x["attrs"]["transport"] for x in fact] == (
        ["local"] + ["file"] * (len(fact) - 1))
