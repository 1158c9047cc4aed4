"""The four-chip deployment on virtual devices: exchanged partitions stay on,
and are consumed on, the chip that owns them (runtime/placement.py,
parallel/stage_exchange.py, spark/local_runner.py), checked on the
benchmark's own q03 no-BHJ plan against its plain reference; the spans and
counters that say so; and the three benchmark readers of them."""

import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from blaze_tpu.columnar import ColumnBatch, Field, FLOAT64, INT64, Schema
from blaze_tpu.config import conf
from blaze_tpu.runtime import jit_cache, placement, resources, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
FALLBACKS = ("retries", "degradations", "ladder_rung", "task_fallbacks",
             "breaker_trips", "breaker_reroutes", "bytes_copied_fallback",
             "pool_stages", "file_stages", "spill_count")


def _load(rel: str):
    path = os.path.join(BENCH, rel)
    spec = importlib.util.spec_from_file_location(
        "mr_" + rel.replace("/", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    """The cell's configuration, generator, query and comparison."""
    with open(os.path.join(BENCH, "configs", "tpcds_sf1_nobhj_x4.json")) as fh:
        config = json.load(fh)
    return {"config": config, "gen": _load("datagen/tpcds.py"),
            "q03": _load("queries/q03.py"),
            "compare": _load("harness/compare.py")}


@pytest.fixture
def tables(bench, tmp_path_factory):
    made = {}

    def get(seed: int, rows: int = 50_000):
        if (seed, rows) not in made:
            out = str(tmp_path_factory.mktemp(f"t{seed}_{rows}"))
            made[seed, rows] = bench["gen"].generate(
                bench["config"], seed, out, rows)
        return made[seed, rows]

    return get


@pytest.fixture
def visible(monkeypatch):
    """Show the program only the first n of the eight virtual devices."""
    real = jax.devices

    def limit(n: int):
        monkeypatch.setattr(
            jax, "devices", lambda *a, **k: real(*a, **k)[:n])

    return limit


@pytest.fixture
def traced():
    old = conf.trace_enabled
    conf.trace_enabled = True
    trace.reset()
    yield
    conf.trace_enabled = old
    trace.reset()


PARAMS = {"month": 11, "manufact": 128}


def _run_q03(bench, tables, seed: int, width: int = 4):
    from blaze_tpu.spark.local_runner import run_plan

    paths, frames = tables(seed)
    config = json.loads(json.dumps(bench["config"]))
    config["settings"]["exchange_width"] = width
    info: dict = {}
    out = run_plan(bench["q03"].plan(paths, config, PARAMS),
                   num_partitions=width, mesh_exchange="auto", run_info=info)
    got = bench["compare"].to_frame(out)
    want = bench["q03"].reference(frames, config, PARAMS)
    wrong = bench["compare"].diff(
        got, want, config["guarantees"]["float_rtol"], None)
    return info, wrong, len(want)


# (a), (b): the cell's own plan, row for row against its reference
@pytest.mark.parametrize("seed,width,ndev,mesh_devices", [
    (11, 4, 4, 4),             # the cell: one partition per chip
    (2147483659, 4, 4, 4),     # another seed, other partition sizes
    (11, 8, 4, 4),             # P > D: two partitions a chip
    (11, 4, 8, 4),             # more chips than partitions: four are used
])
def test_q03_nobhj_equals_its_reference_on_the_mesh(
        bench, tables, visible, seed, width, ndev, mesh_devices):
    visible(ndev)
    info, wrong, rows = _run_q03(bench, tables, seed, width)
    assert wrong is None and rows > 0
    assert info["mesh_stages"] == 5 and info["mesh_devices"] == mesh_devices
    assert info["mesh_host_bytes"] == 0
    assert {k: info[k] for k in FALLBACKS if info.get(k)} == {}
    assert not [k for k in info if k.startswith("errors.")]


# (c): where the partitions lie, and what the spans say of it
SCHEMA = Schema([Field("k", INT64), Field("v", FLOAT64)])


def _writer_node(batches, partitions: int):
    from blaze_tpu.plan import plan_pb2 as pb
    from blaze_tpu.plan.to_proto import encode_schema

    rid = resources.register(lambda: iter(batches))
    node = pb.PlanNode()
    w = node.shuffle_writer
    w.input.ffi_reader.schema.CopyFrom(encode_schema(SCHEMA))
    w.input.ffi_reader.export_iter_resource_id = rid
    w.partitioning.kind = pb.HashRepartition.HASH
    w.partitioning.num_partitions = partitions
    w.partitioning.keys.add().column.name = "k"
    return node, rid


def _devices_of(batch) -> set:
    return {d for x in jax.tree_util.tree_leaves(batch)
            if isinstance(x, jax.Array) for d in x.devices()}


@pytest.mark.parametrize("partitions,ndev", [(4, 4), (8, 4), (4, 8)])
def test_provider_yields_each_partition_on_its_owner_chip(
        rng, visible, partitions, ndev):
    from blaze_tpu.parallel.stage_exchange import run_mesh_shuffle_stage

    visible(ndev)
    batch = ColumnBatch.from_numpy(
        {"k": rng.integers(0, 10_000, 3000).astype(np.int64),
         "v": rng.random(3000)}, SCHEMA)
    node, rid = _writer_node([batch], partitions)
    stats: dict = {}
    assert run_mesh_shuffle_stage(node, stage_id=981, ntasks=1, stats=stats)
    use_d, kpd = placement.layout(partitions, ndev)
    assert stats["devices"] == use_d == 4 and "host_bytes" not in stats
    reader = resources.get("shuffle:981")
    rows = 0
    for p in range(partitions):
        owner = placement.owner(p, partitions)
        assert owner == jax.devices()[p // kpd]
        with placement.on_device(owner):   # a task placed on the owner
            for b in reader(p):
                assert _devices_of(b) == {owner}
                rows += int(b.num_rows)
        for b in reader(p):                # an unplaced reader: a copy
            assert _devices_of(b) == {jax.devices()[0]}
    assert rows == 3000
    resources.pop("shuffle:981")
    resources.pop(rid)


def test_consumer_spans_name_the_chip_that_owns_the_partition(
        bench, tables, visible, traced):
    visible(4)
    info, wrong, _ = _run_q03(bench, tables, 11)
    assert wrong is None and info["mesh_host_bytes"] == 0
    spans = [r for r in trace.query_records(info["query_id"])
             if r.get("type") == "span"]
    # the tasks of the stages that read exchanged partitions (two join
    # stages and the result stage: 12 tasks) ran on the chip that owns
    # their partition, off the driver's thread; a scan stage's one task
    # is no attempt
    tasks = {s["task_id"]: s for s in spans if s["kind"] == "task_attempt"}
    assert len(tasks) == 12
    for s in tasks.values():
        assert s["attrs"]["device"] == placement.owner(
            s["attrs"]["partition"], 4).id
        assert s["thread"].startswith("blz-task")
    assert {s["attrs"]["device"] for s in tasks.values()} == {0, 1, 2, 3}
    # every exchanged batch was handed over on the consuming task's chip
    # (a join's small side, read whole by every task, as a copy from its
    # owner's), each task took its own partition there, none via the host
    placed = [s for s in spans if s["kind"] == "exchange"
              and s["attrs"]["transport"] == "place"]
    own = set()
    for s in placed:
        task = tasks[s["task_id"]]["attrs"]
        assert s["attrs"]["device"] == task["device"]
        assert s["attrs"]["host_bytes"] == 0
        if s["attrs"]["partition"] == task["partition"]:
            own.add(s["task_id"])
    # (a result task whose partition got no group has nothing to take)
    assert own >= {t for t in tasks if t.startswith("mesh_map")}
    mesh = [s for s in spans if s["kind"] == "exchange"
            and s["attrs"]["transport"] == "mesh"]
    assert mesh and all(
        s["attrs"]["devices"] == 4 and s["attrs"]["rows"] > 0
        and s["attrs"]["bytes"] > 0 and s["attrs"]["capacity"] > 0
        and s["attrs"]["host_bytes"] == 0 for s in mesh)
    assert trace.TRACE.dropped == 0


def test_placed_programs_are_keyed_by_chip(visible):
    visible(4)
    jit_cache.clear()
    made = []

    def make():
        made.append(placement.current())
        return lambda x: x + 1

    x = np.arange(4)
    for dev in [None, jax.devices()[1], jax.devices()[2], jax.devices()[1]]:
        with placement.on_device(dev):
            out = jit_cache.get_or_compile(("test_placed_kind", 4), make)(x)
            assert _devices_of(out) == {dev or jax.devices()[0]}
    assert made == [None, jax.devices()[1], jax.devices()[2]]
    assert placement.current() is None


# (e): one device takes none of it
def test_one_device_runs_as_before(bench, tables, visible, traced):
    visible(1)
    jit_cache.clear()
    info, wrong, _ = _run_q03(bench, tables, 11)
    assert wrong is None
    assert info["mesh_stages"] == 5 and info["mesh_devices"] == 1
    assert info["mesh_host_bytes"] == 0
    spans = [r for r in trace.query_records(info["query_id"])
             if r.get("type") == "span"]
    assert {s["attrs"]["transport"] for s in spans
            if s["kind"] == "exchange"} == {"local"}
    assert {s["attrs"]["device"] for s in spans
            if s["kind"] == "task_attempt"} == {0}
    kinds = {s["attrs"]["program"] for s in spans if s["kind"] == "dispatch"}
    assert "local_xchg" in kinds
    assert not kinds & {"mesh_xchg", "mesh_fit"}
    with jit_cache._lock:
        keys = list(jit_cache._cache)
    assert keys and not [k for k in keys if "@dev" in repr(k)]


# (f): tracing off builds no span on the new paths
def test_tracing_off_builds_no_span_on_the_placed_paths(
        bench, tables, visible, monkeypatch):
    visible(4)
    conf.trace_enabled = False
    made = []
    real_init = trace._Span.__init__

    def counting(self, *a, **k):
        made.append(a)
        real_init(self, *a, **k)

    monkeypatch.setattr(trace._Span, "__init__", counting)
    monkeypatch.setattr(
        jax.profiler, "TraceAnnotation",
        lambda *a, **k: pytest.fail("an annotation with tracing off"))
    before = len(trace.TRACE)
    info, wrong, _ = _run_q03(bench, tables, 11)
    assert wrong is None and info["mesh_devices"] == 4
    assert made == [] and len(trace.TRACE) == before


# (g): the three readers, on recorded runs
def _reader(name: str):
    return _load(f"metrics/{name}.py").read


def _span(transport, dur=0, **attrs):
    return {"kind": "exchange", "dur": dur,
            "attrs": {"transport": transport, **attrs}}


def _run(*queries, reduction=None):
    return {"window": [{"seconds": 1.0, "spans": q, "query": "q"}
                       for q in queries[:-1]],
            "profiled": [{"seconds": 1.0, "spans": queries[-1],
                          "query": "q"}] if queries else [],
            "reduction": reduction}


STAGE_A = [_span("mesh", 200_000_000, host_bytes=0),
           _span("mesh", 100_000_000, host_bytes=0),
           _span("unshard", 50_000_000, host_bytes=60_000_000),
           _span("unshard", 50_000_000, host_bytes=90_000_000),
           {"kind": "stage", "dur": 10 ** 9, "attrs": {"transport": "mesh"}}]
STAGE_B = [_span("mesh", 150_000_000, host_bytes=0),
           _span("place", 1_000_000, host_bytes=0, device=2, partition=2)]
ONE_CHIP = [_span("local", 300_000_000)]
PARENT = [_span("mesh", 200_000_000), _span("unshard", 50_000_000)]


@pytest.mark.parametrize("name,run,want", [
    ("mesh_exchange_s", _run(STAGE_A, STAGE_A, STAGE_A), 0.3),
    ("mesh_exchange_s", _run(STAGE_B, STAGE_A, STAGE_B), 0.15),
    ("mesh_exchange_s", _run(ONE_CHIP, ONE_CHIP), None),
    ("mesh_exchange_s", _run(None, None), None),        # tracing off
    ("mesh_host_roundtrip_MB", _run(STAGE_A, STAGE_A), 150.0),
    # a mesh exchange that moved nothing through the host reads 0, not None
    ("mesh_host_roundtrip_MB", _run(STAGE_B, STAGE_B, STAGE_B), 0.0),
    ("mesh_host_roundtrip_MB", _run(STAGE_B, STAGE_A, STAGE_B), 0.0),
    ("mesh_host_roundtrip_MB", _run(ONE_CHIP, ONE_CHIP), None),
    # a program whose spans carry no such counter: nothing to read
    ("mesh_host_roundtrip_MB", _run(PARENT, PARENT), None),
    ("mesh_host_roundtrip_MB", _run(None), None),
    ("chip_busy_balance", _run(STAGE_B, reduction={"per_device": [
        {"device": 0, "busy_s": 4.0}, {"device": 1, "busy_s": 1.0},
        {"device": 2, "busy_s": 2.0}, {"device": 3, "busy_s": 3.0}]}), 25.0),
    ("chip_busy_balance", _run(STAGE_B, reduction={"per_device": [
        {"device": 0, "busy_s": 2.5}, {"device": 1, "busy_s": 0.0}]}), 0.0),
    ("chip_busy_balance", _run(STAGE_B, reduction={"per_device": [
        {"device": 0, "busy_s": 1.5}]}), 100.0),
    ("chip_busy_balance", _run(STAGE_B, reduction=None), None),
    ("chip_busy_balance", _run(STAGE_B, reduction={"per_device": [
        {"device": 0, "busy_s": 0.0}, {"device": 1, "busy_s": 0.0}]}), None),
])
def test_readers_on_recorded_runs(name, run, want):
    got = _reader(name)(run)
    assert got == want if want is None else got == pytest.approx(want)
    if name == "chip_busy_balance" and got is not None:
        assert 0.0 <= got <= 100.0


def test_manifest_lists_the_cell_its_config_and_its_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    # by name: later PRs append cells and configurations to the lists
    (cell,) = [c for c in manifest["workloads"]
               if c["name"] == "sf1_q03_nobhj_x4"]
    assert cell == {**cell, "chips": 4, "config": "tpcds_sf1_nobhj_x4",
                    "traffic": "q03_loop1"}
    (config,) = [c for c in manifest["configs"]
                 if c["name"] == cell["config"]]
    with open(os.path.join(REPO, config["file"])) as fh:
        on_file = json.load(fh)
    assert config["name"] == on_file["name"] == "tpcds_sf1_nobhj_x4"
    assert config["source"] == on_file["source"]
    assert sorted(config["reduced"]) == sorted(on_file["reduced"])
    # the cell's own three metrics, by name: later PRs append to the list
    own = [m for m in manifest["per_layer"] if m["name"] in (
        "mesh_exchange_s", "mesh_host_roundtrip_MB", "chip_busy_balance")]
    assert len(own) == 3
    for m in own:
        assert m["workloads"] == ["sf1_q03_nobhj_x4"]
        assert m["moves"] == "query_s.p50"
        assert os.path.exists(
            os.path.join(BENCH, "metrics", m["name"] + ".py"))
    four = [c for c in manifest["workloads"] if c["chips"] == 4]
    assert len(four) == 1
