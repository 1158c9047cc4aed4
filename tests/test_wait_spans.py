"""The control pulls' seam (columnar.batch.pull_rows / pull_array) and the
`wait` span it records: what it returns, what it costs off, what a span
carries on, where the sites live in the sources, the operator tap taking
the rows a producer has pulled, explain_analyze's stage lines and the stage
account of tools/wait_account.py."""

import ast
import importlib.util
import os
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import ColumnBatch, pull_array, pull_rows
from blaze_tpu.config import conf
from blaze_tpu.ops.base import batch_tap, count_stream
from blaze_tpu.runtime import trace
from blaze_tpu.runtime.metrics import MetricsSet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEAM = os.path.join("blaze_tpu", "columnar", "batch.py")


@pytest.fixture(autouse=True)
def _clean():
    saved = conf.trace_enabled
    trace.reset()
    yield
    conf.trace_enabled = saved
    trace.reset()


def _frame(**cols):
    schema = T.Schema([T.Field(k, T.INT64) for k in cols])
    return ColumnBatch.from_numpy(
        {k: np.asarray(v, np.int64) for k, v in cols.items()}, schema)


def _batch(n=3):
    return _frame(a=np.arange(n))


def _waits():
    return [r for r in trace.TRACE.snapshot()
            if r["type"] == "span" and r["kind"] == "wait"]


# -- what the seam returns, and what it costs off ----------------------------


@pytest.mark.parametrize("on", [False, True], ids=["off", "on"])
def test_pull_rows_is_int_of_num_rows(on):
    conf.trace_enabled = on
    b = _batch(5)
    got = pull_rows(b, "test.rows")
    assert got == int(b.num_rows) == 5 and type(got) is int


@pytest.mark.parametrize("on", [False, True], ids=["off", "on"])
def test_pull_array_is_np_asarray(on):
    conf.trace_enabled = on
    x = jnp.arange(6, dtype=jnp.int32).reshape(2, 3) * 7
    got = pull_array(x, "test.array")
    assert isinstance(got, np.ndarray) and got.dtype == np.int32
    np.testing.assert_array_equal(got, np.asarray(x))
    assert int(pull_array(jnp.sum(x), "test.total")) == int(jnp.sum(x))


def test_off_the_ring_stays_empty_and_no_span_object_is_built(monkeypatch):
    conf.trace_enabled = False
    made = []
    real_init = trace._Span.__init__

    def counting(self, *a, **k):
        made.append(a)
        real_init(self, *a, **k)

    monkeypatch.setattr(trace._Span, "__init__", counting)

    class Spy:  # is_ready must not be asked with tracing off
        def is_ready(self):
            raise AssertionError("is_ready read with tracing off")

        def __array__(self, dtype=None, copy=None):
            return np.arange(3)

    b = _batch(4)
    for _ in range(3):
        assert pull_rows(b, "test.rows") == 4
    np.testing.assert_array_equal(pull_array(Spy(), "test.array"),
                                  np.arange(3))
    assert made == [] and len(trace.TRACE) == 0


# -- what a span carries on --------------------------------------------------


def test_one_wait_span_per_device_pull_with_site_ready_parent_and_stage():
    conf.trace_enabled = True
    b = _batch(4)
    x = jnp.arange(5)
    with trace.span("query", query_id="qw"):
        with trace.span("stage", stage_id=3, stage_kind="shuffle_map") as st:
            pull_rows(b, "test.rows")
            with trace.span("exchange", transport="local") as ex:
                pull_array(x, "test.bounds")
    waits = _waits()
    assert [w["attrs"]["site"] for w in waits] == ["test.rows", "test.bounds"]
    assert all(type(w["attrs"]["ready"]) is bool for w in waits)
    assert [w["parent"] for w in waits] == [st.id, ex.id]
    assert all(w["stage_id"] == 3 and w["query_id"] == "qw" for w in waits)
    assert all(w["thread"] == threading.current_thread().name and
               w["dur"] >= 0 and "id" in w for w in waits)


def test_ready_says_whether_the_device_had_finished_when_the_host_asked():
    conf.trace_enabled = True

    class Pending:
        def __init__(self, ready):
            self.ready = ready

        def is_ready(self):
            return self.ready

        def __array__(self, dtype=None, copy=None):
            return np.asarray(11)

    assert int(pull_array(Pending(False), "test.blocked")) == 11
    assert int(pull_array(Pending(True), "test.there")) == 11
    done = jnp.arange(4).block_until_ready()
    pull_array(done, "test.done")
    assert [(w["attrs"]["site"], w["attrs"]["ready"]) for w in _waits()] == [
        ("test.blocked", False), ("test.there", True), ("test.done", True)]


def test_a_host_number_opens_no_span():
    conf.trace_enabled = True
    b = _batch(2)
    on_host = ColumnBatch(b.schema, b.columns, np.int32(2), b.capacity)
    assert pull_rows(on_host, "test.rows") == 2
    assert pull_rows(ColumnBatch(b.schema, b.columns, 2, b.capacity),
                     "test.rows") == 2
    np.testing.assert_array_equal(
        pull_array(np.arange(3), "test.array"), np.arange(3))
    assert int(pull_array(7, "test.array")) == 7
    assert _waits() == []


def test_a_wait_on_a_pool_thread_names_its_parent_through_the_context():
    conf.trace_enabled = True
    b = _batch(3)
    with trace.span("stage", stage_id=9, stage_kind="shuffle_map"):
        with trace.span("task_attempt", task_id="t1") as task:
            snap = trace.current_context()

            def work():
                with trace.context(**snap):
                    pull_rows(b, "test.rows")

            t = threading.Thread(target=work, name="pool-thread-7")
            t.start()
            t.join()
    (w,) = _waits()
    assert w["parent"] == task.id and w["stage_id"] == 9
    assert w["task_id"] == "t1" and w["thread"] == "pool-thread-7"


def test_the_profiler_annotation_of_a_wait_carries_its_site(monkeypatch):
    entered = []

    class Ann:
        def __init__(self, name, **kw):
            entered.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    conf.trace_enabled = True
    with trace.span("exchange", transport="local", rows=5, site_like="no"):
        pull_rows(_batch(1), "test.rows")
    (ex, ex_kw), (wait, wait_kw) = entered
    assert ex == "blaze:exchange" and set(ex_kw) == {"span_id"}
    assert wait == "blaze:wait" and wait_kw["site"] == "test.rows"
    assert set(wait_kw) == {"span_id", "site"}    # site, and no other attr


def test_a_jax_profiler_trace_holds_blaze_wait_events_with_their_site(
        tmp_path):
    from jax.profiler import ProfileData

    spec = importlib.util.spec_from_file_location(
        "ws_trace_reduce", os.path.join(REPO, "benchmarks", "trace_reduce.py"))
    reduce_ = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reduce_)
    conf.trace_enabled = True
    b = _batch(3)
    with jax.profiler.trace(str(tmp_path),
                            profiler_options=reduce_.profile_options()):
        with trace.span("stage", stage_id=1, stage_kind="shuffle_map"):
            pull_rows(b, "test.profiled_rows")
    data = ProfileData.from_file(reduce_.find_xplane(str(tmp_path)))
    sites = [dict(ev.stats).get("site") for plane in data.planes
             if plane.name == reduce_.HOST_PLANE for line in plane.lines
             for ev in line.events if ev.name == "blaze:wait"]
    assert sites == ["test.profiled_rows"]


# -- the tap takes the rows a producer has already pulled --------------------


class _Op:
    children = ()

    def __init__(self):
        self.metrics = MetricsSet()

    def name(self):
        return "TestOp"


def test_the_tap_takes_rows_from_the_producer_and_pulls_nothing_more():
    conf.trace_enabled = True
    op, b = _Op(), _batch(4)

    def producer():
        note = batch_tap(op)
        rows = pull_rows(b, "test.out_rows")   # the one pull of this batch
        note(b, rows)
        yield b

    assert list(producer()) == [b]
    assert op.metrics.snapshot()["output_rows"] == 4
    assert [w["attrs"]["site"] for w in _waits()] == ["test.out_rows"]
    trace.reset()
    assert list(count_stream(op, iter([b]))) == [b]   # a plain batch: pulled
    assert [w["attrs"]["site"] for w in _waits()] == ["op.output_rows"]
    assert op.metrics.snapshot()["output_rows"] == 8


def test_a_join_output_batch_is_one_wait_span():
    """The duplicate that went: HashJoinLikeExec pulled a joined batch's rows
    to drop an empty one and count_stream pulled them again."""
    from blaze_tpu.ops.basic import MemorySourceExec
    from blaze_tpu.ops.base import ExecContext
    from blaze_tpu.ops.join import BroadcastJoinExec, JoinKey, JoinType

    left = _frame(k=[1, 2, 3, 4], v=[10, 20, 30, 40])
    right = _frame(k2=[2, 4, 6])
    join = BroadcastJoinExec(MemorySourceExec([left], left.schema),
                             MemorySourceExec([right], right.schema),
                             [JoinKey(0, 0)], JoinType.INNER,
                             build_is_left=False)
    conf.trace_enabled = True
    out = list(join.execute(ExecContext(partition=0, num_partitions=1)))
    assert [type(b) for b in out] == [ColumnBatch]
    assert sum(int(b.num_rows) for b in out) == 2
    sites = [w["attrs"]["site"] for w in _waits()]
    assert sites.count("join.out_rows") == 1
    assert join.metrics.snapshot()["output_rows"] == 2
    # the taps of the two sources pulled theirs; the join's tap did not
    assert sites.count("op.output_rows") == 2


def _self_tapped(kind):
    """(operator, its output rows, the site its one pull a batch carries)."""
    from blaze_tpu.exprs import ir
    from blaze_tpu.ops.agg import AggCall, AggExec, AggMode
    from blaze_tpu.ops.basic import (
        CoalesceBatchesExec, LocalLimitExec, MemorySourceExec,
    )
    from blaze_tpu.ops.join import BroadcastNestedLoopJoinExec, JoinType

    left = _frame(k=[1, 2, 3, 4], v=[10, 20, 30, 40])
    src = MemorySourceExec([left], left.schema)
    if kind == "limit":
        return LocalLimitExec(src, 10), 4, "limit.input_rows"
    if kind == "coalesce":
        return CoalesceBatchesExec(src, 2), 4, "coalesce.input_rows"
    if kind == "agg":
        calls = [AggCall("sum", (ir.col("v"),), T.INT64, "sum_v")]
        return (AggExec(src, [ir.col("k")], ["k"], calls, AggMode.PARTIAL),
                4, "agg.out_rows")
    right = _frame(k2=[2, 4, 6])
    return (BroadcastNestedLoopJoinExec(
        src, MemorySourceExec([right], right.schema), JoinType.INNER),
        12, "nlj.out_rows")


@pytest.mark.parametrize("kind", ["limit", "coalesce", "agg", "nlj"])
def test_an_operator_that_feeds_its_own_tap_yields_batches_pulled_once(kind):
    from blaze_tpu.ops.base import ExecContext

    op, rows, site = _self_tapped(kind)
    conf.trace_enabled = True
    out = list(op.execute(ExecContext(partition=0, num_partitions=1)))
    assert out and {type(b) for b in out} == {ColumnBatch}
    snap = op.metrics.snapshot()
    assert (snap["output_rows"], snap["output_batches"]) == (rows, len(out))
    sites = [w["attrs"]["site"] for w in _waits()]
    assert sites.count(site) == len(out)
    # the sources' taps pulled theirs; this operator's tap did not
    assert sites.count("op.output_rows") == len(op.children)


# -- the sources: one seam, static sites, the list in PERF.md ----------------


def _py_files():
    for root, _, files in os.walk(os.path.join(REPO, "blaze_tpu")):
        for f in files:
            if f.endswith(".py"):
                yield os.path.join(root, f)


def _sites_in_sources():
    """{site: {relative file, ...}} of every pull_rows / pull_array call;
    asserts the site is a string literal."""
    found = {}
    for path in _py_files():
        rel = os.path.relpath(path, REPO)
        with open(path) as fh:
            tree = ast.parse(fh.read())
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Name) and node.func.id in (
                        "pull_rows", "pull_array")):
                continue
            site = node.args[1] if len(node.args) > 1 else None
            if rel == SEAM and isinstance(site, ast.Name):
                continue        # the seam hands its caller's on
            assert isinstance(site, ast.Constant) and isinstance(
                site.value, str), f"{rel}:{node.lineno}: site is no literal"
            found.setdefault(site.value, set()).add(rel)
    return found


def test_no_row_count_pull_outside_the_seam():
    pat = re.compile(r"int\([^)]*num_rows\)")
    hits = []
    for path in _py_files():
        with open(path) as fh:
            for i, line in enumerate(fh, 1):
                if pat.search(line):
                    hits.append((os.path.relpath(path, REPO), line.strip()))
    assert hits == [(SEAM, "return int(batch.num_rows)")]


def test_the_exchange_pulls_bounds_overflow_and_counts_through_the_seam():
    with open(os.path.join(REPO, "blaze_tpu", "parallel",
                           "stage_exchange.py")) as fh:
        src = fh.read()
    for name in ("bounds", "overflow", "out_counts"):
        assert not re.search(r"np\.asarray\(\s*%s\s*\)" % name, src)
    for site in ("exchange.local_bounds", "exchange.mesh_overflow",
                 "exchange.mesh_counts", "exchange.map_rows"):
        assert f'"{site}"' in src


# a site's first word is its layer: where its callers may live
LAYER_FILES = {
    "agg": {"blaze_tpu/ops/agg.py"},
    "coalesce": {"blaze_tpu/ops/basic.py"},
    "collect": {"blaze_tpu/spark/local_runner.py"},
    "concat": {"blaze_tpu/ops/common.py"},
    "d2h": {"blaze_tpu/columnar/serde.py", "blaze_tpu/columnar/batch.py",
            "blaze_tpu/columnar/arrow_io.py"},
    "debug": {"blaze_tpu/ops/basic.py"},
    "exchange": {"blaze_tpu/parallel/stage_exchange.py"},
    "expand": {"blaze_tpu/ops/expand.py"},
    "ipc": {"blaze_tpu/ops/shuffle.py"},
    "join": {"blaze_tpu/ops/join.py"},
    "limit": {"blaze_tpu/ops/basic.py"},
    "nlj": {"blaze_tpu/ops/join.py"},
    "op": {"blaze_tpu/ops/base.py"},
    "parquet_sink": {"blaze_tpu/ops/parquet.py"},
    "shuffle": {"blaze_tpu/ops/shuffle.py"},
    "sort": {"blaze_tpu/ops/sort.py"},
    "stage": {"blaze_tpu/runtime/stage_compiler.py"},
    "window": {"blaze_tpu/ops/window.py"},
}


def test_every_site_is_a_literal_of_its_layer_and_listed_in_perf_md():
    found = _sites_in_sources()
    for site, files in found.items():
        layer, _, purpose = site.partition(".")
        assert purpose and re.fullmatch(r"[a-z0-9_]+\.[a-z0-9_]+", site), site
        assert files <= LAYER_FILES[layer], (site, files)
    with open(os.path.join(REPO, "PERF.md")) as fh:
        perf = fh.read()
    listed = perf[perf.index("Wait sites ("):]
    listed = listed[:listed.index("\n\n")]
    assert set(re.findall(r"`([a-z0-9_]+\.[a-z0-9_]+)`", listed)) == set(found)


def test_wait_is_a_registered_span_kind_and_the_registries_are_in_sync():
    from pathlib import Path

    from tools.blazelint.core import run_checkers
    from tools.blazelint.registry_sync import RegistrySync

    assert "wait" in trace.SPAN_KINDS
    result = run_checkers(Path(REPO), ["blaze_tpu"], [RegistrySync()])
    assert [f.render() for f in result.findings
            if f.severity == "error"] == []
    assert not any("wait" in f.render() for f in result.findings)


# -- explain_analyze and the stage account -----------------------------------


def test_explain_analyze_stage_lines_carry_the_stages_waits():
    conf.trace_enabled = True
    ticks = iter(range(0, 10 ** 12, 10 ** 7))     # every clock read: +10 ms
    log = trace.TRACE
    real = log.clock
    log.clock = lambda: next(ticks)
    try:
        with trace.span("query", query_id="qe"):
            with trace.span("stage", stage_id=4, stage_kind="shuffle_map"):
                with trace.span("wait", site="test.a", ready=False):
                    pass
                with trace.span("wait", site="test.b", ready=True):
                    pass
                with trace.span("wait", site="test.a", ready=False):
                    pass
            with trace.span("stage", stage_id=5, stage_kind="result"):
                pass
        with trace.span("query", query_id="other"):
            with trace.span("stage", stage_id=4, stage_kind="shuffle_map"):
                with trace.span("wait", site="test.a", ready=True):
                    pass
    finally:
        log.clock = real
    text = trace.explain_analyze(_Op(), records=trace.query_records("qe"))
    (line4,) = [ln for ln in text.splitlines() if ln.startswith("  stage 4")]
    (line5,) = [ln for ln in text.splitlines() if ln.startswith("  stage 5")]
    assert "wait 0.03 s in 3 pulls (2 blocking)" in line4
    assert "wait" not in line5
    # the whole ring: each query's stage 4 keeps its own waits
    both = [ln for ln in trace.explain_analyze(_Op()).splitlines()
            if ln.startswith("  stage 4")]
    assert ["3 pulls (2 blocking)" in ln for ln in both] == [True, False]
    assert "1 pulls (0 blocking)" in both[1]


def _account():
    spec = importlib.util.spec_from_file_location(
        "ws_wait_account", os.path.join(REPO, "tools", "wait_account.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _span(kind, ts, dur, sid=1, thread="main", **attrs):
    _span.n += 1
    return {"type": "span", "kind": kind, "id": _span.n, "ts": ts,
            "dur": dur, "stage_id": sid, "thread": thread, "attrs": attrs}


_span.n = 0


def test_the_stage_account_counts_every_instant_once():
    spans = [
        _span("stage", 0, 1000, stage_kind="shuffle_map"),
        _span("dispatch", 100, 50, program="fused"),
        _span("exchange", 200, 400, transport="local"),
        _span("dispatch", 210, 40, program="local_xchg"),   # in the exchange
        _span("wait", 260, 300, site="exchange.local_bounds", ready=False),
        _span("wait", 700, 100, site="op.output_rows", ready=True),
        _span("wait", 720, 50, thread="pool", site="x.y", ready=False),
        _span("wait", 900, 500, site="join.out_rows", ready=False),  # clipped
        _span("dispatch", 10, 20, sid=2, program="other_stage"),
        _span("stage", 2000, 100, sid=2, stage_kind="result"),
    ]
    (st,) = _account().stage_account(spans)
    ns = {k: round(v * 1e9) for k, v in st["parts_s"].items()}
    assert ns == {"dispatch": 90, "exchange": 60, "wait_blocked": 400,
                  "wait_ready": 100, "self": 350}
    assert sum(ns.values()) == 1000
    assert (st["pulls"], st["blocking"], st["dispatches"]) == (3, 2, 2)
    assert st["programs"] == {"fused": 1, "local_xchg": 1}
    assert [s[0] for s in st["sites"]] == [
        "join.out_rows", "exchange.local_bounds", "op.output_rows"]
    # the reader is the definition of what covers a stage; the tool's
    # `self` is the same 35 %
    spec = importlib.util.spec_from_file_location(
        "ws_stage_self_share", os.path.join(
            REPO, "benchmarks", "metrics", "stage_self_share.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    run = {"window": [{"spans": spans}], "profiled": []}
    assert reader.read(run) == pytest.approx(100.0 * ns["self"] / 1000)
