"""Span ids and parents, program names by kind, the spans where the work
happens (plan / scan_decode / h2d / dispatch / exchange / d2h / collect), the
profiler annotations beside them, and the benchmark's six span readers
(benchmarks/metrics/*.py) on hand-built runs."""

import importlib.util
import os
import threading

import jax
import jax.numpy as jnp
import pytest

from blaze_tpu.config import conf
from blaze_tpu.runtime import jit_cache, pipeline, trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    saved = {k: getattr(conf, k) for k in ("trace_enabled", "profiler_dir")}
    trace.reset()
    yield
    for k, v in saved.items():
        setattr(conf, k, v)
    trace.reset()


def _spans(kind=None):
    return [r for r in trace.TRACE.snapshot() if r["type"] == "span"
            and (kind is None or r["kind"] == kind)]


# ---------------------------------------------------------------------------
# id / parent
# ---------------------------------------------------------------------------


def test_span_ids_unique_and_parent_is_innermost_open_span():
    conf.trace_enabled = True
    with trace.span("query", query_id="qN"):
        with trace.span("stage", stage_id=1):
            with trace.span("task_attempt", task_id="t"):
                trace.event("retry", n=1)
        with trace.span("stage", stage_id=2):
            pass
    recs = trace.TRACE.snapshot()
    query = next(r for r in recs if r["kind"] == "query")
    s1, s2 = [r for r in recs if r["kind"] == "stage"]
    task = next(r for r in recs if r["kind"] == "task_attempt")
    retry = next(r for r in recs if r["kind"] == "retry")
    ids = [r["id"] for r in (query, s1, s2, task)]
    assert len(set(ids)) == 4 and all(isinstance(i, int) for i in ids)
    assert query["parent"] is None
    assert s1["parent"] == query["id"] and s2["parent"] == query["id"]
    assert task["parent"] == s1["id"]
    # events name the span they happened in
    assert retry["parent"] == task["id"]
    # existing fields stay
    assert {"ts", "dur", "wall", "thread", "query_id"} <= set(s1)


def test_parent_travels_with_context_to_prefetch_producer_thread():
    """A span opened on a prefetch producer thread has the query's id and
    the span that built the stream as parent (pipeline._CtxSnapshot replays
    trace.current_context(), which carries `parent`)."""
    conf.trace_enabled = True
    seen_threads = []

    def gen():
        seen_threads.append(threading.current_thread().name)
        with trace.span("scan_decode", file="f"):
            pass
        yield 1

    with trace.span("query", query_id="qP"):
        with trace.span("stage", stage_id=7):
            assert list(pipeline.prefetch(gen(), 2)) == [1]
    stage = _spans("stage")[0]
    decode = _spans("scan_decode")[0]
    assert decode["query_id"] == "qP" and decode["stage_id"] == 7
    assert decode["parent"] == stage["id"]
    assert decode["thread"] == seen_threads[0]
    assert decode["thread"] != stage["thread"]


def test_parent_travels_through_explicit_context_handoff():
    conf.trace_enabled = True
    with trace.span("query", query_id="qH") as q:
        snap = trace.current_context()

    def work():
        with trace.context(**snap):
            with trace.span("task_attempt", task_id="t0"):
                pass

    t = threading.Thread(target=work)
    t.start()
    t.join()
    task = _spans("task_attempt")[0]
    assert task["parent"] == q.id and task["query_id"] == "qH"


# ---------------------------------------------------------------------------
# off path
# ---------------------------------------------------------------------------


def test_off_path_shared_null_span_and_no_span_object_per_dispatch(
        monkeypatch):
    conf.trace_enabled = False
    assert trace.span("h2d", rows=1) is trace._NULL_SPAN
    assert trace.span("query", query_id="q") is trace._NULL_SPAN
    made = []
    real_init = trace._Span.__init__

    def counting(self, *a, **k):
        made.append(a)
        real_init(self, *a, **k)

    monkeypatch.setattr(trace._Span, "__init__", counting)
    fn = jit_cache.get_or_compile(("test_off_kind", 1),
                                  lambda: (lambda x: x + 1))
    for _ in range(5):
        fn(jnp.arange(4))
    assert made == [] and len(trace.TRACE) == 0


def test_annotations_made_only_when_tracing_is_on(monkeypatch):
    entered, exited = [], []

    class Ann:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            entered.append((self.name, self.kw))

        def __exit__(self, *exc):
            exited.append(self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    conf.trace_enabled = False
    with trace.span("query", query_id="q0"):
        with trace.span("h2d", rows=3):
            pass
    assert entered == [] and exited == []
    conf.trace_enabled = True
    with trace.span("query", query_id="q1"):
        with trace.span("h2d", rows=3) as sp:
            pass
    assert [n for n, _ in entered] == ["blaze:query", "blaze:h2d"]
    assert exited == ["blaze:h2d", "blaze:query"]
    assert entered[0][1]["query_id"] == "q1"
    assert entered[1][1]["span_id"] == sp.id


def test_clock_anchor_recorded_once(monkeypatch):
    conf.trace_enabled = False
    trace.anchor_clock()
    assert len(trace.TRACE) == 0       # off: nothing
    conf.trace_enabled = True
    trace.anchor_clock()
    trace.anchor_clock()
    anchors = [r for r in trace.TRACE.snapshot()
               if r["kind"] == "clock_anchor"]
    assert len(anchors) == 1
    assert anchors[0]["ts"] > 0 and anchors[0]["wall"] > 0
    assert "query_id" not in anchors[0]


# ---------------------------------------------------------------------------
# programs named by kind
# ---------------------------------------------------------------------------


def _module_name(key):
    """The HLO module name of the program cached under `key`."""
    jitted = jit_cache._retry[key][0]
    text = jitted.lower(jnp.arange(4)).as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


@pytest.mark.parametrize("key,want", [
    (("join_match", "plan", 3), "jit_join_match"),
    (("agg_collapse", True), "jit_agg_collapse"),
    (("local_xchg", 4, (0,)), "jit_local_xchg"),
    (12345, "jit_other"),
    ((7, "not_first"), "jit_other"),
    ("a_string_key", "jit_other"),
])
def test_jit_name_is_the_keys_kind(key, want):
    def run(x):
        return x * 2

    fn = jit_cache.get_or_compile(key, lambda: run)
    assert int(fn(jnp.arange(4))[3]) == 6
    assert _module_name(key) == want
    assert jit_cache.kind_of(key) == want[len("jit_"):]
    # a shared function is wrapped, never renamed in place
    assert run.__name__ == "run"


def test_jit_name_may_say_more_and_is_cut_to_a_fixed_length():
    key = ("fused", True, "k1")
    jit_cache.get_or_compile(key, lambda: (lambda x: x),
                             name="fused.filter.project")(jnp.arange(4))
    assert _module_name(key) == "jit_fused.filter.project"
    key2 = ("fused", True, "k2")
    jit_cache.get_or_compile(key2, lambda: (lambda x: x),
                             name="fused." + "project." * 40)(jnp.arange(4))
    assert len(_module_name(key2)) == len("jit_") + jit_cache._NAME_MAX


def test_static_and_donate_kwargs_keep_working_through_the_wrapper():
    def make():
        def run(x, n, scale=1):
            return x * n * scale
        return run

    fn = jit_cache.get_or_compile(("kw_kind", 1), make,
                                  static_argnames=("n", "scale"))
    assert int(fn(jnp.arange(4), n=3, scale=2)[1]) == 6
    fn2 = jit_cache.get_or_compile(("kw_kind", 2), make,
                                   static_argnums=(1,), donate_argnums=(0,))
    assert int(fn2(jnp.arange(4), 5)[1]) == 5


def test_stale_exec_rebuild_keeps_the_name():
    key = ("join_match", "stale")
    fn = jit_cache.get_or_compile(key, lambda: (lambda x: x + 1))
    fn(jnp.arange(4))
    holder = jit_cache._retry[key]
    good = holder[0]

    def stale(*a, **k):
        raise ValueError("Execution supplied 3 buffers but compiled "
                         "program expected 4 buffers")

    holder[0] = stale
    before = jit_cache.stats().get("stale_exec_rebuilds", 0)
    assert int(fn(jnp.arange(4))[0]) == 1
    assert jit_cache.stats()["stale_exec_rebuilds"] == before + 1
    assert holder[0] is not stale and holder[0] is not good
    assert _module_name(key) == "jit_join_match"


def test_dispatch_span_marks_first_call_once():
    conf.trace_enabled = True
    fn = jit_cache.get_or_compile(("dispatch_kind", 1),
                                  lambda: (lambda x: x + 1))
    with trace.span("query", query_id="qD") as q:
        for _ in range(3):
            fn(jnp.arange(4))
    spans = _spans("dispatch")
    assert len(spans) == 3
    assert [bool(s["attrs"].get("first_call")) for s in spans] == \
        [True, False, False]
    assert all(s["attrs"]["program"] == "dispatch_kind" for s in spans)
    assert all(s["query_id"] == "qD" and s["parent"] == q.id for s in spans)


def test_fused_chain_program_carries_operator_names():
    import numpy as np

    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import ColumnBatch
    from blaze_tpu.columnar.types import Field, Schema
    from blaze_tpu.exprs import ir
    from blaze_tpu.ops.base import ExecContext
    from blaze_tpu.ops.basic import FilterExec, MemorySourceExec, ProjectExec
    from blaze_tpu.runtime.executor import execute_plan

    schema = Schema([Field("a", T.INT32)])
    batch = ColumnBatch.from_numpy({"a": np.arange(8, dtype=np.int32)},
                                   schema)
    src = MemorySourceExec([batch], schema)
    flt = FilterExec(src, [ir.Binary(ir.BinOp.GT, ir.col("a"),
                                     ir.Literal(T.INT32, 2))])
    op = ProjectExec(flt, [ir.col("a")], ["a"])
    conf.trace_enabled = True
    out = list(execute_plan(op, ExecContext()))
    assert int(out[0].num_rows) == 5
    fused = [k for k in jit_cache._retry
             if isinstance(k, tuple) and k[0] == "fused"
             and "('mem', ('a',))" in repr(k)]
    assert fused
    jitted = jit_cache._retry[fused[-1]][0]
    text = jitted.lower(batch).as_text(debug_info=True)
    assert "module @jit_fused.filter.project" in text
    # one named scope per operator of the chain
    assert "filter" in text and "project" in text
    assert any(s["attrs"]["program"] == "fused" for s in _spans("dispatch"))


def test_named_scopes_inside_match_ranges_and_collapse_sort():
    import numpy as np

    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import ColumnBatch
    from blaze_tpu.columnar.types import Field, Schema
    from blaze_tpu.ops.join import match_ranges
    from blaze_tpu.ops.sort_keys import SortSpec, sort_batch

    schema = Schema([Field("k", T.INT64)])
    b = ColumnBatch.from_numpy({"k": np.arange(16, dtype=np.int64)}, schema)

    def match(build, probe):
        return match_ranges(build, probe, [0], [0], [False], [False])

    text = jax.jit(match).lower(b, b).as_text(debug_info=True)
    for scope in ("match.keys", "match.merge_sort", "match.run_starts",
                  "match.run_cumsums", "match.run_scans",
                  "match.to_probe_order", "match.to_build_order"):
        assert scope in text, scope
    text = jax.jit(lambda x: sort_batch(x, [SortSpec(0)])).lower(b).as_text(
        debug_info=True)
    for scope in ("sort.encode_keys", "sort.sort", "sort.permute"):
        assert scope in text, scope


# ---------------------------------------------------------------------------
# the spans of a small run_plan
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    from blaze_tpu.spark import validator

    d = str(tmp_path_factory.mktemp("span_tables"))
    return validator.generate_tables(d, rows=3000)


@pytest.mark.parametrize("query,mode,mesh", [
    ("q2_q06_core_agg", "bhj", "auto"),
    ("q3_join_agg_sort", "smj", "auto"),
    ("q1_scan_filter_project", "bhj", "off"),
])
def test_run_plan_spans_carry_the_query_id(tables, tmp_path, query, mode,
                                           mesh):
    from blaze_tpu.spark import validator
    from blaze_tpu.spark.local_runner import run_plan

    paths, frames = tables
    plan, oracle = validator.QUERIES[query](paths, frames, mode)
    conf.trace_enabled = True
    info = {}
    out = run_plan(plan, num_partitions=4, work_dir=str(tmp_path),
                   mesh_exchange=mesh, run_info=info)
    qid = info["query_id"]
    assert out._query_id == qid
    got = out.to_numpy()          # the caller's last pull, context popped
    assert trace.current_context() == {}
    assert len(next(iter(got.values()))) == len(oracle())
    recs = trace.query_records(qid)
    spans = [r for r in recs if r["type"] == "span"]
    kinds = {s["kind"] for s in spans}
    assert {"query", "plan", "stage", "scan_decode", "h2d", "dispatch",
            "collect"} <= kinds
    if mesh == "auto":
        assert "exchange" in kinds
    # every span record has id and parent; ids are unique
    assert all("id" in s and "parent" in s for s in spans)
    assert len({s["id"] for s in spans}) == len(spans)
    query_span = next(s for s in spans if s["kind"] == "query")
    plan_span = next(s for s in spans if s["kind"] == "plan")
    assert plan_span["parent"] == query_span["id"]
    assert plan_span["attrs"]["stages"] >= 1
    for s in spans:
        if s["kind"] == "stage":
            assert s["parent"] == query_span["id"]
    collect = next(s for s in spans if s["kind"] == "collect")
    stage_ids = {s["id"] for s in spans if s["kind"] == "stage"}
    assert collect["parent"] in stage_ids
    assert collect["attrs"]["partitions"] >= 1
    # scan spans carry the counts of their boundary, from the host's side
    decodes = [s for s in spans if s["kind"] == "scan_decode"]
    assert sum(s["attrs"].get("rows", 0) for s in decodes) >= 3000
    assert all(s["attrs"]["file"] for s in decodes)
    uploads = [s for s in spans if s["kind"] == "h2d"]
    assert all(s["attrs"]["bytes"] > 0 for s in uploads
               if s["attrs"].get("what") == "scan")
    # the final pull: either the host-sorted collect left the result on the
    # host (no pull, no span), or to_numpy recorded a d2h marked final
    finals = [s for s in spans if s["kind"] == "d2h"
              and s["attrs"].get("final")]
    if collect["attrs"]["host_sorted"]:
        assert finals == []
        assert any(s["kind"] == "d2h" and s["attrs"]["what"] == "to_host"
                   for s in spans)
    else:
        assert len(finals) == 1 and finals[0]["attrs"]["what"] == "to_numpy"
        assert finals[0]["ts"] >= query_span["ts"] + query_span["dur"]
    # one clock anchor per process, outside every query
    assert sum(r["kind"] == "clock_anchor"
               for r in trace.TRACE.snapshot()) == 1


def test_to_numpy_of_an_untagged_batch_is_a_plain_d2h():
    import numpy as np

    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import ColumnBatch
    from blaze_tpu.columnar.types import Field, Schema

    schema = Schema([Field("a", T.INT32)])
    b = ColumnBatch.from_numpy({"a": np.arange(5, dtype=np.int32)}, schema)
    conf.trace_enabled = False
    assert list(b.to_numpy()["a"]) == [0, 1, 2, 3, 4]
    assert len(trace.TRACE) == 0
    conf.trace_enabled = True
    b.to_numpy()
    (d2h,) = _spans("d2h")
    assert d2h["attrs"]["final"] is False and d2h["attrs"]["rows"] == 5
    assert d2h["attrs"]["bytes"] > 0 and "query_id" not in d2h


# ---------------------------------------------------------------------------
# the benchmark's span readers
# ---------------------------------------------------------------------------


def _reader(name):
    path = os.path.join(REPO, "benchmarks", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("m_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _sp(kind, ts, dur, id=None, parent=None, thread="MainThread", **attrs):
    return {"type": "span", "kind": kind, "ts": ts, "dur": dur, "id": id,
            "parent": parent, "thread": thread, "attrs": attrs}


MS = 1_000_000


def _query(extra):
    """One query of 1000 ms on the driver thread with a plan span of 10 ms
    and one stage of 900 ms, plus `extra` spans."""
    return {"spans": [
        _sp("query", 0, 1000 * MS, id=1),
        _sp("plan", 1 * MS, 10 * MS, id=2, parent=1),
        _sp("stage", 20 * MS, 900 * MS, id=3, parent=1,
            stage_kind="shuffle_map"),
    ] + extra}


PRESENT = _query([
    _sp("scan_decode", 30 * MS, 100 * MS, id=10, parent=3, thread="io-0"),
    _sp("scan_decode", 130 * MS, 50 * MS, id=11, parent=3, thread="io-0"),
    _sp("h2d", 135 * MS, 15 * MS, id=12, parent=3, thread="io-0"),
    _sp("h2d", 180 * MS, 25 * MS, id=13, parent=3, thread="io-0"),
    _sp("exchange", 300 * MS, 200 * MS, id=14, parent=3),
    _sp("exchange", 600 * MS, 100 * MS, id=15, parent=3),
    _sp("collect", 930 * MS, 40 * MS, id=16, parent=3),
    _sp("d2h", 940 * MS, 20 * MS, id=17, parent=16, what="to_host",
        final=False),
    _sp("d2h", 1001 * MS, 7 * MS, id=18, parent=None, what="to_numpy",
        final=True),
])
# a second query whose sums are larger: the median of two is their mean
PRESENT2 = _query([
    _sp("scan_decode", 30 * MS, 250 * MS, id=20, parent=3, thread="io-0"),
    _sp("h2d", 280 * MS, 60 * MS, id=21, parent=3, thread="io-0"),
    _sp("exchange", 400 * MS, 500 * MS, id=22, parent=3),
    _sp("collect", 930 * MS, 60 * MS, id=23, parent=3),
])
ABSENT = {"spans": [  # a parent-commit program: no id, no parent, 3 kinds
    {"type": "span", "kind": "query", "ts": 0, "dur": 1000 * MS,
     "thread": "MainThread"},
    {"type": "span", "kind": "stage", "ts": 20 * MS, "dur": 900 * MS,
     "thread": "MainThread", "attrs": {"stage_kind": "shuffle_map"}},
]}


def _run(*queries, profiled=()):
    return {"window": list(queries), "profiled": list(profiled)}


@pytest.mark.parametrize("name,one,two", [
    ("scan_decode_s", 0.150, 0.200),
    ("upload_s", 0.040, 0.050),
    ("first_upload_ms", 150.0, 245.0),
    ("exchange_s", 0.300, 0.400),
    ("collect_s", 0.047, 0.0535),
    # 1000 - (10 + 900) = 90 of 1000 uncovered
    ("query_self_share", 9.0, 9.0),
])
def test_span_reader_present(name, one, two):
    read = _reader(name)
    assert read(_run(PRESENT)) == pytest.approx(one)
    # median per query over window + profiled
    assert read(_run(PRESENT, profiled=[PRESENT2])) == pytest.approx(two)


@pytest.mark.parametrize("name", [
    "scan_decode_s", "upload_s", "first_upload_ms", "exchange_s",
    "collect_s", "query_self_share"])
def test_span_reader_absent_returns_none(name):
    read = _reader(name)
    assert read(_run(ABSENT)) is None          # the parent's spans
    assert read(_run({"spans": None})) is None  # tracing off
    assert read(_run()) is None
    # a run that mixes both reads what is there
    assert read(_run(ABSENT, PRESENT)) is not None


def test_query_self_share_overlapping_and_foreign_children():
    read = _reader("query_self_share")
    q = {"spans": [
        _sp("query", 100 * MS, 1000 * MS, id=1),
        # overlapping children: union 100..700 = 600 ms
        _sp("stage", 100 * MS, 400 * MS, id=2, parent=1),
        _sp("stage", 300 * MS, 400 * MS, id=3, parent=1),
        # contained in another child: adds nothing
        _sp("plan", 150 * MS, 50 * MS, id=4, parent=1),
        # a child on another thread, a grandchild, and another query's
        # child do not count
        _sp("stage", 700 * MS, 300 * MS, id=5, parent=1, thread="pool-1"),
        _sp("collect", 800 * MS, 100 * MS, id=6, parent=3),
        _sp("stage", 800 * MS, 100 * MS, id=7, parent=99),
        # clipped to the query's interval: 1050..1100 counts, the rest not
        _sp("stage", 1050 * MS, 500 * MS, id=8, parent=1),
    ]}
    # covered: 600 + 50 = 650 of 1000
    assert read(_run(q)) == pytest.approx(35.0)


def test_collect_s_counts_only_the_final_pull():
    read = _reader("collect_s")
    q = _query([
        _sp("collect", 900 * MS, 10 * MS, id=5, parent=3),
        _sp("d2h", 902 * MS, 5 * MS, id=6, parent=5, final=False),
        _sp("d2h", 1000 * MS, 30 * MS, id=7, final=True),
    ])
    assert read(_run(q)) == pytest.approx(0.040)


def test_first_upload_is_the_earliest_started_upload():
    read = _reader("first_upload_ms")
    q = _query([
        _sp("h2d", 500 * MS, 10 * MS, id=5, parent=3),
        _sp("h2d", 40 * MS, 30 * MS, id=6, parent=3),
    ])
    assert read(_run(q)) == pytest.approx(70.0)
