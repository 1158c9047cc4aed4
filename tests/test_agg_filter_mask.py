"""A filter that feeds a partial aggregate hands it a mask, not a compacted
batch (ops/agg `AggExec._mask_filter`): the collapse's sort sends the rows the
filter dropped behind the kept ones, as it sends padding.

Every equivalence case runs one plan twice: Filter -> partial Agg -> final
Agg with the mask carried, and the same aggregates over the batches the same
FilterExec produced on its own (its `fused.filter` program, which compacts).
Same rows in the same order, so the answers are equal to the bit. The rest
holds what must not move: who still compacts, the plan's keys, the
stage_compiler's match, a filter-less aggregate's programs, and the absorbed
filter's counters, trace events and fault point."""

import decimal

import numpy as np
import pytest

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import ColumnBatch
from blaze_tpu.config import conf
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.ir import BinOp, col, lit
from blaze_tpu.ops import agg as agg_mod
from blaze_tpu.ops.agg import KEEP_PLANE, AggCall, AggExec, AggMode
from blaze_tpu.ops.base import ExecContext
from blaze_tpu.ops.basic import (
    FilterExec, LocalLimitExec, MemorySourceExec, ProjectExec,
)
from blaze_tpu.ops.join import JoinKey, JoinType, SortMergeJoinExec
from blaze_tpu.ops.shuffle import Partitioning, ShuffleWriterExec
from blaze_tpu.ops.sort import SortExec
from blaze_tpu.ops.sort_keys import SortSpec
from blaze_tpu.runtime import compile_service, faults, jit_cache, trace
from blaze_tpu.runtime import stage_compiler

MONEY = T.decimal(7, 2)
SCHEMA = T.Schema([
    T.Field("k", T.INT64),
    T.Field("v", T.FLOAT64),
    T.Field("n", T.INT32),
    T.Field("m", MONEY),
    T.Field("s", T.STRING),
])


def _batches(seed, sizes, null_frac=0.0, nkeys=9):
    rng = np.random.default_rng(seed)
    out = []
    for n in sizes:
        data = {
            "k": rng.integers(0, nkeys, n).astype(np.int64),
            "v": rng.random(n) * 200.0,
            "n": rng.integers(-100, 100, n).astype(np.int32),
            "m": rng.integers(0, 2_000_000, n).astype(np.int64),
            "s": [f"s{j}" for j in rng.integers(0, 30, n)],
        }
        validity = None
        if null_frac:
            validity = {c: rng.random(n) > null_frac
                        for c in ("k", "v", "n", "m", "s")}
        out.append(ColumnBatch.from_numpy(data, SCHEMA, validity=validity))
    return out


def _gt(name, value):
    return ir.Binary(BinOp.GT, col(name), lit(value))


def _lt(name, value):
    return ir.Binary(BinOp.LT, col(name), lit(value))


SUMS = [AggCall("sum", (col("v"),), T.FLOAT64, "sum_v"),
        AggCall("count", (col("v"),), T.INT64, "cnt_v"),
        AggCall("avg", (col("v"),), T.FLOAT64, "avg_v"),
        AggCall("sum", (col("n"),), T.INT64, "sum_n")]
ORDERED = [AggCall("first", (col("v"),), T.FLOAT64, "first_v"),
           AggCall("first_ignores_null", (col("v"),), T.FLOAT64, "fnn_v"),
           AggCall("collect_list", (col("n"),), T.list_of(T.INT32), "lst_n")]
MINMAX = [AggCall("min", (col("n"),), T.INT32, "min_n"),
          AggCall("max", (col("v"),), T.FLOAT64, "max_v"),
          AggCall("min", (col("s"),), T.STRING, "min_s"),
          AggCall("count", (col("v"), col("n")), T.INT64, "cnt_vn")]
DECIMAL = [AggCall("sum", (col("m"),), T.decimal(17, 2), "sum_m"),
           AggCall("avg", (col("m"),), T.decimal(11, 6), "avg_m"),
           AggCall("max", (col("m"),), MONEY, "max_m")]

# name -> (predicates, group-by?, aggregates, batch sizes, share of nulls,
# collapse threshold). Sizes differ so the raw work batches have different
# capacities; a high threshold concatenates them before ONE collapse.
ONE_BY_ONE, ALL_AT_ONCE = 1, 10 ** 9
CASES = {
    "keeps_none": ([_gt("v", 1e9)], True, SUMS, (700, 90), 0.0, ONE_BY_ONE),
    "keeps_none_global": ([_gt("v", 1e9)], False, SUMS, (700, 90), 0.0,
                          ONE_BY_ONE),
    "keeps_half": ([_gt("v", 100.0)], True, SUMS, (700, 90), 0.0,
                   ONE_BY_ONE),
    "keeps_all": ([_gt("v", -1.0)], True, SUMS, (700, 90), 0.0, ONE_BY_ONE),
    "null_predicate_nullable_inputs": (
        [_gt("v", 100.0)], True, SUMS + MINMAX, (700, 300), 0.3, ONE_BY_ONE),
    "null_group_keys": ([_lt("n", 0)], True, SUMS, (512, 40), 0.4,
                        ALL_AT_ONCE),
    "capacities_differ_one_collapse": (
        [_gt("v", 60.0)], True, SUMS + MINMAX, (3000, 70, 900, 5), 0.1,
        ALL_AT_ONCE),
    "two_predicates": ([_gt("v", 50.0), _lt("n", 40)], True, SUMS,
                       (700, 90), 0.2, ALL_AT_ONCE),
    "global_aggregate": ([_gt("v", 100.0)], False, SUMS + MINMAX, (700, 90),
                         0.2, ONE_BY_ONE),
    "global_one_collapse": ([_gt("v", 100.0)], False, SUMS, (700, 90, 260),
                            0.0, ALL_AT_ONCE),
    "order_sensitive": ([_gt("v", 100.0)], True, ORDERED, (700, 90), 0.25,
                        ONE_BY_ONE),
    "order_sensitive_one_collapse": ([_gt("v", 100.0)], True, ORDERED,
                                     (300, 40, 1100), 0.25, ALL_AT_ONCE),
    "decimal_int64_plane": ([_gt("m", decimal.Decimal("10000.00"))], True,
                            DECIMAL, (700, 90), 0.2, ONE_BY_ONE),
    "decimal_one_collapse": ([_gt("m", decimal.Decimal("10000.00"))], True,
                             DECIMAL + SUMS, (700, 90, 2100), 0.0,
                             ALL_AT_ONCE),
    "string_group_key": ([_gt("v", 100.0)], "s", SUMS, (700, 90), 0.2,
                         ALL_AT_ONCE),
}


def _two_phase(source, group, calls, threshold):
    names = [] if not group else ["s" if group == "s" else "k"]
    keys = [col(n) for n in names]
    partial = AggExec(source, keys, names, calls, AggMode.PARTIAL,
                      collapse_threshold=threshold)
    final = AggExec(partial, keys, names, calls, AggMode.FINAL)
    return partial, final


def _rows(op):
    """The operator's streaming output, batch by batch, on the host."""
    return [b.to_numpy() for b in op.execute(ExecContext())]


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert list(g) == list(w)
        for name in g:
            a, b = list(g[name]), list(w[name])
            assert len(a) == len(b), name
            for x, y in zip(a, b):
                if isinstance(x, float) and isinstance(y, float):
                    assert x == y or (np.isnan(x) and np.isnan(y)), name
                else:
                    assert np.array_equal(x, y) if isinstance(
                        x, (list, np.ndarray)) else x == y, name


@pytest.mark.parametrize("case", sorted(CASES))
def test_carried_mask_equals_the_filter_run_on_its_own(case):
    preds, group, calls, sizes, null_frac, threshold = CASES[case]
    batches = _batches(7, sizes, null_frac)
    before = compile_service.TELEMETRY.snapshot()

    carried_filter = FilterExec(MemorySourceExec(batches, SCHEMA), preds)
    partial, final = _two_phase(carried_filter, group, calls, threshold)
    assert partial._mask_filter() is carried_filter
    got = _rows(final)
    mid = compile_service.TELEMETRY.snapshot()
    assert mid["filter_masks_carried"] - before["filter_masks_carried"] == \
        len(batches)
    assert mid["filter_compactions"] == before["filter_compactions"]

    own_filter = FilterExec(MemorySourceExec(batches, SCHEMA), preds)
    kept = list(own_filter.execute(ExecContext()))
    after = compile_service.TELEMETRY.snapshot()
    assert after["filter_compactions"] - mid["filter_compactions"] == \
        len(batches)
    assert after["filter_masks_carried"] == mid["filter_masks_carried"]
    partial2, final2 = _two_phase(MemorySourceExec(kept, SCHEMA), group,
                                  calls, threshold)
    assert partial2._mask_filter() is None
    want = _rows(final2)

    _same(got, want)
    if case.startswith("keeps_none"):
        # a grouped aggregate of nothing is nothing; a global one is one row
        assert len(got) == (0 if group else 1)
    else:
        assert got and len(next(iter(got[0].values()))) > 0
    # the filter's own counters read what its program would have counted
    for name in ("output_rows", "output_batches"):
        assert carried_filter.metrics.snapshot()[name] == \
            own_filter.metrics.snapshot()[name]
    assert partial.metrics.snapshot().get("collapses", 0) == \
        partial2.metrics.snapshot().get("collapses", 0)


def test_an_all_dead_batch_among_live_ones_is_skipped_like_an_empty_one():
    batches = _batches(3, (400, 64, 400))
    dead = batches[1]
    batches[1] = ColumnBatch(
        SCHEMA, [c if f.name != "v" else type(c)(c.dtype, c.data * 0.0,
                                                 c.validity)
                 for f, c in zip(SCHEMA, dead.columns)],
        dead.num_rows, dead.capacity)
    filt = FilterExec(MemorySourceExec(batches, SCHEMA), [_gt("v", 100.0)])
    partial, final = _two_phase(filt, True, SUMS, ONE_BY_ONE)
    got = _rows(final)
    kept = list(FilterExec(MemorySourceExec(batches, SCHEMA),
                           [_gt("v", 100.0)]).execute(ExecContext()))
    assert [int(b.num_rows) > 0 for b in kept] == [True, False, True]
    partial2, final2 = _two_phase(MemorySourceExec(kept, SCHEMA), True, SUMS,
                                  ONE_BY_ONE)
    _same(got, _rows(final2))
    # two raw collapses (the dead batch never reached one) and their merge
    assert partial.metrics.snapshot()["collapses"] == \
        partial2.metrics.snapshot()["collapses"] == 3
    assert filt.metrics.snapshot()["output_batches"] == 3


def test_compact_is_never_traced_on_the_carried_path(monkeypatch):
    calls = {"n": 0}
    real = ColumnBatch.compact

    def counting(self, keep):
        calls["n"] += 1
        return real(self, keep)

    monkeypatch.setattr(ColumnBatch, "compact", counting)
    # literals no other test uses: every program here is traced afresh
    batches = _batches(5, (600, 50))
    filt = FilterExec(MemorySourceExec(batches, SCHEMA), [_gt("v", 101.25)])
    _, final = _two_phase(filt, True, SUMS + ORDERED, ALL_AT_ONCE)
    assert _rows(final)
    assert calls["n"] == 0
    # the control: the same filter on its own traces it, once a shape
    own = FilterExec(MemorySourceExec(batches, SCHEMA), [_gt("v", 101.25)])
    list(own.execute(ExecContext()))
    assert calls["n"] == len({b.shape_key() for b in batches})


def _under_join(filt, tmp_path):
    right = MemorySourceExec(_batches(9, (50,)), SCHEMA)
    return SortMergeJoinExec(filt, right, [JoinKey(0, 0)], JoinType.INNER)


def _under_sort(filt, tmp_path):
    return SortExec(filt, [SortSpec(0)])


def _under_limit(filt, tmp_path):
    return LocalLimitExec(filt, 10 ** 6)


def _under_exchange(filt, tmp_path):
    return ShuffleWriterExec(filt, Partitioning("hash", 4, (col("k"),)),
                             str(tmp_path / "x.data"),
                             str(tmp_path / "x.index"))


def _under_project_then_agg(filt, tmp_path):
    proj = ProjectExec(filt, [col(f.name) for f in SCHEMA],
                       [f.name for f in SCHEMA])
    return _two_phase(proj, True, SUMS, ONE_BY_ONE)[1]


def _under_merging_agg(mode):
    """A PARTIAL_MERGE or FINAL aggregate reads state columns: its child's
    rows are states, and a filter over them (a HAVING pushed under the
    merge) compacts."""
    def consumer(filt, tmp_path):
        partial = AggExec(filt.child, [col("k")], ["k"], SUMS,
                          AggMode.PARTIAL)
        states = list(partial.execute(ExecContext()))
        having = FilterExec(MemorySourceExec(states, partial.schema),
                            [ir.Binary(BinOp.GT, col("k"), lit(2))])
        return AggExec(having, [col("k")], ["k"], SUMS, mode)

    consumer.__name__ = f"_under_{mode.value}_agg"
    return consumer


@pytest.mark.parametrize("consumer", [
    _under_join, _under_sort, _under_limit, _under_exchange,
    _under_project_then_agg, _under_merging_agg(AggMode.FINAL),
    _under_merging_agg(AggMode.PARTIAL_MERGE)],
    ids=lambda f: f.__name__.lstrip("_"))
def test_a_filter_under_any_other_consumer_still_compacts(consumer,
                                                          tmp_path):
    batches = _batches(2, (300, 40))
    filt = FilterExec(MemorySourceExec(batches, SCHEMA), [_gt("v", 100.0)])
    root = consumer(filt, tmp_path)
    before = compile_service.TELEMETRY.snapshot()
    list(root.execute(ExecContext()))
    after = compile_service.TELEMETRY.snapshot()
    assert after["filter_masks_carried"] == before["filter_masks_carried"]
    # the filter under the root ran its own program on each of its batches
    assert after["filter_compactions"] - before["filter_compactions"] >= 1
    for op in _walk(root):
        if isinstance(op, AggExec):
            assert op._mask_filter() is None


def _walk(op):
    yield op
    for c in op.children:
        yield from _walk(c)


def test_a_filter_whose_predicate_crosses_to_the_host_is_not_absorbed():
    batches = _batches(4, (200,))
    digest = ir.ScalarFn("crc32", (ir.Cast(col("s"), T.BINARY),))
    host_pred = ir.Binary(BinOp.GT, digest, lit(2 ** 31))
    filt = FilterExec(MemorySourceExec(batches, SCHEMA), [host_pred])
    assert not filt.jit_safe()
    partial, final = _two_phase(filt, True, SUMS, ONE_BY_ONE)
    assert partial._mask_filter() is None
    before = compile_service.TELEMETRY.snapshot()
    got = _rows(final)
    after = compile_service.TELEMETRY.snapshot()
    assert after["filter_masks_carried"] == before["filter_masks_carried"]
    assert after["filter_compactions"] - before["filter_compactions"] == 1
    kept = list(FilterExec(MemorySourceExec(batches, SCHEMA),
                           [host_pred]).execute(ExecContext()))
    _same(got, _rows(_two_phase(MemorySourceExec(kept, SCHEMA), True, SUMS,
                                ONE_BY_ONE)[1]))


def test_the_plan_keeps_its_keys_and_the_stage_compiler_its_match():
    batches = _batches(1, (300,))
    src = MemorySourceExec(batches, SCHEMA)
    pred = _gt("v", 100.0)
    filt = FilterExec(src, [pred])
    partial, final = _two_phase(filt, True, SUMS, ONE_BY_ONE)
    assert filt.plan_key() == ("filter", (pred.key(),), src.plan_key())
    assert partial.plan_key() == (
        "agg", "partial", (col("k").key(),),
        tuple(c.key() for c in SUMS), filt.plan_key())
    assert partial.children == [filt] and filt.children == [src]
    assert partial.tree_string() == \
        "AggExec\n  FilterExec\n    MemorySourceExec\n"
    # scan -> filter -> partial agg is still the whole-stage pattern, with
    # the filter in its chain
    m = stage_compiler._match(partial)
    assert m is not None
    final_op, partial_op, chain, source = m
    assert partial_op is partial and chain == [filt] and source is src


def test_a_filterless_partial_aggregate_keeps_its_programs():
    batches = _batches(6, (300,))
    src = MemorySourceExec(batches, SCHEMA)
    partial, _ = _two_phase(src, True, SUMS, ONE_BY_ONE)
    work = partial._to_work(batches[0])
    assert isinstance(work, ColumnBatch)
    assert work.schema.names() == [
        "k", "in.sum_v.0", "in.cnt_v.0", "in.avg_v.0", "in.sum_n.0"]
    assert KEEP_PLANE not in work.schema.names()
    state = partial._collapse([work], raw_input=True)
    assert state.schema.names() == partial._state_schema.names()
    assert ("agg_work", True, partial.plan_key(),
            batches[0].shape_key()) in jit_cache._cache
    assert ("agg_collapse", True, partial.plan_key(),
            work.shape_key()) in jit_cache._cache
    # and under a filter the work batch ends in the keep plane, its rows
    # still the physical ones
    filt = FilterExec(src, [_gt("v", 100.0)])
    masked, _ = _two_phase(filt, True, SUMS, ONE_BY_ONE)
    mwork, kept = masked._to_work(batches[0], filt)
    assert mwork.schema.names() == work.schema.names() + [KEEP_PLANE]
    assert int(mwork.num_rows) == 300 and 0 < int(kept) < 300
    v = np.asarray(batches[0].to_numpy()["v"], dtype=float)
    assert int(kept) == int((v > 100.0).sum())


def test_sort_batch_with_a_liveness_plane_compacts_stably():
    from blaze_tpu.ops.sort_keys import sort_batch

    (b,) = _batches(8, (200,), nkeys=4)
    v = np.asarray(b.to_numpy()["v"], dtype=float)
    keep = np.zeros(b.capacity, bool)
    keep[:200] = v > 120.0
    import jax.numpy as jnp

    out = sort_batch(b, [SortSpec(0)], live=jnp.asarray(keep))
    assert int(out.num_rows) == int(keep.sum())
    want = sort_batch(b.compact(jnp.asarray(keep)), [SortSpec(0)])
    got_np, want_np = out.to_numpy(), want.to_numpy()
    for name in ("k", "v", "n", "m", "s"):
        assert list(got_np[name]) == list(want_np[name])
    # without the plane the function is the one it was
    plain = sort_batch(b, [SortSpec(0)])
    assert int(plain.num_rows) == 200


# -- the absorbed filter stays visible --------------------------------------


@pytest.fixture
def _clean_runtime():
    saved = {k: getattr(conf, k) for k in (
        "trace_enabled", "fault_injection_spec",
        "enable_input_batch_statistics")}
    trace.reset()
    yield
    for k, v in saved.items():
        setattr(conf, k, v)
    trace.reset()
    faults.install(None)
    faults.reset_telemetry()


def _plans(batches, preds):
    """(root, filter) with the mask carried, and with the filter's own
    program run by a consumer that is no aggregate's business."""
    carried = FilterExec(MemorySourceExec(batches, SCHEMA), preds)
    compacting = FilterExec(MemorySourceExec(batches, SCHEMA), preds)
    return ((_two_phase(carried, True, SUMS, ONE_BY_ONE)[1], carried),
            (compacting, compacting))


def test_the_absorbed_filters_counters_are_the_compacting_paths(
        _clean_runtime):
    conf.enable_input_batch_statistics = True
    batches = _batches(12, (500, 64, 130), null_frac=0.2)
    (root, carried), (own_root, own) = _plans(batches, [_gt("v", 100.0)])
    list(root.execute(ExecContext()))
    list(own_root.execute(ExecContext()))
    got, want = carried.metrics.snapshot(), own.metrics.snapshot()
    v = [np.asarray([x if x is not None else np.nan
                     for x in b.to_numpy()["v"]], dtype=float)
         for b in batches]
    assert want["output_rows"] == sum(int((x > 100.0).sum()) for x in v)
    for name in ("output_rows", "output_batches", "stat_bytes",
                 "stat_max_batch_rows"):
        assert got[name] == want[name], name
    assert got["output_batches"] == 3


def test_the_absorbed_filters_fault_point_still_fires(_clean_runtime):
    batches = _batches(13, (200, 90))
    fired = {}
    for which in ("carried", "compacting"):
        (root, _), (own_root, _) = _plans(batches, [_gt("v", 100.0)])
        faults.install({"seed": 1, "points": {
            "op.FilterExec": {"nth": 2, "kind": "retryable"}}})
        with pytest.raises(faults.RetryableError) as err:
            list((root if which == "carried" else own_root)
                 .execute(ExecContext()))
        fired[which] = (err.value.point, list(faults.injection_log))
    assert fired["carried"] == fired["compacting"] == (
        "op.FilterExec", [("op.FilterExec", 2)])


def test_the_absorbed_filters_batches_are_in_the_trace(_clean_runtime):
    conf.trace_enabled = True
    batches = _batches(14, (300, 40, 77))
    events = {}
    for which in ("carried", "compacting"):
        (root, _), (own_root, _) = _plans(batches, [_gt("v", 100.0)])
        trace.reset()
        with trace.context(query_id="q-" + which):
            list((root if which == "carried" else own_root)
                 .execute(ExecContext()))
        events[which] = [
            (r["attrs"]["op"], r["attrs"]["rows"], r.get("query_id"))
            for r in trace.TRACE.snapshot()
            if r["kind"] == "batch" and r["attrs"]["op"] == "FilterExec"]
    assert len(events["carried"]) == 3
    assert [e[:2] for e in events["carried"]] == \
        [e[:2] for e in events["compacting"]]
    assert {e[2] for e in events["carried"]} == {"q-carried"}
    # no dispatch of a filter's own program on the carried path, one a
    # batch on the other (jit_cache's `dispatch` spans, by program kind)
    programs = {}
    for which in ("carried", "compacting"):
        (root, _), (own_root, _) = _plans(batches, [_gt("v", 100.0)])
        trace.reset()
        list((root if which == "carried" else own_root)
             .execute(ExecContext()))
        programs[which] = [r["attrs"]["program"]
                           for r in trace.TRACE.snapshot()
                           if r["kind"] == "dispatch"]
    assert programs["compacting"] == ["fused"] * 3
    assert "fused" not in programs["carried"]
    assert programs["carried"].count("agg_work") == 3


def test_telemetry_names_both_filter_counters():
    snap = compile_service.TELEMETRY.snapshot()
    assert "filter_masks_carried" in snap and "filter_compactions" in snap
    assert agg_mod.KEEP_PLANE == "filter.keep"


# -- the benchmark's reader of the two counters (benchmarks/tests holds the
# -- cell's rehearsal; tier-1 does not run that directory) -------------------


@pytest.mark.parametrize("telemetry, want", [
    ({"filter_masks_carried": 128, "filter_compactions": 0}, 100.0),
    ({"filter_masks_carried": 0, "filter_compactions": 56}, 0.0),
    ({"filter_masks_carried": 3, "filter_compactions": 1}, 75.0),
    # a query without a filter, and a program without the counters (the
    # parent of PR 33): nothing to report, and no raise
    ({"filter_masks_carried": 0, "filter_compactions": 0}, None),
    ({"cache_hits": 308}, None),
])
def test_the_benchmarks_filter_mask_share_reader(telemetry, want):
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "m_filter_mask_share", os.path.join(
            repo, "benchmarks", "metrics", "filter_mask_share.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.read({"window": [], "telemetry": telemetry}) == want
