"""Whole-stage single-dispatch execution (runtime/stage_compiler.py) and the
MXU dense grouped aggregation (ops/mxu_agg.py).

The stage compiler exists because the streaming executor pays several
dispatches and a host round trip per batch; correctness contract: identical results to the streaming executor,
with range/null violations falling back to it transparently.
"""

import numpy as np
import pandas as pd
import pytest

import jax.numpy as jnp

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import ColumnBatch
from blaze_tpu.config import conf
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.ir import BinOp, col
from blaze_tpu.ops import mxu_agg
from blaze_tpu.ops.agg import AggCall, AggExec, AggMode
from blaze_tpu.ops.basic import FilterExec, MemorySourceExec, ProjectExec
from blaze_tpu.runtime.executor import collect

SCHEMA = T.Schema([T.Field("k", T.INT64), T.Field("v", T.FLOAT64),
                   T.Field("n", T.INT32)])

CALLS = [AggCall("sum", (col("v"),), T.FLOAT64, "sv"),
         AggCall("sum", (col("n"),), T.INT64, "sn"),
         AggCall("count", (col("v"),), T.INT64, "cnt"),
         AggCall("avg", (col("v"),), T.FLOAT64, "av")]


def _batches(rng, nb, n, kmin=0, kmax=300, null_frac=0.0):
    out = []
    for _ in range(nb):
        data = {"k": rng.integers(kmin, kmax, n).astype(np.int64),
                "v": rng.random(n) * 10 - 3,
                "n": rng.integers(-50, 50, n).astype(np.int32)}
        validity = None
        if null_frac:
            validity = {"v": rng.random(n) > null_frac}
        out.append(ColumnBatch.from_numpy(data, SCHEMA, validity=validity,
                                          capacity=max(n, 1024)))
    return out


def _plan(batches, with_filter=True):
    node = MemorySourceExec(batches, SCHEMA)
    if with_filter:
        node = FilterExec(node, [ir.Binary(BinOp.GE, col("v"),
                                           ir.Literal(T.FLOAT64, -1.0))])
    for mode in (AggMode.PARTIAL, AggMode.FINAL):
        node = AggExec(node, [col("k")], ["k"], CALLS, mode)
    return node


def _oracle(batches, with_filter=True):
    frames = []
    for b in batches:
        d = b.to_numpy()
        frames.append(pd.DataFrame({"k": np.asarray(d["k"]),
                                    "v": [x for x in d["v"]],
                                    "n": [x for x in d["n"]]}))
    df = pd.concat(frames, ignore_index=True)
    if with_filter:
        df = df[df["v"] >= -1.0]
    return df


def _check(out, batches, with_filter=True):
    d = out.to_numpy()
    df = _oracle(batches, with_filter)
    want = df.groupby("k").agg(
        sv=("v", lambda x: x.dropna().sum()),
        sn=("n", "sum"),
        cnt=("v", lambda x: x.notna().sum()),
        av=("v", lambda x: x.dropna().mean()))
    got_k = list(np.asarray(d["k"]))
    assert got_k == sorted(want.index), "groups"
    for i, k in enumerate(got_k):
        # float-sum tolerance follows conf.float_sum_digit_planes
        # (38-bit digitization by default => ~1e-9 class errors)
        np.testing.assert_allclose(float(d["sv"][i]), want.loc[k, "sv"],
                                   rtol=4e-8)
        assert int(d["sn"][i]) == int(want.loc[k, "sn"])
        assert int(np.asarray(d["cnt"])[i]) == int(want.loc[k, "cnt"])
        np.testing.assert_allclose(float(d["av"][i]), want.loc[k, "av"],
                                   rtol=4e-8)


def test_stage_matches_pandas(rng):
    batches = _batches(rng, 4, 700)
    plan = _plan(batches)
    out = collect(plan)
    assert plan.metrics["stage_compiled"] == 1
    _check(out, batches)


def test_stage_matches_streaming(rng):
    batches = _batches(rng, 3, 500, null_frac=0.3)
    got = collect(_plan(batches)).to_numpy()
    conf.enable_stage_compiler = False
    try:
        want = collect(_plan(batches)).to_numpy()
    finally:
        conf.enable_stage_compiler = True
    assert list(np.asarray(got["k"])) == list(np.asarray(want["k"]))
    np.testing.assert_allclose(
        [float(x) for x in got["sv"]], [float(x) for x in want["sv"]],
        rtol=4e-8)
    assert list(np.asarray(got["cnt"])) == list(np.asarray(want["cnt"]))


def test_negative_and_offset_keys(rng):
    """Key range is offset by the observed minimum, so negative/huge-base
    keys still take the dense path."""
    batches = _batches(rng, 2, 400, kmin=-150, kmax=80)
    plan = _plan(batches, with_filter=False)
    out = collect(plan)
    assert plan.metrics["stage_compiled"] == 1
    _check(out, batches, with_filter=False)
    batches = _batches(rng, 2, 400, kmin=10 ** 12, kmax=10 ** 12 + 500)
    plan = _plan(batches, with_filter=False)
    out = collect(plan)
    assert plan.metrics["stage_compiled"] == 1
    _check(out, batches, with_filter=False)


def test_wide_range_falls_back(rng):
    """Keys spanning more than dense_agg_range: in-program flag trips and
    the result comes from the streaming path — identical values."""
    batches = _batches(rng, 2, 300, kmin=0, kmax=10 ** 9)
    plan = _plan(batches, with_filter=False)
    out = collect(plan)
    assert plan.metrics["stage_compiled"] == 0
    _check(out, batches, with_filter=False)


def test_null_group_keys_fall_back(rng):
    """Null grouping keys form their own group (Spark): dense path cannot
    represent them, so the stage falls back and the result still carries
    the null group."""
    n = 200
    data = {"k": rng.integers(0, 5, n).astype(np.int64),
            "v": rng.random(n), "n": np.zeros(n, np.int32)}
    knull = rng.random(n) > 0.8
    b = ColumnBatch.from_numpy(data, SCHEMA, validity={"k": ~knull})
    plan = _plan([b], with_filter=False)
    out = collect(plan)
    d = out.to_numpy()
    ks = list(d["k"])
    assert None in ks  # the null group survived via fallback
    nn = ks.index(None)
    df = pd.DataFrame({"k": np.where(knull, np.nan, data["k"]),
                       "v": data["v"]})
    np.testing.assert_allclose(
        float(d["sv"][nn]), df[df["k"].isna()]["v"].sum(), rtol=1e-9)


def test_mxu_grouped_sum_kernels(rng):
    n = 1 << 12
    R = 1 << 10
    keys = jnp.asarray(rng.integers(0, R, n).astype(np.int32))
    valid = jnp.asarray(rng.random(n) > 0.2)
    fvals = jnp.asarray(rng.random(n) * 1e6 - 4e5)
    ivals = jnp.asarray(rng.integers(-10 ** 12, 10 ** 12, n))
    got = np.asarray(mxu_agg.grouped_sum(keys, fvals, valid, R))
    want = np.zeros(R)
    np.add.at(want, np.asarray(keys)[np.asarray(valid)],
              np.asarray(fvals)[np.asarray(valid)])
    np.testing.assert_allclose(got, want, rtol=4e-8, atol=1e-6)
    got = np.asarray(mxu_agg.grouped_sum(keys, ivals, valid, R))
    want = np.zeros(R, np.int64)
    np.add.at(want, np.asarray(keys)[np.asarray(valid)],
              np.asarray(ivals)[np.asarray(valid)])
    np.testing.assert_array_equal(got, want)
    got = np.asarray(mxu_agg.grouped_count(keys, valid, R))
    want = np.bincount(np.asarray(keys)[np.asarray(valid)], minlength=R)
    np.testing.assert_array_equal(got, want)


def test_multi_key_grouping(rng):
    """Composite GROUP BY (k, n) packs into one dense range (q3's
    item x year shape); results match pandas and the key columns unpack."""
    batches = _batches(rng, 3, 500, kmin=5, kmax=40)
    node = MemorySourceExec(batches, SCHEMA)
    calls = [AggCall("sum", (col("v"),), T.FLOAT64, "sv"),
             AggCall("count", (col("v"),), T.INT64, "cnt")]
    for mode in (AggMode.PARTIAL, AggMode.FINAL):
        node = AggExec(node, [col("k"), col("n")], ["k", "n"], calls, mode)
    out = collect(node)
    assert node.metrics["stage_compiled"] == 1
    d = out.to_numpy()
    frames = []
    for b in batches:
        bd = b.to_numpy()
        frames.append(pd.DataFrame({"k": np.asarray(bd["k"]),
                                    "n": np.asarray(bd["n"]),
                                    "v": [x for x in bd["v"]]}))
    df = pd.concat(frames, ignore_index=True)
    want = df.groupby(["k", "n"])["v"].agg(["sum", "count"])
    got = {}
    for k, n, s, c in zip(np.asarray(d["k"]), np.asarray(d["n"]),
                          d["sv"], np.asarray(d["cnt"])):
        got[(int(k), int(n))] = (float(s), int(c))
    assert set(got) == set(want.index)
    for key, (s, c) in got.items():
        np.testing.assert_allclose(s, want.loc[key, "sum"], rtol=4e-8)
        assert c == want.loc[key, "count"]


def test_chain_stage_single_dispatch(rng):
    """Agg-less scan->filter->project runs in one dispatch and matches the
    streaming executor row-for-row."""
    batches = _batches(rng, 4, 600)
    proj_exprs = [col("k"),
                  ir.Binary(BinOp.MUL, col("v"), ir.Literal(T.FLOAT64, 2.0))]
    plan = ProjectExec(
        FilterExec(MemorySourceExec(batches, SCHEMA),
                   [ir.Binary(BinOp.GE, col("v"),
                              ir.Literal(T.FLOAT64, 0.0))]),
        proj_exprs, ["k", "v2"])
    out = collect(plan)
    assert plan.metrics["stage_compiled"] == 1
    got = out.to_numpy()

    plan2 = ProjectExec(
        FilterExec(MemorySourceExec(batches, SCHEMA),
                   [ir.Binary(BinOp.GE, col("v"),
                              ir.Literal(T.FLOAT64, 0.0))]),
        proj_exprs, ["k", "v2"])
    conf.enable_stage_compiler = False
    try:
        want = collect(plan2).to_numpy()
    finally:
        conf.enable_stage_compiler = True
    np.testing.assert_array_equal(np.asarray(got["k"]),
                                  np.asarray(want["k"]))
    np.testing.assert_allclose([float(x) for x in got["v2"]],
                               [float(x) for x in want["v2"]], rtol=0)


def test_chain_stage_string_columns(rng):
    """String columns flatten-compact correctly through the chain stage."""
    schema = T.Schema([T.Field("k", T.INT64), T.Field("s", T.STRING)])
    bs = []
    for _ in range(3):
        n = 300
        bs.append(ColumnBatch.from_numpy({
            "k": rng.integers(0, 100, n).astype(np.int64),
            "s": [f"val{i}" for i in rng.integers(0, 50, n)],
        }, schema))
    plan = FilterExec(MemorySourceExec(bs, schema),
                      [ir.Binary(BinOp.LT, col("k"),
                                 ir.Literal(T.INT64, 50))])
    out = collect(plan)
    assert plan.metrics["stage_compiled"] == 1
    d = out.to_numpy()
    want_rows = []
    for b in bs:
        bd = b.to_numpy()
        for k, sv in zip(np.asarray(bd["k"]), bd["s"]):
            if k < 50:
                want_rows.append((int(k), sv))
    got_rows = list(zip((int(x) for x in np.asarray(d["k"])), d["s"]))
    assert sorted(got_rows) == sorted(want_rows)


def test_nonfinite_values_fall_back(rng):
    """NaN/Inf sum inputs can't ride the int8 digit planes (their digits
    would corrupt every dense slot): grouped_multi raises the bad flag,
    the stage program reports oob, and the streaming path produces the
    per-group NaN/Inf Spark semantics."""
    batches = _batches(rng, 2, 400, kmin=0, kmax=8)
    d0 = batches[0].to_numpy()
    v = np.asarray(d0["v"], np.float64).copy()
    kk = np.asarray(d0["k"], np.int64).copy()
    v[3], kk[3] = np.nan, 2       # NaN lands in group 2
    v[7], kk[7] = np.inf, 5       # Inf lands in group 5
    n0 = np.asarray(d0["n"], np.int32)
    batches[0] = ColumnBatch.from_numpy(
        {"k": kk, "v": v, "n": n0}, SCHEMA, capacity=batches[0].capacity)
    plan = _plan(batches, with_filter=False)
    out = collect(plan)
    assert plan.metrics["stage_compiled"] == 0  # fell back
    d = out.to_numpy()
    ks = list(np.asarray(d["k"]))
    sv = {k: float(d["sv"][i]) for i, k in enumerate(ks)}
    assert np.isnan(sv[2])
    assert np.isinf(sv[5])
    # untouched groups still match pandas exactly
    df = _oracle(batches, with_filter=False)
    want = df.groupby("k")["v"].sum()
    for k in ks:
        if k in (2, 5):
            continue
        np.testing.assert_allclose(sv[k], want.loc[k], rtol=1e-9)


def test_fixed_scale_drift_reprobes(rng):
    """The probed per-stage float scale is memoized like key ranges; a
    later dataset with 1000x larger values must trip the in-program
    overflow flag (checked in the FLOAT domain — an int64-cast overflow
    saturates and would silently corrupt) and re-probe, not return
    garbage sums."""
    def plan_for(scale):
        batches = []
        for _ in range(3):
            data = {"k": rng.integers(0, 50, 600).astype(np.int64),
                    "v": (rng.random(600) * 10 - 3) * scale,
                    "n": rng.integers(-50, 50, 600).astype(np.int32)}
            batches.append(ColumnBatch.from_numpy(data, SCHEMA,
                                                  capacity=1024))
        return batches

    small = plan_for(1.0)
    p1 = _plan(small, with_filter=False)
    _check(collect(p1), small, with_filter=False)
    assert p1.metrics["stage_compiled"] == 1

    big = plan_for(1000.0)   # beyond the 4x drift headroom
    p2 = _plan(big, with_filter=False)
    out = collect(p2)        # same plan/shape key -> memoized scale
    _check(out, big, with_filter=False)


def test_partial_only_stage_state_columns(rng, tmp_path):
    """Shuffle-map-side shape: a PARTIAL-only agg stage whole-stage
    compiles and emits the typed agg-buf STATE columns the FINAL merge
    consumes — end-to-end through a shuffle writer + reader + final
    agg, vs pandas."""
    from blaze_tpu.ops.base import ExecContext
    from blaze_tpu.ops.shuffle import (
        Partitioning, ShuffleWriterExec, read_shuffle_partition,
    )

    batches = _batches(rng, 3, 600)
    node = MemorySourceExec(batches, SCHEMA)
    node = FilterExec(node, [ir.Binary(BinOp.GE, col("v"),
                                       ir.Literal(T.FLOAT64, -1.0))])
    partial = AggExec(node, [col("k")], ["k"], CALLS, AggMode.PARTIAL)
    data = str(tmp_path / "s.data")
    index = str(tmp_path / "s.index")
    w = ShuffleWriterExec(partial, Partitioning("hash", 2, [col("k")]),
                          data, index)
    list(w.execute(ExecContext()))
    assert partial.metrics["stage_compiled"] == 1, \
        "partial-only stage must whole-stage compile"

    parts = []
    for p in range(2):
        parts.extend(read_shuffle_partition(data, index, p,
                                            partial.schema))
    merged = MemorySourceExec(parts, partial.schema)
    final = AggExec(merged, [col("#0")], ["k"], CALLS, AggMode.FINAL)
    out = collect(final)
    _check(out, batches)


def test_fallback_with_join_source(rng):
    """Regression (q5 validator cell): when the stage source is a JOIN
    subtree and the captured batches force the fallback (mixed shapes),
    the rebuild must swap exactly the SOURCE node — replacing every leaf
    re-joined the captured join output against itself and produced
    silently wrong counts."""
    from blaze_tpu.ops.join import JoinKey, JoinType, SortMergeJoinExec

    LS = T.Schema([T.Field("cat", T.INT32), T.Field("price", T.FLOAT64),
                   T.Field("dk", T.INT64)])
    RS = T.Schema([T.Field("rk", T.INT64)])
    # two left batches with DIFFERENT capacities -> join outputs with
    # different shape keys -> the stage compiler must fall back
    lbs = []
    for n, cap in ((700, 1024), (200, 256)):
        lbs.append(ColumnBatch.from_numpy({
            "cat": rng.integers(1, 8, n).astype(np.int32),
            "price": rng.random(n) * 100,
            "dk": rng.integers(0, 50, n).astype(np.int64)}, LS,
            capacity=cap))
    rb = ColumnBatch.from_numpy(
        {"rk": np.arange(0, 40, dtype=np.int64)}, RS)
    join = SortMergeJoinExec(MemorySourceExec(lbs, LS),
                             MemorySourceExec([rb], RS),
                             [JoinKey(2, 0)], JoinType.LEFT_SEMI)
    calls = [AggCall("sum", (col("price"),), T.FLOAT64, "rev"),
             AggCall("count", (col("price"),), T.INT64, "n")]
    for mode in (AggMode.PARTIAL, AggMode.FINAL):
        node = AggExec(join if mode == AggMode.PARTIAL else node,
                       [col("cat") if mode == AggMode.PARTIAL
                        else col("#0")], ["cat"], calls, mode)
    out = collect(node)
    d = out.to_numpy()
    # pandas oracle
    frames = []
    for b in lbs:
        bd = b.to_numpy()
        frames.append(pd.DataFrame({k: np.asarray(v) for k, v in
                                    bd.items()}))
    df = pd.concat(frames)
    df = df[df.dk < 40]
    want = df.groupby("cat").agg(rev=("price", "sum"),
                                 n=("price", "count"))
    ks = list(np.asarray(d["cat"]))
    assert ks == sorted(want.index)
    np.testing.assert_array_equal([int(x) for x in d["n"]],
                                  want["n"].loc[ks])
    np.testing.assert_allclose([float(x) for x in d["rev"]],
                               want["rev"].loc[ks], rtol=1e-9)


MM_CALLS = [AggCall("min", (col("v"),), T.FLOAT64, "mn"),
            AggCall("max", (col("v"),), T.FLOAT64, "mx"),
            AggCall("min", (col("n"),), T.INT32, "imn"),
            AggCall("max", (col("n"),), T.INT32, "imx"),
            AggCall("first_ignores_null", (col("v"),), T.FLOAT64, "fst"),
            AggCall("sum", (col("v"),), T.FLOAT64, "sv")]


def _mm_plan(batches, modes=(AggMode.PARTIAL, AggMode.FINAL)):
    node = MemorySourceExec(batches, SCHEMA)
    node = FilterExec(node, [ir.Binary(BinOp.GE, col("v"),
                                       ir.Literal(T.FLOAT64, -1.0))])
    for mode in modes:
        node = AggExec(node, [col("k")], ["k"], MM_CALLS, mode)
    return node


def test_minmax_first_stage_matches_streaming(rng):
    """min/max/first ride dense segment carriers in the whole-stage
    program (VERDICT r4 #1b); results must equal the streaming path."""
    batches = _batches(rng, 3, 500, null_frac=0.25)
    plan = _mm_plan(batches)
    got = collect(plan).to_numpy()
    assert plan.metrics["stage_compiled"] == 1
    conf.enable_stage_compiler = False
    try:
        want = collect(_mm_plan(batches)).to_numpy()
    finally:
        conf.enable_stage_compiler = True
    assert list(np.asarray(got["k"])) == list(np.asarray(want["k"]))
    for name in ("mn", "mx", "imn", "imx", "fst"):
        g, w = got[name], want[name]
        for a, b in zip(g, w):
            if b is None:
                assert a is None, (name, a, b)
            else:
                np.testing.assert_allclose(float(a), float(b), rtol=1e-9)

    # pandas oracle for min/max (first is order-dependent; streaming
    # comparison above covers it)
    frames = []
    for b in batches:
        d = b.to_numpy()
        frames.append(pd.DataFrame(
            {"k": np.asarray(d["k"]),
             "v": [None if x is None else float(x) for x in d["v"]],
             "n": np.asarray(d["n"])}))
    df = pd.concat(frames)
    df = df[df.v.astype(float).fillna(-1e30) >= -1.0]
    want_pd = df.groupby("k").agg(mn=("v", "min"), mx=("v", "max"),
                                  imn=("n", "min"), imx=("n", "max"))
    ks = np.asarray(got["k"])
    for i, k in enumerate(ks):
        np.testing.assert_allclose(float(got["mn"][i]),
                                   want_pd.loc[k, "mn"], rtol=1e-9)
        np.testing.assert_allclose(float(got["mx"][i]),
                                   want_pd.loc[k, "mx"], rtol=1e-9)
        assert int(got["imn"][i]) == int(want_pd.loc[k, "imn"])
        assert int(got["imx"][i]) == int(want_pd.loc[k, "imx"])


def test_minmax_partial_state_columns(rng):
    """Partial-only min/max stage emits [val, has] typed state columns the
    FINAL merge consumes (shuffle map side)."""
    batches = _batches(rng, 2, 400, null_frac=0.3)
    partial = _mm_plan(batches, modes=(AggMode.PARTIAL,))
    got = collect(partial)
    assert partial.metrics["stage_compiled"] == 1
    conf.enable_stage_compiler = False
    try:
        want = collect(_mm_plan(batches, modes=(AggMode.PARTIAL,)))
    finally:
        conf.enable_stage_compiler = True
    gd, wd = got.to_numpy(), want.to_numpy()
    assert set(gd.keys()) == set(wd.keys())
    # group order may differ (dense slots vs sort); compare sorted by key
    gk, wk = np.argsort(np.asarray(gd["k"])), np.argsort(np.asarray(wd["k"]))
    for name in gd:
        g = np.asarray(gd[name], dtype=object)[gk]
        w = np.asarray(wd[name], dtype=object)[wk]
        for a, b in zip(g, w):
            if b is None or a is None:
                assert (a is None) == (b is None), (name, a, b)
            elif isinstance(b, (bool, np.bool_)):
                assert bool(a) == bool(b), (name, a, b)
            else:
                np.testing.assert_allclose(float(a), float(b), rtol=1e-9)


def test_float_digit_plane_knob_precision(rng):
    """conf.float_sum_digit_planes is the precision policy: 6 planes
    (46-bit) tightens float sums by ~2^8 over the 5-plane default."""
    import jax.numpy as jnp

    n, R = 1 << 12, 1 << 10
    keys = jnp.asarray(rng.integers(0, R, n).astype(np.int32))
    valid = jnp.ones((n,), bool)
    fvals = jnp.asarray(rng.random(n) * 1e6 - 4e5)
    want = np.zeros(R)
    np.add.at(want, np.asarray(keys), np.asarray(fvals))
    old = conf.float_sum_digit_planes
    try:
        conf.float_sum_digit_planes = 6
        got6 = np.asarray(mxu_agg.grouped_sum(keys, fvals, valid, R))
        np.testing.assert_allclose(got6, want, rtol=1e-12, atol=1e-6)
        conf.float_sum_digit_planes = 5
        got5 = np.asarray(mxu_agg.grouped_sum(keys, fvals, valid, R))
        np.testing.assert_allclose(got5, want, rtol=4e-8, atol=1e-4)
    finally:
        conf.float_sum_digit_planes = old


def test_decimal_aggs_whole_stage(rng):
    """int64-backed decimal sum/avg/min ride the dense MXU path (exact
    int digit planes) and end in the streaming path's own finalize
    (ops/agg.finalize_sum / finalize_avg): both equal an integer
    reference, avg HALF_UP at Spark's scale + 4. Types are Spark's for a
    decimal(8,2) input; wide decimals (p>18, or an avg whose sum buffer
    is) keep the streaming path."""
    dec = T.decimal(8, 2)
    schema = T.Schema([T.Field("k", T.INT64), T.Field("d", dec)])
    calls = [AggCall("sum", (col("d"),), T.decimal(18, 2), "s"),
             AggCall("avg", (col("d"),), T.decimal(12, 6), "a"),
             AggCall("min", (col("d"),), dec, "mn"),
             AggCall("count", (col("d"),), T.INT64, "c")]
    batches, rows = [], []
    for _ in range(3):
        n = 400
        k = rng.integers(0, 50, n).astype(np.int64)
        d = rng.integers(-10**6, 10**6, n)
        ok = rng.random(n) > 0.2
        rows += [(int(a), int(b)) for a, b, v in zip(k, d, ok) if v]
        batches.append(ColumnBatch.from_numpy(
            {"k": k, "d": d}, schema, validity={"d": ok}, capacity=1024))
    node = MemorySourceExec(batches, schema)
    for mode in (AggMode.PARTIAL, AggMode.FINAL):
        node = AggExec(node, [col("k")], ["k"], calls, mode)
    got = collect(node)
    assert node.metrics["stage_compiled"] == 1
    conf.enable_stage_compiler = False
    try:
        node2 = MemorySourceExec(batches, schema)
        for mode in (AggMode.PARTIAL, AggMode.FINAL):
            node2 = AggExec(node2, [col("k")], ["k"], calls, mode)
        want = collect(node2)
    finally:
        conf.enable_stage_compiler = True
    gd, wd = got.to_numpy(), want.to_numpy()
    assert list(np.asarray(gd["k"])) == list(np.asarray(wd["k"]))
    for name in ("s", "a", "mn", "c"):
        assert [None if x is None else int(x) for x in gd[name]] == \
            [None if x is None else int(x) for x in wd[name]], name

    def half_up(num, den):       # Python ints: ties away from zero
        q, r = divmod(abs(num), den)
        return (q + (2 * r >= den)) * (1 if num >= 0 else -1)

    for i, key in enumerate(int(x) for x in gd["k"]):
        vals = [d for k, d in rows if k == key]
        assert int(gd["c"][i]) == len(vals)
        assert int(gd["s"][i]) == sum(vals)
        assert int(gd["mn"][i]) == min(vals)
        assert int(gd["a"][i]) == half_up(sum(vals) * 10 ** 4, len(vals))
