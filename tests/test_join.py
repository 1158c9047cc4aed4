"""Join engine vs pandas oracle — all join types, nulls, duplicates, strings.

Mirrors the reference's SMJ test battery (sort_merge_join_exec.rs:1024+,
~15 cases incl. inner/left/right/full/semi/anti with nulls and small batch
chunking) plus BHJ build-side reversal (BlazeConverters.scala:420-434)."""

import re

import jax
import numpy as np
import pandas as pd
import pytest

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import ColumnBatch
from blaze_tpu.exprs import ir
from blaze_tpu.ops.basic import MemorySourceExec
from blaze_tpu.ops.join import (
    BroadcastNestedLoopJoinExec, JoinKey, JoinType, SortMergeJoinExec,
    _join_sort_keys, match_ranges, sort_batch_by_keys,
)
from blaze_tpu.runtime.executor import collect

LS = T.Schema([T.Field("lk", T.INT64), T.Field("lv", T.FLOAT64)])
RS = T.Schema([T.Field("rk", T.INT64), T.Field("rv", T.FLOAT64)])


def _mk(schema, k, v, validity=None, cap=None):
    names = schema.names()
    return ColumnBatch.from_numpy(
        {names[0]: np.asarray(k, np.int64), names[1]: np.asarray(v)},
        schema, validity=validity, capacity=cap)


def _df(batch):
    d = batch.to_numpy()
    return pd.DataFrame({k: [x for x in v] if not isinstance(v, np.ndarray)
                         else v for k, v in d.items()})


def _rows(df):
    out = []
    for t in df.itertuples(index=False):
        out.append(tuple(None if (isinstance(x, float) and np.isnan(x))
                         else x for x in t))
    return sorted(out, key=repr)


def _oracle(ldf, rdf, how):
    m = ldf.merge(rdf, left_on="lk", right_on="rk", how=how)
    return m


@pytest.mark.parametrize("jt,how", [
    (JoinType.INNER, "inner"),
    (JoinType.LEFT, "left"),
    (JoinType.RIGHT, "right"),
    (JoinType.FULL, "outer"),
])
def test_join_types_with_dups(rng, jt, how):
    lk = rng.integers(0, 20, 150)
    rk = rng.integers(0, 20, 80)
    left = _mk(LS, lk, rng.random(150))
    right = _mk(RS, rk, rng.random(80))
    j = SortMergeJoinExec(MemorySourceExec([left], LS),
                          MemorySourceExec([right], RS),
                          [JoinKey(0, 0)], jt)
    out = collect(j)
    got = _rows(_df(out))
    want = _rows(_oracle(_df(left), _df(right), how))
    assert got == want


@pytest.mark.parametrize("jt", [JoinType.INNER, JoinType.LEFT, JoinType.FULL])
def test_join_with_null_keys(rng, jt):
    n = 60
    lk = rng.integers(0, 8, n)
    lnull = rng.random(n) > 0.7
    rk = rng.integers(0, 8, 40)
    rnull = rng.random(40) > 0.7
    left = _mk(LS, lk, rng.random(n), validity={"lk": ~lnull})
    right = _mk(RS, rk, rng.random(40), validity={"rk": ~rnull})
    j = SortMergeJoinExec(MemorySourceExec([left], LS),
                          MemorySourceExec([right], RS),
                          [JoinKey(0, 0)], jt)
    got = _rows(_df(collect(j)))
    how = {"inner": "inner", "left": "left", "full": "outer"}[jt.value]
    # pandas merge matches NaN keys to each other; Spark does not — build a
    # null-correct oracle by joining non-null keys and appending unmatched
    ldf, rdf = _df(left), _df(right)
    lm, rm = ldf.dropna(subset=["lk"]), rdf.dropna(subset=["rk"])
    inner = lm.merge(rm, left_on="lk", right_on="rk", how="inner")
    parts = [inner]
    rkeys, lkeys = set(rm["rk"]), set(lm["lk"])
    if how in ("left", "outer"):
        un = ldf[[pd.isna(k) or k not in rkeys for k in ldf["lk"]]].copy()
        un["rk"] = np.nan
        un["rv"] = np.nan
        parts.append(un)
    if how == "outer":
        un = rdf[[pd.isna(k) or k not in lkeys for k in rdf["rk"]]].copy()
        un.insert(0, "lk", np.nan)
        un.insert(1, "lv", np.nan)
        parts.append(un)
    want = _rows(pd.concat(parts, ignore_index=True))
    assert got == want


def test_semi_anti_existence(rng):
    lk = rng.integers(0, 30, 100)
    rk = rng.integers(0, 15, 50)
    left = _mk(LS, lk, rng.random(100))
    right = _mk(RS, rk, rng.random(50))
    rset = set(rk.tolist())

    semi = collect(SortMergeJoinExec(
        MemorySourceExec([left], LS), MemorySourceExec([right], RS),
        [JoinKey(0, 0)], JoinType.LEFT_SEMI))
    want_semi = sorted(k for k in lk if k in rset)
    assert sorted(np.asarray(semi.to_numpy()["lk"]).tolist()) == want_semi

    anti = collect(SortMergeJoinExec(
        MemorySourceExec([left], LS), MemorySourceExec([right], RS),
        [JoinKey(0, 0)], JoinType.LEFT_ANTI))
    want_anti = sorted(k for k in lk if k not in rset)
    assert sorted(np.asarray(anti.to_numpy()["lk"]).tolist()) == want_anti

    ex = collect(SortMergeJoinExec(
        MemorySourceExec([left], LS), MemorySourceExec([right], RS),
        [JoinKey(0, 0)], JoinType.EXISTENCE))
    d = ex.to_numpy()
    for k, e in zip(np.asarray(d["lk"]), np.asarray(d["exists"])):
        assert bool(e) == (int(k) in rset)


def test_build_side_left(rng):
    # BHJ with build side = left: same results, left++right column order
    lk = rng.integers(0, 10, 70)
    rk = rng.integers(0, 10, 90)
    left = _mk(LS, lk, rng.random(70))
    right = _mk(RS, rk, rng.random(90))
    for jt, how in [(JoinType.INNER, "inner"), (JoinType.LEFT, "left"),
                    (JoinType.RIGHT, "right")]:
        j = SortMergeJoinExec(MemorySourceExec([left], LS),
                              MemorySourceExec([right], RS),
                              [JoinKey(0, 0)], jt, build_is_left=True)
        got = _rows(_df(collect(j)))
        want = _rows(_oracle(_df(left), _df(right), how))
        assert got == want, jt


def test_multi_key_and_string_key(rng):
    ls = T.Schema([T.Field("k1", T.INT64), T.Field("ks", T.STRING),
                   T.Field("lv", T.FLOAT64)])
    rs = T.Schema([T.Field("k1", T.INT64), T.Field("ks", T.STRING),
                   T.Field("rv", T.FLOAT64)])
    n, m = 80, 60
    l1 = rng.integers(0, 5, n)
    lsx = [f"g{i}" for i in rng.integers(0, 4, n)]
    r1 = rng.integers(0, 5, m)
    rsx = [f"g{i}" for i in rng.integers(0, 4, m)]
    left = ColumnBatch.from_numpy(
        {"k1": l1.astype(np.int64), "ks": lsx, "lv": rng.random(n)}, ls)
    right = ColumnBatch.from_numpy(
        {"k1": r1.astype(np.int64), "ks": rsx, "rv": rng.random(m)}, rs)
    j = SortMergeJoinExec(MemorySourceExec([left], ls),
                          MemorySourceExec([right], rs),
                          [JoinKey(0, 0), JoinKey(1, 1)], JoinType.INNER)
    out = _df(collect(j))
    ldf = pd.DataFrame({"k1": l1, "ks": lsx, "lv": left.to_numpy()["lv"]})
    rdf = pd.DataFrame({"k1": r1, "ks": rsx, "rv": right.to_numpy()["rv"]})
    want = ldf.merge(rdf, on=["k1", "ks"], how="inner")
    assert len(out) == len(want)
    out2 = out.copy()
    out2["ks"] = [s.decode() for s in out["ks"]]
    got = sorted(map(tuple, out2[["k1", "ks", "lv", "rv"]].itertuples(
        index=False)))
    wn = want.rename(columns={"k1_x": "k1"}) if "k1_x" in want else want
    wanted = sorted(map(tuple, wn[["k1", "ks", "lv", "rv"]].itertuples(
        index=False)))
    for g, w in zip(got, wanted):
        assert g[0] == w[0] and g[1] == w[1]
        np.testing.assert_allclose(g[2:], w[2:], rtol=1e-9)


def test_null_safe_equal(rng):
    left = _mk(LS, [1, 2, 3], [1.0, 2.0, 3.0],
               validity={"lk": np.array([True, False, True])})
    right = _mk(RS, [1, 9, 9], [10.0, 20.0, 30.0],
                validity={"rk": np.array([True, False, False])})
    j = SortMergeJoinExec(MemorySourceExec([left], LS),
                          MemorySourceExec([right], RS),
                          [JoinKey(0, 0, null_safe=True)], JoinType.INNER)
    d = collect(j).to_numpy()
    pairs = sorted(zip([x for x in d["lv"]], [x for x in d["rv"]]))
    # null key matches both null right keys; 1 matches 1
    assert pairs == [(1.0, 10.0), (2.0, 20.0), (2.0, 30.0)]


def test_streamed_probe_batches(rng):
    batches = [
        _mk(LS, rng.integers(0, 12, 40), rng.random(40)) for _ in range(4)]
    right = _mk(RS, rng.integers(0, 12, 30), rng.random(30))
    j = SortMergeJoinExec(MemorySourceExec(batches, LS),
                          MemorySourceExec([right], RS),
                          [JoinKey(0, 0)], JoinType.FULL)
    got = _rows(_df(collect(j)))
    ldf = pd.concat([_df(b) for b in batches], ignore_index=True)
    want = _rows(_oracle(ldf, _df(right), "outer"))
    assert got == want


def test_empty_sides(rng):
    left = _mk(LS, rng.integers(0, 5, 20), rng.random(20))
    empty_r = MemorySourceExec([], RS)
    # inner with empty build -> no rows
    out = collect(SortMergeJoinExec(MemorySourceExec([left], LS), empty_r,
                                    [JoinKey(0, 0)], JoinType.INNER))
    assert int(out.num_rows) == 0
    # left outer with empty build -> all left rows, right nulls
    out = collect(SortMergeJoinExec(
        MemorySourceExec([left], LS), MemorySourceExec([], RS),
        [JoinKey(0, 0)], JoinType.LEFT))
    assert int(out.num_rows) == 20
    assert all(v is None for v in out.to_numpy()["rv"])


def test_inner_join_filter(rng):
    left = _mk(LS, [1, 1, 2], [1.0, 5.0, 2.0])
    right = _mk(RS, [1, 1, 2], [3.0, 9.0, 1.0])
    j = SortMergeJoinExec(
        MemorySourceExec([left], LS), MemorySourceExec([right], RS),
        [JoinKey(0, 0)], JoinType.INNER,
        join_filter=ir.Binary(ir.BinOp.LT, ir.col("lv"), ir.col("rv")))
    d = collect(j).to_numpy()
    pairs = sorted(zip([x for x in d["lv"]], [x for x in d["rv"]]))
    assert pairs == [(1.0, 3.0), (1.0, 9.0), (2.0, 2.0)][:2] + [(5.0, 9.0)]


def test_bnlj_cross_and_condition(rng):
    left = _mk(LS, [1, 2], [1.0, 2.0])
    right = _mk(RS, [7, 8, 9], [0.5, 1.5, 2.5])
    cross = collect(BroadcastNestedLoopJoinExec(
        MemorySourceExec([left], LS), MemorySourceExec([right], RS),
        JoinType.INNER))
    assert int(cross.num_rows) == 6
    cond = collect(BroadcastNestedLoopJoinExec(
        MemorySourceExec([left], LS), MemorySourceExec([right], RS),
        JoinType.INNER,
        condition=ir.Binary(ir.BinOp.GT, ir.col("lv"), ir.col("rv"))))
    d = cond.to_numpy()
    pairs = sorted(zip([x for x in d["lv"]], [x for x in d["rv"]]))
    assert pairs == [(1.0, 0.5), (2.0, 0.5), (2.0, 1.5)]
    louter = collect(BroadcastNestedLoopJoinExec(
        MemorySourceExec([left], LS), MemorySourceExec([right], RS),
        JoinType.LEFT,
        condition=ir.Binary(ir.BinOp.GT, ir.col("lv"),
                            ir.Binary(ir.BinOp.MUL, ir.col("rv"),
                                      ir.lit(100.0)))))
    d = louter.to_numpy()
    assert int(louter.num_rows) == 2
    assert all(v is None for v in d["rv"])


def test_bnlj_chunked_expansion(rng):
    """BNLJ must expand the cartesian product in bounded left chunks, not
    one |L|x|R| batch (VERDICT r2 weak-5). With a tiny batch_size the
    600x400 product forces many chunks; results must match pandas."""
    import pandas as pd

    from blaze_tpu.config import conf

    old = conf.batch_size
    conf.batch_size = 64  # chunk = 64*16//400 = 2 left rows per expansion
    try:
        left = _mk(LS, rng.integers(0, 5, 600), rng.random(600))
        right = _mk(RS, rng.integers(0, 5, 400), rng.random(400))
        cond = ir.Binary(ir.BinOp.LT, ir.col("lv"), ir.col("rv"))
        j = BroadcastNestedLoopJoinExec(
            MemorySourceExec([left], LS), MemorySourceExec([right], RS),
            JoinType.INNER, condition=cond)
        out = collect(j)
        ldf, rdf = _df(left), _df(right)
        want = ldf.merge(rdf, how="cross")
        want = want[want.lv < want.rv]
        assert int(out.num_rows) == len(want)
        got_sum = float(np.sum(np.asarray(out.to_numpy()["lv"], np.float64)))
        np.testing.assert_allclose(got_sum, want["lv"].sum(), rtol=1e-9)
    finally:
        conf.batch_size = old


def test_bnlj_existence(rng):
    """BNLJ EXISTENCE: left rows + exists flag from condition matches."""
    left = _mk(LS, [1, 2, 3], [0.1, 0.9, 0.5])
    right = _mk(RS, [7, 8], [0.45, 0.2])
    cond = ir.Binary(ir.BinOp.LT, ir.col("lv"), ir.col("rv"))
    j = BroadcastNestedLoopJoinExec(
        MemorySourceExec([left], LS), MemorySourceExec([right], RS),
        JoinType.EXISTENCE, condition=cond)
    out = collect(j).to_numpy()
    # lv=0.1 < 0.45 -> True; 0.9 -> False; 0.5 -> False (0.45, 0.2 both <=)
    by = dict(zip(np.asarray(out["lk"]), np.asarray(out["exists"])))
    assert by == {1: True, 2: False, 3: False}
    # empty right side: all False
    j2 = BroadcastNestedLoopJoinExec(
        MemorySourceExec([left], LS), MemorySourceExec([], RS),
        JoinType.EXISTENCE, condition=cond)
    out2 = collect(j2).to_numpy()
    assert list(np.asarray(out2["exists"])) == [False, False, False]


# ---------------------------------------------------------------------------
# runtime BHJ build-size fallback (ref broadcast_join_exec.rs:188-249)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("jt,how", [
    (JoinType.INNER, "inner"),
    (JoinType.LEFT_SEMI, None),
    (JoinType.LEFT_ANTI, None),
])
def test_bhj_runtime_size_fallback(rng, jt, how):
    """An oversized build side flips BroadcastJoinExec into bounded
    chunked-build mode at RUNTIME (enable_bhj_fallbacks_to_smj): results
    stay identical to the resident path and the switch is observable as
    the bhj_fallback_to_smj metric."""
    from blaze_tpu.config import conf
    from blaze_tpu.ops.join import BroadcastJoinExec

    n_build, n_probe = 5000, 700
    bk = rng.integers(0, 400, n_build).astype(np.int64)
    bv = rng.random(n_build)
    pk = rng.integers(0, 500, n_probe).astype(np.int64)
    pv = rng.random(n_probe)
    right = _mk(RS, bk, bv)          # build side (right)
    left = _mk(LS, pk, pv)           # probe side

    def run(threshold):
        old = conf.bhj_fallback_rows_threshold
        conf.bhj_fallback_rows_threshold = threshold
        try:
            j = BroadcastJoinExec(MemorySourceExec([left], LS),
                                  MemorySourceExec([right], RS),
                                  [JoinKey(0, 0)], jt)
            out = _df(collect(j))
            return out, j.metrics["bhj_fallback_to_smj"]
        finally:
            conf.bhj_fallback_rows_threshold = old

    resident, m0 = run(10_000_000)
    chunked, m1 = run(1024)          # build 5000 rows > 1024 -> fallback
    assert m0 == 0
    assert m1 == 1
    assert _rows(resident) == _rows(chunked)
    if how:  # cross-check inner against pandas
        want = _oracle(pd.DataFrame({"lk": pk, "lv": pv}),
                       pd.DataFrame({"rk": bk, "rv": bv}), how)
        assert _rows(chunked) == _rows(want)


# ---------------------------------------------------------------------------
# match_ranges against a plain numpy reference
# ---------------------------------------------------------------------------

def _ints(lo, hi):
    return lambda rng, n: rng.integers(lo, hi, n).astype(np.int64)


def _strings(rng, n):
    # widths 1..9: the two sides usually differ in their widest string
    return ["k" * int(w) for w in rng.integers(1, 10, n)]


# name -> (key dtypes, per-side (rows, capacity, null share), per-column
# draws for build and probe, null_safe)
MATCH_CASES = {
    "empty_build": ([T.INT64], (0, 16, 0), (30, None, 0),
                    [_ints(0, 5)], [_ints(0, 5)], [False]),
    "every_key_duplicated": ([T.INT64], (40, None, 0), (60, None, 0),
                             [_ints(0, 3)], [_ints(0, 3)], [False]),
    "no_match_at_all": ([T.INT64], (20, None, 0), (40, None, 0),
                        [_ints(0, 20)], [_ints(100, 140)], [False]),
    "null_build_keys": ([T.INT64], (50, None, 0.4), (70, None, 0),
                        [_ints(0, 8)], [_ints(0, 8)], [False]),
    "null_probe_keys": ([T.INT64], (50, None, 0), (70, None, 0.4),
                        [_ints(0, 8)], [_ints(0, 8)], [False]),
    "null_safe_keys": ([T.INT64], (50, None, 0.3), (70, None, 0.3),
                       [_ints(0, 8)], [_ints(0, 8)], [True]),
    "two_column_keys": ([T.INT64, T.INT64], (60, None, 0.1), (90, None, 0.1),
                        [_ints(0, 4), _ints(0, 3)],
                        [_ints(0, 4), _ints(0, 3)], [False, True]),
    "string_key": ([T.STRING], (40, None, 0.1), (60, None, 0.1),
                   [_strings], [_strings], [False]),
    # from_numpy pads with zeros, and 0 is a live key on both sides
    "padding_rows_both_sides": ([T.INT64], (20, 64, 0), (50, 128, 0),
                                [_ints(0, 4)], [_ints(0, 4)], [False]),
    "build_capacity_larger": ([T.INT64], (200, 256, 0), (10, 16, 0),
                              [_ints(0, 30)], [_ints(0, 30)], [False]),
}


def _match_side(rng, dtypes, size, draws):
    n, cap, null_share = size
    schema = T.Schema([T.Field(f"k{i}", dt) for i, dt in enumerate(dtypes)])
    data = {f.name: draw(rng, n) for f, draw in zip(schema, draws)}
    validity = ({f.name: rng.random(n) >= null_share for f in schema}
                if null_share else None)
    return ColumnBatch.from_numpy(data, schema, capacity=cap,
                                  validity=validity)


def _key_rows(batch):
    d = batch.to_numpy()
    return list(zip(*[list(d[f.name]) for f in batch.schema]))


def _keys_equal(a, b, null_safe):
    return all((x is None and y is None and ns) or
               (x is not None and y is not None and x == y)
               for x, y, ns in zip(a, b, null_safe))


@pytest.mark.parametrize("case", sorted(MATCH_CASES))
def test_match_ranges_against_numpy(rng, case):
    dtypes, bsize, psize, bdraws, pdraws, null_safe = MATCH_CASES[case]
    build = _match_side(rng, dtypes, bsize, bdraws)
    probe = _match_side(rng, dtypes, psize, pdraws)
    cols = list(range(len(dtypes)))
    flags = [b.validity is not None or p.validity is not None
             for b, p in zip(build.columns, probe.columns)]
    build_sorted = sort_batch_by_keys(
        build, _join_sort_keys(build, cols, null_safe, flags, 0))
    start, cnt, bmatch = map(np.asarray, jax.jit(
        lambda b, p: match_ranges(b, p, cols, cols, null_safe, flags))(
            build_sorted, probe))
    assert cnt.shape == start.shape == (probe.capacity,)
    assert bmatch.shape == (build.capacity,)

    bkeys, pkeys = _key_rows(build_sorted), _key_rows(probe)
    assert sorted(bkeys, key=repr) == sorted(_key_rows(build), key=repr)
    for i, pk in enumerate(pkeys):
        want = [j for j, bk in enumerate(bkeys)
                if _keys_equal(pk, bk, null_safe)]
        assert cnt[i] == len(want), (i, pk)
        # `start` of an unmatched probe row is unspecified
        if want:
            assert list(range(start[i], start[i] + cnt[i])) == want, (i, pk)
    for j, bk in enumerate(bkeys):
        assert bmatch[j] == sum(_keys_equal(pk, bk, null_safe)
                                for pk in pkeys), (j, bk)
    # padding probe rows match nothing
    assert not cnt[len(pkeys):].any() and not start[len(pkeys):].any()


@pytest.mark.parametrize("case", ["null_safe_keys", "string_key"])
def test_match_ranges_lowers_to_sorts_and_scans_only(rng, case):
    """On the TPU a gather or a scatter by computed index costs 7-26 ns an
    element at full batch capacity, a sort of the batch ~9 ms and a scan
    next to nothing (PERF.md, PR 29): the match finds its runs by scans, and
    this fails the day someone indexes by a run id again."""
    dtypes, bsize, psize, bdraws, pdraws, null_safe = MATCH_CASES[case]
    build = _match_side(rng, dtypes, bsize, bdraws)
    probe = _match_side(rng, dtypes, psize, pdraws)
    text = jax.jit(lambda b, p: match_ranges(
        b, p, [0], [0], null_safe, [True])).lower(build, probe).as_text()
    ops = re.findall(r"stablehlo\.(\w+)", text)
    assert ops.count("sort") == 3, ops
    assert not [op for op in ops if "gather" in op or "scatter" in op], ops
