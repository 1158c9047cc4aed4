"""Money as the specification types it, decimal(7,2), served on the normal
path and held to references, not to another path of the program:

(a) the benchmark's `q06core_dec` plan (Spark's plan for decimal(7,2): a sum
    over the unscaled longs made a decimal(17,2), a double avg cast to
    decimal(11,6)) over `datagen/tpcds_decimal` tables through run_plan
    equals `queries/q06core_dec.reference`: sums and counts as integers,
    exactly, the avg within the configuration's `float_rtol`; nothing
    refused, and a third query after two warm-ups compiles nothing;
(b) a q3-shaped plan under decimal money, both join arms, planned the same
    way: decimal through join payloads, a two-phase sum of unscaled longs
    made a decimal(17,2), ORDER BY that decimal desc, LIMIT: against an
    integer reference;
(c) operator cases for aggregates TYPED decimal (what Spark keeps decimal:
    sum from p = 9, avg from p = 12): avg's HALF_UP ties, null groups, the
    state's type across partial -> final, intermediates past 2^63, sums past
    their precision, comparisons across scales, decimal literals, what is
    refused at plan time. Computing such an avg in double, or flooring it,
    fails a case here.
"""

import importlib.util
import json
import os
from decimal import Decimal

import jax
import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.arrow_io import batch_from_arrow
from blaze_tpu.columnar.batch import ColumnBatch
from blaze_tpu.config import conf
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.ir import BinOp, col, lit
from blaze_tpu.ops.agg import (AggCall, AggExec, AggMode, avg_sum_dtype,
                               state_fields)
from blaze_tpu.ops.basic import FilterExec, MemorySourceExec
from blaze_tpu.ops.parquet import _stat_prune
from blaze_tpu.runtime import compile_service, trace
from blaze_tpu.runtime.executor import collect
from blaze_tpu.spark import plan_model as P
from blaze_tpu.spark.local_runner import run_plan

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
ROWS = 50_000
SEED = 11
MONEY = T.decimal(7, 2)


def _load(rel: str):
    spec = importlib.util.spec_from_file_location(
        "dec_" + rel.replace("/", "_").replace(".", "_"),
        os.path.join(BENCH, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*rel: str):
    with open(os.path.join(BENCH, *rel)) as fh:
        return json.load(fh)


def half_up(num: int, den: int) -> int:
    """Python ints: round(num / den), ties away from zero."""
    q, r = divmod(abs(num), den)
    return (q + (2 * r >= den)) * (1 if num >= 0 else -1)


@pytest.fixture
def one_chip(monkeypatch):
    real = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a, **k: real(*a, **k)[:1])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    config = _json("configs", "tpcds_sf1_decimal.json")
    paths, frames = _load("datagen/tpcds_decimal.py").generate(
        config, SEED, str(tmp_path_factory.mktemp("tpcds_sf1_decimal")), ROWS)
    return config, paths, frames


# -- (a) the cell's own plan against its own reference ----------------------


def test_q06core_dec_equals_its_reference_and_warm_compiles_nothing(
        served, one_chip):
    config, paths, frames = served
    compare = _load("harness/compare.py")
    evidence = _load("harness/evidence.py")
    query = _load("queries/q06core_dec.py")
    (entry,) = _json("traffic", "q06core_dec_loop1.json")["mix"]
    assert entry["query"] == "q06core_dec"
    settings, params = config["settings"], entry["params"]
    rtol = config["guarantees"]["float_rtol"]
    want = query.reference(frames, config, params)
    # the reference has what the comparison must bite on: groups without a
    # priced row (null avg); sums and counts are integers, so compare.diff
    # holds them exactly, and only the avg (a double's rounding) is a float
    assert want.avg_price.isna().any() and len(want) > 1000
    assert want.total.dtype == np.int64 and want.cnt.dtype.kind == "i"
    assert want.avg_price.dtype == np.float64 and 0 < rtol < 1e-7

    def run():
        info: dict = {}
        got = compare.to_frame(run_plan(
            query.plan(paths, config, params),
            num_partitions=settings["exchange_width"],
            mesh_exchange=settings["mesh_exchange"], run_info=info))
        assert got.total.dtype == np.int64 and got.cnt.dtype == np.int64
        units = np.array([np.nan if x is None else x for x in
                          got.sort_values("item").avg_price], np.float64)
        # never more than one unit of the sixth place off
        assert np.nanmax(np.abs(units - want.sort_values(
            "item").avg_price.to_numpy())) <= 1
        return (compare.diff(got, want, rtol, query.ORDER_KEYS),
                evidence.refusals(info, 1, settings["exchange_width"]))

    for _ in range(2):
        assert run() == (None, [])
    before = compile_service.TELEMETRY.snapshot()
    assert before.get("compile_count", 0) > 0
    assert run() == (None, [])
    after = compile_service.TELEMETRY.snapshot()
    assert after.get("compile_count", 0) == before.get("compile_count", 0)
    # the type the sums were done in, as seg_sum saw its arrays: the money
    # sum and the merged counts add integers, the avg's sum doubles
    sums = after["seg_sums"] - before["seg_sums"]
    ints = after["seg_int_sums"] - before["seg_int_sums"]
    assert 0 < ints < sums


def test_the_reference_is_sparks_double_avg_cast_half_up(served):
    """The reference against Python ints, group by group: totals exact; the
    avg a double rounded HALF_UP at the sixth place through its shortest
    decimal string, so it is the exact quotient's HALF_UP rounding or, where
    the double fell on the other side of a tie, its neighbour."""
    config, _, frames = served
    query = _load("queries/q06core_dec.py")
    to_dec = query.double_to_decimal
    assert to_dec(0.0003125, 6) == 313 and to_dec(-0.0003125, 6) == -313
    # 1.0000005 * 1e6 is 1000000.4999999999 in doubles: the string decides
    assert to_dec(1.0000005, 6) == 1000001
    assert to_dec(2.5e-7, 6) == 0 and to_dec(108.3447615, 6) == 108344762
    want = query.reference(frames, config, {"min_price": "100.00"})
    ss = frames["store_sales"]
    kept = ss[ss.ss_ext_sales_price.notna()
              & (ss.ss_ext_sales_price.fillna(0) > 10000)]
    by_item = kept.groupby("ss_item_sk").agg(
        ext=("ss_ext_sales_price", lambda s: [int(x) for x in s.dropna()]),
        prices=("ss_sales_price", lambda s: [int(x) for x in s.dropna()]))
    rounded_up = 0
    for _, row in want.head(2000).iterrows():
        ext, prices = by_item.loc[int(row["item"])]
        assert int(row["total"]) == sum(ext) and int(row["cnt"]) == len(ext)
        if not prices:
            assert np.isnan(row["avg_price"])
            continue
        exact = half_up(sum(prices) * 10 ** 4, len(prices))
        assert abs(int(row["avg_price"]) - exact) <= 1
        rounded_up += (sum(prices) * 10 ** 4) // len(prices) != int(
            row["avg_price"])
    assert rounded_up > 100     # a floored avg is not this reference


# -- (b) q3-shaped, decimal through joins, sort keys and the host sort -------


def _q3_plan(paths, arm: str, width: int, month: int, manufact_below: int):
    def join(left, right, lkey, rkey, schema):
        if arm == "broadcast":
            return P.bhj(left, P.broadcast_exchange(right), [col(lkey)],
                         [col(rkey)], "inner", "right", schema)
        return P.smj(P.shuffle_exchange(left, [col(lkey)], width),
                     P.shuffle_exchange(right, [col(rkey)], width),
                     [col(lkey)], [col(rkey)], "inner", schema)

    def pruned(child, fields):
        return P.project(child, [col(f.name) for f in fields],
                         [f.name for f in fields], T.Schema(fields))

    total_t = T.decimal(17, 2)
    ss_f = [T.Field("ss_sold_date_sk", T.INT64),
            T.Field("ss_item_sk", T.INT64),
            T.Field("ss_ext_sales_price", MONEY)]
    dd_f = [T.Field("d_date_sk", T.INT64), T.Field("d_year", T.INT32),
            T.Field("d_moy", T.INT32)]
    it_f = [T.Field("i_item_sk", T.INT64), T.Field("i_brand_id", T.INT32),
            T.Field("i_brand", T.STRING), T.Field("i_manufact_id", T.INT32)]
    ss = P.scan(T.Schema(ss_f), [(paths["store_sales"], [])])
    dd = pruned(P.filter_(
        P.scan(T.Schema(dd_f), [(paths["date_dim"], [])]),
        ir.Binary(BinOp.EQ, col("d_moy"), lit(month))), dd_f[:2])
    it = pruned(P.filter_(
        P.scan(T.Schema(it_f), [(paths["item"], [])]),
        ir.Binary(BinOp.LT, col("i_manufact_id"), lit(manufact_below))),
        it_f[:3])
    j1 = pruned(join(ss, dd, "ss_sold_date_sk", "d_date_sk",
                     T.Schema(ss_f + dd_f[:2])),
                [dd_f[1], ss_f[1], ss_f[2]])
    j2 = pruned(join(j1, it, "ss_item_sk", "i_item_sk",
                     T.Schema([dd_f[1], ss_f[1], ss_f[2]] + it_f[:3])),
                [dd_f[1], ss_f[2], it_f[1], it_f[2]])
    keys = [col("d_year"), col("i_brand_id"), col("i_brand")]
    names = ["d_year", "brand_id", "brand"]
    key_fields = [T.Field("d_year", T.INT32), T.Field("brand_id", T.INT32),
                  T.Field("brand", T.STRING)]
    # Spark's plan for sum(decimal(7,2)): the unscaled longs summed, the
    # sum made a decimal(17,2) in the final aggregate's result expressions
    aggs = [{"fn": "sum", "args": [ir.UnscaledValue(
        col("ss_ext_sales_price"))], "dtype": T.INT64, "name": "sum_u"}]
    partial = P.hash_agg(j2, "partial", keys, names, aggs,
                         T.Schema(key_fields))
    exchanged = P.shuffle_exchange(partial, [col(n) for n in names], width)
    final = P.hash_agg(exchanged, "final", keys, names, aggs, T.Schema(
        key_fields + [T.Field("sum_u", T.INT64)]))
    made = P.project(
        final, [col(n) for n in names] + [ir.MakeDecimal(col("sum_u"), 17, 2)],
        names + ["sum_agg"],
        T.Schema(key_fields + [T.Field("sum_agg", total_t)]))
    ordered = P.sort(made, [(col("d_year"), True, True),
                             (col("sum_agg"), False, False),
                             (col("brand_id"), True, True)])
    return P.limit(ordered, 100, True)


def _q3_reference(frames, month: int, manufact_below: int) -> pd.DataFrame:
    """Integers only: cents summed per group by pandas on Int64."""
    ss, dd, it = frames["store_sales"], frames["date_dim"], frames["item"]
    dated = ss[ss.ss_sold_date_sk.notna()].copy()
    dated["d_date_sk"] = dated.ss_sold_date_sk.astype(np.int64)
    rows = dated.merge(dd[dd.d_moy == month], on="d_date_sk").merge(
        it[it.i_manufact_id < manufact_below], left_on="ss_item_sk",
        right_on="i_item_sk")
    out = rows.groupby(["d_year", "i_brand_id", "i_brand"])[
        "ss_ext_sales_price"].agg(lambda s: s.sum(min_count=1)).reset_index()
    out.columns = ["d_year", "brand_id", "brand", "sum_agg"]
    assert str(out.sum_agg.dtype) == "Int64"
    out = out.sort_values(["d_year", "sum_agg", "brand_id"],
                          ascending=[True, False, True], na_position="last")
    return out.head(100).reset_index(drop=True)


@pytest.mark.parametrize("arm", ["broadcast", "sort_merge"])
def test_q3_shaped_under_decimal_money_equals_an_integer_reference(
        served, one_chip, arm):
    config, paths, frames = served
    compare = _load("harness/compare.py")
    evidence = _load("harness/evidence.py")
    width = config["settings"]["exchange_width"]
    want = _q3_reference(frames, 11, 200)
    assert len(want) == 100 and want.sum_agg.notna().all()
    info: dict = {}
    out = run_plan(_q3_plan(paths, arm, width, 11, 200), num_partitions=width,
                   mesh_exchange="auto", run_info=info)
    assert out.schema.field("sum_agg").dtype == T.decimal(17, 2)
    wrong = compare.diff(compare.to_frame(out), want, 0.0, None)
    assert wrong is None
    assert evidence.refusals(info, 1, width) == []


# -- (c) operator cases ------------------------------------------------------


def _agg(batches, schema, calls, stage_compiler: bool):
    old = conf.enable_stage_compiler
    conf.enable_stage_compiler = stage_compiler
    try:
        node = MemorySourceExec(batches, schema)
        for mode in (AggMode.PARTIAL, AggMode.FINAL):
            node = AggExec(node, [col("k")], ["k"], calls, mode)
        out = collect(node).to_numpy()
    finally:
        conf.enable_stage_compiler = old
    return {int(k): {name: (None if out[name][i] is None
                            else int(out[name][i]))
                     for name in out if name != "k"}
            for i, k in enumerate(out["k"])}


def _money_calls(dec=MONEY):
    return [AggCall("sum", (col("d"),),
                    T.decimal(min(38, dec.precision + 10), dec.scale), "s"),
            AggCall("count", (col("d"),), T.INT64, "c"),
            AggCall("avg", (col("d"),),
                    T.decimal(dec.precision + 4, dec.scale + 4), "a")]


# group -> the cents of its rows (None = a null row)
TIES = {
    # 1 cent over 32 rows: 0.0003125 -> 0.000313, not 0.000312
    1: [1] + [0] * 31,
    # the negative tie goes away from zero: -0.000313
    2: [-1] + [0] * 31,
    # odd sums over 32 rows are all ties at the seventh digit; a double's
    # cents / 100 lies under some of them
    3: [201] + [0] * 31, 4: [29] + [0] * 31, 5: [829] + [0] * 31,
    6: [1_999_999] + [0] * 31, 7: [-1_234_567] + [0] * 31,
    # a third: rounds down, and up
    8: [1, 0, 0], 9: [2, 0, 0],
    # no non-null value: null avg, null sum, count 0
    10: [None, None, None],
    # nulls are skipped, not counted
    11: [None, 5, None, 6],
}


@pytest.mark.parametrize("stage_compiler", [False, True],
                         ids=["streaming", "whole_stage"])
def test_avg_is_half_up_at_the_seventh_digit_and_null_without_values(
        stage_compiler):
    schema = T.Schema([T.Field("k", T.INT64), T.Field("d", MONEY)])
    ks, ds, oks = [], [], []
    for k, cents in TIES.items():
        for c in cents:
            ks.append(k)
            ds.append(0 if c is None else c)
            oks.append(c is not None)
    order = np.random.default_rng(3).permutation(len(ks))
    half = len(ks) // 2
    batches = [ColumnBatch.from_numpy(
        {"k": np.array(ks, np.int64)[ix], "d": np.array(ds, np.int64)[ix]},
        schema, validity={"d": np.array(oks)[ix]}, capacity=1024)
        for ix in (order[:half], order[half:])]
    got = _agg(batches, schema, _money_calls(), stage_compiler)
    floored = doubled = 0
    for k, cents in TIES.items():
        vals = [c for c in cents if c is not None]
        if not vals:
            assert got[k] == {"s": None, "c": 0, "a": None}
            continue
        exact = half_up(sum(vals) * 10 ** 4, len(vals))
        assert got[k] == {"s": sum(vals), "c": len(vals), "a": exact}, k
        floored += (sum(vals) * 10 ** 4) // len(vals) != exact
        as_double = sum(vals) / 100.0 / len(vals) * 1e6
        doubled += int(np.sign(as_double)
                       * np.floor(abs(as_double) + 0.5)) != exact
    assert got[1]["a"] == 313 and got[2]["a"] == -313
    # the cases bite: each shortcut is wrong on one of them
    assert floored > 0 and doubled > 0


def test_the_state_crosses_partial_to_final_at_the_sums_type():
    """avg(decimal(7,2)) is planned decimal(11,6); its sum state is typed
    decimal(17,2) (Spark's buffer) and holds cents, in the partial's output
    schema and through a final built from that output alone."""
    avg = AggCall("avg", (col("d"),), T.decimal(11, 6), "a")
    assert avg_sum_dtype(avg.dtype) == T.decimal(17, 2)
    assert [f.dtype for f in state_fields(avg, 0)] == [T.decimal(17, 2),
                                                       T.INT64]
    schema = T.Schema([T.Field("k", T.INT64), T.Field("d", MONEY)])
    batch = ColumnBatch.from_numpy(
        {"k": np.array([1, 1, 1, 2], np.int64),
         "d": np.array([10001, 10002, 10002, 7], np.int64)}, schema)
    partial = AggExec(MemorySourceExec([batch], schema), [col("k")], ["k"],
                      [avg], AggMode.PARTIAL)
    assert [f.dtype for f in partial.schema.fields[1:]] == [
        T.decimal(17, 2), T.INT64]
    state = collect(partial)
    pulled = state.to_numpy()
    sums = dict(zip((int(k) for k in pulled["k"]),
                    (int(s) for s in pulled[partial.schema.names()[1]])))
    assert sums == {1: 30005, 2: 7}       # cents, not millionths
    # two copies of the state through a final: sums and counts double
    final = AggExec(MemorySourceExec([state, state], partial.schema),
                    [col("k")], ["k"], [avg], AggMode.FINAL)
    assert final.schema.field("a").dtype == T.decimal(11, 6)
    out = collect(final).to_numpy()
    got = dict(zip((int(k) for k in out["k"]), (int(a) for a in out["a"])))
    assert got == {1: half_up(60010 * 10 ** 4, 6), 2: 70000}
    assert got[1] == 100016667


@pytest.mark.parametrize("case", ["wide_state", "compact_state"])
def test_an_avg_whose_scaled_sum_passes_two_to_the_63(case):
    if case == "wide_state":
        # decimal(12,2) near its bound: the buffer decimal(22,2) is limb
        # planes, the result decimal(16,6) an int64
        dec = T.decimal(12, 2)
        schema = T.Schema([T.Field("k", T.INT64), T.Field("d", dec)])
        rng = np.random.default_rng(5)
        vals = rng.integers(999_999_000_000, 999_999_999_999, 9000)
        vals[::7] *= -1
        keys = rng.integers(0, 3, 9000).astype(np.int64)
        got = _agg([ColumnBatch.from_numpy({"k": keys, "d": vals}, schema,
                                           capacity=16384)],
                   schema, _money_calls(dec)[1:], False)
        for k in range(3):
            mine = [int(v) for v in vals[keys == k]]
            assert abs(sum(mine)) * 10 ** 4 > 2 ** 63
            assert got[k] == {"c": len(mine),
                              "a": half_up(sum(mine) * 10 ** 4, len(mine))}
        return
    # decimal(8,2): the buffer decimal(18,2) is an int64; a state as 6e7
    # rows would leave it, handed to the final directly
    avg = AggCall("avg", (col("d"),), T.decimal(12, 6), "a")
    sfields = state_fields(avg, 0)
    assert sfields[0].dtype == T.decimal(18, 2)
    sschema = T.Schema([T.Field("k", T.INT64)] + sfields)
    sums = [5_000_000_000_000_001, -5_000_000_000_000_001, 10 ** 17]
    counts = [60_000_000, 60_000_000, 3]
    state = ColumnBatch.from_numpy(
        {"k": np.arange(3, dtype=np.int64), sfields[0].name:
         np.array(sums, np.int64), sfields[1].name:
         np.array(counts, np.int64)}, sschema)
    final = AggExec(MemorySourceExec([state], sschema), [col("k")], ["k"],
                    [avg], AggMode.FINAL)
    out = collect(final).to_numpy()
    assert sums[0] * 10 ** 4 > 2 ** 63
    assert int(out["a"][0]) == half_up(sums[0] * 10 ** 4, counts[0])
    assert int(out["a"][1]) == -int(out["a"][0])
    # 10^17 / 3 at scale 6 has 21 digits: past decimal(12,6), so null
    assert out["a"][2] is None


def test_a_sum_past_its_precision_is_null():
    """decimal(8,2) sums are decimal(18,2): 1.2e18 unscaled fits the int64
    and not the type, so Spark (ANSI off) gives null; 9.9e17 stays."""
    total = AggCall("sum", (col("d"),), T.decimal(18, 2), "s")
    sfields = state_fields(total, 0)
    sschema = T.Schema([T.Field("k", T.INT64)] + sfields)
    state = ColumnBatch.from_numpy(
        {"k": np.array([0, 0, 1, 1, 2, 2], np.int64),
         sfields[0].name: np.array([6 * 10 ** 17, 6 * 10 ** 17,
                                    -6 * 10 ** 17, -6 * 10 ** 17,
                                    9 * 10 ** 17, 9 * 10 ** 16], np.int64),
         sfields[1].name: np.ones(6, bool)}, sschema)
    final = AggExec(MemorySourceExec([state], sschema), [col("k")], ["k"],
                    [total], AggMode.FINAL)
    out = collect(final).to_numpy()
    got = dict(zip((int(k) for k in out["k"]), out["s"]))
    assert got[0] is None and got[1] is None
    assert int(got[2]) == 99 * 10 ** 16


@pytest.mark.parametrize("literal,cents", [
    (lit(100), 10000),                         # int32, scale 0
    (lit(Decimal("100")), 10000),              # decimal(3,0)
    (lit(Decimal("100.00")), 10000),           # decimal(5,2)
    (lit(Decimal("100.5")), 10050),            # decimal(4,1)
    (lit(Decimal("99.999")), 9999.9),          # scale 3: finer than the column
    (lit(10000, MONEY), 10000),                # the unscaled value, typed
    (lit(10 ** 12), 10 ** 14),                 # int64: aligned on limb planes
    (lit(100.25), 10025),                      # a double: both sides as double
])
def test_a_decimal_column_against_literals_of_other_scales(literal, cents):
    schema = T.Schema([T.Field("d", MONEY)])
    values = np.array([0, 9999, 10000, 10001, 10049, 10050, 10051, 99999,
                       -10000, 5], np.int64)
    valid = np.array([True] * 9 + [False])
    batch = ColumnBatch.from_numpy({"d": values}, schema,
                                   validity={"d": valid})
    for op, py in ((BinOp.GT, lambda a: a > cents),
                   (BinOp.LE, lambda a: a <= cents),
                   (BinOp.EQ, lambda a: a == cents)):
        kept = collect(FilterExec(MemorySourceExec([batch], schema),
                                  [ir.Binary(op, col("d"), literal)]))
        got = sorted(int(x) for x in kept.to_numpy()["d"])
        assert got == sorted(int(v) for v in values[:9] if py(int(v))), op


def test_a_decimal_literal_is_typed_by_its_digits():
    assert lit(Decimal("100.00")) == ir.Literal(T.decimal(5, 2), 10000)
    assert lit(Decimal("-0.001")) == ir.Literal(T.decimal(3, 3), -1)
    assert lit(Decimal("1E+3")) == ir.Literal(T.decimal(4, 0), 1000)
    assert lit(Decimal("100"), MONEY) == ir.Literal(MONEY, 10000)
    for bad in (lambda: lit(Decimal("1.005"), MONEY),       # not exact
                lambda: lit(Decimal("123456.00"), MONEY),   # past precision
                lambda: lit(Decimal("NaN")),
                lambda: lit(Decimal("1.5"), T.INT64)):
        with pytest.raises(TypeError):
            bad()


@pytest.mark.parametrize("fn,in_type,planned", [
    ("avg", MONEY, MONEY),                   # the result type of the input
    ("avg", MONEY, T.decimal(11, 2)),        # scale not + 4
    ("avg", MONEY, T.FLOAT64),               # Spark would cast the input
    ("avg", T.INT64, T.decimal(11, 6)),
    ("sum", MONEY, MONEY),                   # precision not + 10
    ("sum", MONEY, T.decimal(17, 6)),        # a scale-2 sum under scale 6
    ("sum", T.FLOAT64, T.decimal(17, 2)),
])
def test_a_type_spark_never_plans_is_refused_at_plan_time(fn, in_type,
                                                         planned):
    schema = T.Schema([T.Field("k", T.INT64), T.Field("d", in_type)])
    source = MemorySourceExec([ColumnBatch.empty(schema, 1024)], schema)
    with pytest.raises(TypeError, match="planned as|Spark types"):
        AggExec(source, [col("k")], ["k"],
                [AggCall(fn, (col("d"),), planned, "x")], AggMode.PARTIAL)


def test_a_state_under_another_type_than_the_plans_is_refused():
    """The old avg typed its sum state as the RESULT (decimal(11,6)) and
    filled it with cents; a final that meets such a state says so."""
    avg = AggCall("avg", (col("d"),), T.decimal(11, 6), "a")
    p = state_fields(avg, 0)[0].name.rsplit(".", 1)[0]
    mislabelled = T.Schema([T.Field("k", T.INT64),
                            T.Field(f"{p}.sum", T.decimal(11, 6)),
                            T.Field(f"{p}.count", T.INT64)])
    source = MemorySourceExec([ColumnBatch.empty(mislabelled, 1024)],
                              mislabelled)
    with pytest.raises(TypeError, match="arrives as decimal\\(11,6\\)"):
        AggExec(source, [col("k")], ["k"], [avg], AggMode.FINAL)


def test_row_group_pruning_reads_a_decimal_literal_at_its_scale():
    stats = {"d": (Decimal("0.00"), Decimal("200.00"))}
    above = ir.Binary(BinOp.GT, col("d"), lit(50000, MONEY))    # > 500.00
    inside = ir.Binary(BinOp.GT, col("d"), lit(10000, MONEY))   # > 100.00
    assert _stat_prune(above, stats) is True
    assert _stat_prune(inside, stats) is False
    assert _stat_prune(ir.Binary(BinOp.LT, col("d"), lit(1, MONEY)),
                       stats) is False                          # < 0.01


def test_the_decimal128_decode_is_a_span_of_its_own():
    """One `decimal_decode` span a decimal column of a record batch,
    carrying its rows and whether it is wide; none without a decimal."""
    saved = conf.trace_enabled
    trace.reset()
    conf.trace_enabled = True
    try:
        rb = pa.record_batch({
            "k": pa.array([1, 2, 3], pa.int64()),
            "a": pa.array([Decimal("1.25"), None, Decimal("-0.07")],
                          pa.decimal128(7, 2)),
            "w": pa.array([Decimal("1e20"), Decimal("-3"), None],
                          pa.decimal128(25, 0))})
        batch = batch_from_arrow(rb)
        batch_from_arrow(rb.select(["k"]))
        spans = [r for r in trace.TRACE.snapshot()
                 if r["type"] == "span" and r["kind"] == "decimal_decode"]
    finally:
        conf.trace_enabled = saved
        trace.reset()
    assert "decimal_decode" in trace.SPAN_KINDS
    assert [(sp["attrs"]["rows"], sp["attrs"]["wide"]) for sp in spans] == [
        (3, False), (3, True)]
    out = batch.to_numpy()
    assert list(out["a"]) == [125, None, -7]          # unscaled, scale 2
    assert list(out["w"]) == [10 ** 20, -3, None]


@pytest.mark.parametrize("dtype,tally", [
    (np.int64, {"sums": 1, "int_sums": 1}),
    (np.float64, {"sums": 1, "int_sums": 0}),
    (np.bool_, {"sums": 0, "int_sums": 0}),
], ids=["integers", "doubles", "flags"])
def test_seg_sum_tallies_the_dtype_it_is_handed(dtype, tally):
    """The counters behind `decimal_reduction_share` read the array seg_sum
    adds up, not what an operator says of it: money cast to double before
    the reduction shows as a float sum."""
    from blaze_tpu.ops import segment as seg

    layout = seg.group_layout(ColumnBatch.from_numpy(
        {"k": np.array([1, 1, 2, 2], np.int64)},
        T.Schema([T.Field("k", T.INT64)]), capacity=4), [0])
    with seg.count_forms() as forms:
        seg.seg_sum(jax.numpy.asarray(np.ones(4, dtype)), layout,
                    jax.numpy.ones((4,), jax.numpy.bool_))
    assert {k: forms[k] for k in tally} == tally and forms["scan"] == 1


def test_make_decimal_nulls_a_long_past_its_precision():
    """Spark, ANSI off: MakeDecimal(long, p, s) is null where the long has
    more than p digits."""
    from blaze_tpu.exprs.compiler import compile_expr

    schema = T.Schema([T.Field("u", T.INT64)])
    batch = ColumnBatch.from_numpy(
        {"u": np.array([99_999, -99_999, 100_000, -100_000, 0], np.int64)},
        schema, validity={"u": np.array([True, True, True, True, False])},
        capacity=8)
    made = compile_expr(ir.MakeDecimal(col("u"), 5, 2), schema)(batch)
    assert made.dtype == T.decimal(5, 2)
    assert list(np.asarray(made.valid_mask())[:5]) == [
        True, True, False, False, False]
    assert list(np.asarray(made.data)[:2]) == [99_999, -99_999]
