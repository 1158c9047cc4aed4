"""`q06core.py` with money typed as the specification types it, planned as
Spark plans it:

    select ss_item_sk item, sum(ss_ext_sales_price) total,
           count(ss_ext_sales_price) cnt, avg(ss_sales_price) avg_price
    from store_sales where ss_ext_sales_price > [MIN_PRICE]
    group by ss_item_sk

over decimal(7,2) columns. By Spark's result types `total` is decimal(17,2)
and `avg_price` decimal(11,6), but its optimizer (rule DecimalAggregates)
never aggregates decimals this narrow as decimals. `plan` is the physical
plan that rule leaves:

    sum(d)  ->  MakeDecimal(sum(UnscaledValue(d)), 17, 2)         p + 10 <= 18
    avg(d)  ->  cast(avg(UnscaledValue(d)) / 100.0 as decimal(11,6))
                                                                  p + 4 <= 15

a sum over longs, and a double avg over longs (Average's buffer for a long
is a double sum and a long count) that is cast to the decimal at the end.
Same plan shape as the twin: scan -> filter (a decimal comparison) -> partial
agg -> exchange on the key -> final agg, then the final aggregate's result
expressions as a projection; compared as a set keyed by `item`. Both sides
hold a decimal as its unscaled integer (cents for `total`, millionths for
`avg_price`).

`reference` follows that semantics and shares nothing with the plan or with
`blaze_tpu`: `total` and `cnt` in integer arithmetic (numpy int64) over the
frames of `datagen/tpcds_decimal.py` (money as nullable integer cents); the
avg in IEEE doubles as Spark computes it, sum / count, then / 100.0, then
the cast: Spark makes a BigDecimal of the double's shortest decimal string
(Scala's BigDecimal(double), Double.toString) and rounds it HALF_UP to scale
6. `total` and `cnt` are compared exactly; `avg_price` is a double's
rounding, so it comes back as a float column of millionths and is compared
at the configuration's `float_rtol`, which is set to admit one unit of the
sixth place and no more (configs/tpcds_sf1_decimal.json says why: an exact
tie at the seventh digit falls by the last bit of two double divisions, and
the chip's emulated f64 is not IEEE to the last bit).

Departures from Spark: a group's double sum of longs is taken as the integer
sum (equal while the sum stays under 2^53; here under 2^43); Python's repr of
a double is its shortest round-trip string, which Java's Double.toString is
from JDK 19 on (older JDKs print a digit more for a few doubles).
"""

from __future__ import annotations

from decimal import ROUND_HALF_UP, Decimal

import numpy as np
import pandas as pd

# the device plane of a decimal(7,2) is an int64, as the twin's double is 8
# bytes: hbm_roofline_share reads the same work in both
SCAN_COLUMNS = {
    "store_sales": {"ss_item_sk": 8, "ss_sales_price": 8,
                    "ss_ext_sales_price": 8},
}
# no ORDER BY in the query: both sides are ordered by these before comparing
ORDER_KEYS = ["item"]

MONEY = (7, 2)
TOTAL = (MONEY[0] + 10, MONEY[1])           # Sum's result type
AVG = (MONEY[0] + 4, MONEY[1] + 4)          # Average's result type


def _cents(text) -> int:
    """'100.00' -> 10000: the literal as an unscaled decimal(7,2)."""
    scaled = Decimal(str(text)).scaleb(MONEY[1])
    assert scaled == scaled.to_integral_value(), text
    return int(scaled)


def plan(paths: dict, config: dict, params: dict):
    from blaze_tpu.columnar import types as T
    from blaze_tpu.exprs import ir
    from blaze_tpu.exprs.ir import BinOp, col, lit
    from blaze_tpu.spark import plan_model as P

    money = T.decimal(*MONEY)
    total_t, avg_t = T.decimal(*TOTAL), T.decimal(*AVG)
    scan = P.scan(T.Schema([T.Field("ss_item_sk", T.INT64),
                            T.Field("ss_sales_price", money),
                            T.Field("ss_ext_sales_price", money)]),
                  [(paths["store_sales"], [])])
    # a decimal literal is its unscaled value under its type
    kept = P.filter_(scan, ir.Binary(
        BinOp.GT, col("ss_ext_sales_price"),
        ir.Literal(money, _cents(params["min_price"]))))
    aggs = [{"fn": "sum", "args": [ir.UnscaledValue(col("ss_ext_sales_price"))],
             "dtype": T.INT64, "name": "total_unscaled"},
            {"fn": "count", "args": [col("ss_ext_sales_price")],
             "dtype": T.INT64, "name": "cnt"},
            {"fn": "avg", "args": [ir.UnscaledValue(col("ss_sales_price"))],
             "dtype": T.FLOAT64, "name": "avg_unscaled"}]
    partial = P.hash_agg(kept, "partial", [col("ss_item_sk")], ["item"],
                         aggs, T.Schema([T.Field("item", T.INT64)]))
    exchanged = P.shuffle_exchange(partial, [col("item")],
                                   config["settings"]["exchange_width"])
    final = P.hash_agg(
        exchanged, "final", [col("ss_item_sk")], ["item"], aggs,
        T.Schema([T.Field("item", T.INT64),
                  T.Field("total_unscaled", T.INT64),
                  T.Field("cnt", T.INT64),
                  T.Field("avg_unscaled", T.FLOAT64)]))
    # the final aggregate's result expressions
    return P.project(
        final,
        [col("item"), ir.MakeDecimal(col("total_unscaled"), *TOTAL),
         col("cnt"),
         ir.Cast(ir.Binary(BinOp.DIV, col("avg_unscaled"),
                           lit(10.0 ** MONEY[1])), avg_t)],
        ["item", "total", "cnt", "avg_price"],
        T.Schema([T.Field("item", T.INT64), T.Field("total", total_t),
                  T.Field("cnt", T.INT64), T.Field("avg_price", avg_t)]))


def _ints(column) -> tuple:
    """(values with 0 for null, is-not-null) of a nullable integer column."""
    arr = column.array
    return (arr.to_numpy(dtype=np.int64, na_value=0),
            ~np.asarray(arr.isna()))


def _group_sums(keys: np.ndarray, values: np.ndarray, size: int) -> np.ndarray:
    """Per-key int64 sums by a sort and differences of an int64 prefix sum."""
    order = np.argsort(keys, kind="stable")
    prefix = np.concatenate([[0], np.cumsum(values[order], dtype=np.int64)])
    ends = np.searchsorted(keys[order], np.arange(size + 1), side="left")
    return prefix[ends[1:]] - prefix[ends[:-1]]


def double_to_decimal(value: float, scale: int) -> int:
    """Spark's cast(double as decimal(_, scale)), unscaled: the double's
    shortest decimal string, rounded HALF_UP at `scale`."""
    return int(Decimal(repr(float(value))).quantize(
        Decimal(1).scaleb(-scale), rounding=ROUND_HALF_UP).scaleb(scale))


def reference(frames: dict, config: dict, params: dict,
              float_type=np.float64) -> pd.DataFrame:
    """`float_type`: the precision the avg is computed in. Spark's is double;
    float32 is the reading that sets `float_rtol` from below (PERF.md)."""
    ss = frames["store_sales"]
    ext, ext_ok = _ints(ss.ss_ext_sales_price)
    price, price_ok = _ints(ss.ss_sales_price)
    keep = ext_ok & (ext > _cents(params["min_price"]))   # null: not kept
    item = ss.ss_item_sk.to_numpy()[keep].astype(np.int64)
    size = int(item.max()) + 1 if len(item) else 1
    cnt = np.bincount(item, minlength=size)
    total = _group_sums(item, ext[keep], size)
    priced = price_ok[keep]
    n_priced = np.bincount(item[priced], minlength=size)
    price_sum = _group_sums(item[priced], price[keep][priced], size)
    present = np.flatnonzero(cnt)
    n = n_priced[present]
    with np.errstate(invalid="ignore", divide="ignore"):
        units = (price_sum[present].astype(float_type) / n.astype(float_type)
                 / float_type(10.0 ** MONEY[1]))
    # no non-null price in the group: null (NaN in a float column)
    avg = np.array([double_to_decimal(u, AVG[1]) if k else np.nan
                    for u, k in zip(units, n)], np.float64)
    return pd.DataFrame({"item": present, "total": total[present],
                         "cnt": cnt[present], "avg_price": avg})
