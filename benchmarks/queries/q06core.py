"""BASELINE.json config 2, the core of TPC-DS query 6's aggregate:

    select ss_item_sk item, sum(ss_ext_sales_price) total,
           count(ss_ext_sales_price) cnt, avg(ss_sales_price) avg_price
    from store_sales where ss_ext_sales_price > [MIN_PRICE]
    group by ss_item_sk

scan -> filter -> partial agg -> exchange on the key -> final agg; no join
and no ORDER BY, so the result is compared as a set keyed by `item`.
`reference` is numpy over the generated frame and shares nothing with the
plan.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

SCAN_COLUMNS = {
    "store_sales": {"ss_item_sk": 8, "ss_sales_price": 8,
                    "ss_ext_sales_price": 8},
}
# no ORDER BY in the query: both sides are ordered by these before comparing
ORDER_KEYS = ["item"]


def plan(paths: dict, config: dict, params: dict):
    from blaze_tpu.columnar import types as T
    from blaze_tpu.exprs import ir
    from blaze_tpu.exprs.ir import BinOp, col, lit
    from blaze_tpu.spark import plan_model as P

    scan = P.scan(T.Schema([T.Field("ss_item_sk", T.INT64),
                            T.Field("ss_sales_price", T.FLOAT64),
                            T.Field("ss_ext_sales_price", T.FLOAT64)]),
                  [(paths["store_sales"], [])])
    kept = P.filter_(scan, ir.Binary(BinOp.GT, col("ss_ext_sales_price"),
                                     lit(float(params["min_price"]))))
    aggs = [{"fn": "sum", "args": [col("ss_ext_sales_price")],
             "dtype": T.FLOAT64, "name": "total"},
            {"fn": "count", "args": [col("ss_ext_sales_price")],
             "dtype": T.INT64, "name": "cnt"},
            {"fn": "avg", "args": [col("ss_sales_price")],
             "dtype": T.FLOAT64, "name": "avg_price"}]
    partial = P.hash_agg(kept, "partial", [col("ss_item_sk")], ["item"],
                         aggs, T.Schema([T.Field("item", T.INT64)]))
    exchanged = P.shuffle_exchange(partial, [col("item")],
                                   config["settings"]["exchange_width"])
    return P.hash_agg(
        exchanged, "final", [col("ss_item_sk")], ["item"], aggs,
        T.Schema([T.Field("item", T.INT64), T.Field("total", T.FLOAT64),
                  T.Field("cnt", T.INT64), T.Field("avg_price", T.FLOAT64)]))


def reference(frames: dict, config: dict, params: dict) -> pd.DataFrame:
    ss = frames["store_sales"]
    ext = ss.ss_ext_sales_price.to_numpy()
    keep = ext > params["min_price"]          # NaN compares false
    item = ss.ss_item_sk.to_numpy()[keep]
    price = ss.ss_sales_price.to_numpy()[keep]
    priced = ~np.isnan(price)
    size = int(item.max()) + 1 if len(item) else 1
    cnt = np.bincount(item, minlength=size)
    total = np.bincount(item, weights=ext[keep], minlength=size)
    n_priced = np.bincount(item[priced], minlength=size)
    with np.errstate(invalid="ignore", divide="ignore"):
        avg = np.bincount(item[priced], weights=price[priced],
                          minlength=size) / n_priced   # no price: null
    present = np.flatnonzero(cnt)
    return pd.DataFrame({"item": present, "total": total[present],
                         "cnt": cnt[present], "avg_price": avg[present]})
