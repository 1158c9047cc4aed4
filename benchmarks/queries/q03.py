"""TPC-DS query 3 as published:

    select dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
           sum(ss_ext_sales_price) sum_agg
    from date_dim dt, store_sales, item
    where dt.d_date_sk = store_sales.ss_sold_date_sk
      and store_sales.ss_item_sk = item.i_item_sk
      and item.i_manufact_id = [MANUFACT] and dt.d_moy = [MONTH]
    group by dt.d_year, item.i_brand, item.i_brand_id
    order by dt.d_year, sum_agg desc, brand_id
    limit 100

`plan` is the physical plan Catalyst gives it: both dimension filters
pushed below their joins, a column-pruning Project after each join,
partial agg -> exchange on the grouping keys -> final agg, sort + limit on
top. The configuration's `settings.join` says whether the two joins are
broadcast hash joins or shuffle exchange + sort-merge joins
(autoBroadcastJoinThreshold=-1). (Catalyst's inferred isnotnull filters on
the join keys are left out: the inner joins drop null keys themselves.)
`reference` is numpy/pandas over the generated frames and shares nothing
with the plan.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

# bytes per row the scans must read, for the whole-query byte roofline
SCAN_COLUMNS = {
    "store_sales": {"ss_sold_date_sk": 8, "ss_item_sk": 8,
                    "ss_ext_sales_price": 8},
    "date_dim": {"d_date_sk": 8, "d_year": 4, "d_moy": 4},
    "item": {"i_item_sk": 8, "i_brand_id": 4, "i_brand": 16,
             "i_manufact_id": 4},
}
# the ORDER BY is total (brand_id determines brand): compare as produced
ORDER_KEYS = None


def plan(paths: dict, config: dict, params: dict):
    from blaze_tpu.columnar import types as T
    from blaze_tpu.exprs import ir
    from blaze_tpu.exprs.ir import BinOp, col, lit
    from blaze_tpu.spark import plan_model as P

    width = config["settings"]["exchange_width"]

    def join(left, right, lkey, rkey, schema):
        if config["settings"]["join"] == "broadcast":
            return P.bhj(left, P.broadcast_exchange(right), [col(lkey)],
                         [col(rkey)], "inner", "right", schema)
        return P.smj(P.shuffle_exchange(left, [col(lkey)], width),
                     P.shuffle_exchange(right, [col(rkey)], width),
                     [col(lkey)], [col(rkey)], "inner", schema)

    def pruned(child, fields):
        return P.project(child, [col(f.name) for f in fields],
                         [f.name for f in fields], T.Schema(fields))

    ss_f = [T.Field("ss_sold_date_sk", T.INT64),
            T.Field("ss_item_sk", T.INT64),
            T.Field("ss_ext_sales_price", T.FLOAT64)]
    dd_f = [T.Field("d_date_sk", T.INT64), T.Field("d_year", T.INT32),
            T.Field("d_moy", T.INT32)]
    it_f = [T.Field("i_item_sk", T.INT64), T.Field("i_brand_id", T.INT32),
            T.Field("i_brand", T.STRING), T.Field("i_manufact_id", T.INT32)]

    ss = P.scan(T.Schema(ss_f), [(paths["store_sales"], [])])
    dd = pruned(P.filter_(
        P.scan(T.Schema(dd_f), [(paths["date_dim"], [])]),
        ir.Binary(BinOp.EQ, col("d_moy"), lit(int(params["month"])))),
        dd_f[:2])
    it = pruned(P.filter_(
        P.scan(T.Schema(it_f), [(paths["item"], [])]),
        ir.Binary(BinOp.EQ, col("i_manufact_id"),
                  lit(int(params["manufact"])))), it_f[:3])

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
    aggs = [{"fn": "sum", "args": [col("ss_ext_sales_price")],
             "dtype": T.FLOAT64, "name": "sum_agg"}]
    partial = P.hash_agg(j2, "partial", keys, names, aggs,
                         T.Schema(key_fields))
    exchanged = P.shuffle_exchange(partial, [col(n) for n in names], width)
    final = P.hash_agg(exchanged, "final", keys, names, aggs, T.Schema(
        key_fields + [T.Field("sum_agg", T.FLOAT64)]))
    # (expr, ascending, nulls_first): Spark's defaults, asc nulls first and
    # desc nulls last
    ordered = P.sort(final, [(col("d_year"), True, True),
                             (col("sum_agg"), False, False),
                             (col("brand_id"), True, True)])
    return P.limit(ordered, 100, True)


def reference(frames: dict, config: dict, params: dict) -> pd.DataFrame:
    ss, dd, it = frames["store_sales"], frames["date_dim"], frames["item"]
    base = int(dd.d_date_sk.iloc[0])
    in_month = (dd.d_moy.to_numpy() == params["month"])
    by_manufact = np.zeros(len(it) + 1, bool)
    by_manufact[it.i_item_sk.to_numpy()] = (
        it.i_manufact_id.to_numpy() == params["manufact"])
    date = ss.ss_sold_date_sk.to_numpy()
    dated = ~np.isnan(date)
    date_ix = np.where(dated, date, base).astype(np.int64) - base
    keep = dated & in_month[date_ix] & by_manufact[ss.ss_item_sk.to_numpy()]
    rows = pd.DataFrame({
        "d_year": dd.d_year.to_numpy()[date_ix[keep]],
        "item": ss.ss_item_sk.to_numpy()[keep],
        "price": ss.ss_ext_sales_price.to_numpy()[keep]})
    brands = it.set_index("i_item_sk")
    rows["brand_id"] = brands.i_brand_id.reindex(rows["item"]).to_numpy()
    rows["brand"] = brands.i_brand.reindex(rows["item"]).to_numpy()
    out = rows.groupby(["d_year", "brand_id", "brand"])["price"].agg(
        lambda s: s.sum(min_count=1)).reset_index()
    out.columns = ["d_year", "brand_id", "brand", "sum_agg"]
    out = out.sort_values(["d_year", "sum_agg", "brand_id"],
                          ascending=[True, False, True], na_position="last")
    return out.head(100).reset_index(drop=True)
