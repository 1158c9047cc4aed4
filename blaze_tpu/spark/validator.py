"""Query-level correctness gate: BASELINE configs as query shapes, each run
through the FULL driver path (tagging -> conversion -> stage splitting ->
multi-stage execution) against a pandas oracle, across BOTH join configs.

Ref: the reference's north-star gate is the TPC-DS validator matrix —
every query x {BHJ, forced-SMJ (autoBroadcastJoinThreshold=-1)} x spark
version, executed with the plugin and diffed against vanilla answers
(dev/run-tpcds-test:52-57, .github/workflows/tpcds.yml:92-147). This module
is that gate for this engine: TPC-DS-shaped queries over generated
store_sales/date_dim/item parquet, one command (`python validate.py`),
per-query diffs on failure.
"""

from __future__ import annotations

import dataclasses
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq

from blaze_tpu.columnar import types as T
from blaze_tpu.exprs import ir
from blaze_tpu.exprs.ir import BinOp, col, lit
from blaze_tpu.spark import plan_model as P
from blaze_tpu.spark.local_runner import run_plan

# ---------------------------------------------------------------------------
# TPC-DS-shaped data
# ---------------------------------------------------------------------------

SS_SCHEMA = T.Schema([
    T.Field("ss_sold_date_sk", T.INT64),
    T.Field("ss_item_sk", T.INT64),
    T.Field("ss_customer_sk", T.INT64),
    T.Field("ss_store_sk", T.INT64),
    T.Field("ss_quantity", T.INT32),
    T.Field("ss_sales_price", T.FLOAT64),
    T.Field("ss_ext_sales_price", T.FLOAT64),
])
DD_SCHEMA = T.Schema([
    T.Field("d_date_sk", T.INT64),
    T.Field("d_year", T.INT32),
    T.Field("d_moy", T.INT32),
])
ITEM_SCHEMA = T.Schema([
    T.Field("i_item_sk", T.INT64),
    T.Field("i_category_id", T.INT32),
    T.Field("i_category", T.STRING),
    T.Field("i_current_price", T.FLOAT64),
])

_CATEGORIES = ["Books", "Children", "Electronics", "Home", "Jewelry",
               "Men", "Music", "Shoes", "Sports", "Women"]


def _zipf_keys(rng, n, lo, hi, a=1.3):
    """Zipf-skewed keys over [lo, hi) — real TPC-DS fact keys are skewed
    (hot items/customers); uniform keys hide collision-heavy paths."""
    z = rng.zipf(a, n)
    return lo + (z - 1) % (hi - lo)


def _with_nulls(rng, values, frac=0.05):
    """~frac nulls (pandas: float + NaN; parquet writes real nulls)."""
    v = values.astype(np.float64)
    v[rng.random(len(v)) < frac] = np.nan
    return v


def generate_tables(tmpdir: str, rows: int = 20_000, seed: int = 7):
    """Write store_sales/date_dim/item parquet; returns (paths, frames).

    Data realism (ref: the reference validates against real TPC-DS data,
    tpcds.yml:122-126): ~5% nulls in every nullable measure column, a
    string dim column (i_category) for LIKE/substr filters, and
    Zipf-skewed fact keys (hot items dominate, as in real sales data).
    """
    rng = np.random.default_rng(seed)
    n_dd, n_item = 730, 400
    ss = pd.DataFrame({
        "ss_sold_date_sk": rng.integers(0, n_dd, rows),
        "ss_item_sk": _zipf_keys(rng, rows, 1, n_item + 1),
        "ss_customer_sk": _with_nulls(
            rng, rng.integers(1, 500, rows), 0.03),
        "ss_store_sk": rng.integers(1, 8, rows),
        "ss_quantity": _with_nulls(
            rng, rng.integers(1, 100, rows), 0.05),
        "ss_sales_price": _with_nulls(
            rng, np.round(rng.random(rows) * 200, 2), 0.05),
        "ss_ext_sales_price": _with_nulls(
            rng, np.round(rng.random(rows) * 1000, 2), 0.05),
    })
    dd = pd.DataFrame({
        "d_date_sk": np.arange(n_dd),
        "d_year": (1998 + np.arange(n_dd) // 365).astype(np.int32),
        "d_moy": ((np.arange(n_dd) // 30) % 12 + 1).astype(np.int32),
    })
    item = pd.DataFrame({
        "i_item_sk": np.arange(1, n_item + 1),
        "i_category_id": rng.integers(1, 11, n_item).astype(np.int32),
        "i_category": [_CATEGORIES[i % len(_CATEGORIES)]
                       for i in range(n_item)],
        "i_current_price": np.round(rng.random(n_item) * 90 + 10, 2),
    })
    schemas = {"store_sales": SS_SCHEMA, "date_dim": DD_SCHEMA,
               "item": ITEM_SCHEMA}
    paths = {}
    for name, df in (("store_sales", ss), ("date_dim", dd), ("item", item)):
        path = f"{tmpdir}/{name}.parquet"
        pq.write_table(_to_arrow_typed(df, schemas[name]), path,
                       row_group_size=65536)
        paths[name] = path
    return paths, {"store_sales": ss, "date_dim": dd, "item": item}


def _to_arrow_typed(df: pd.DataFrame, schema: T.Schema) -> pa.Table:
    """pandas -> arrow with the DECLARED column types: float-with-NaN
    columns become nullable int64/int32 where the schema says integer
    (pandas can't hold null ints natively)."""
    from blaze_tpu.columnar.arrow_io import dtype_to_arrow

    arrays = []
    for f in schema.fields:
        col = df[f.name]
        at = dtype_to_arrow(f.dtype)
        if pa.types.is_integer(at) and col.dtype.kind == "f":
            mask = col.isna().to_numpy()
            vals = np.where(mask, 0, col.to_numpy()).astype(np.int64)
            arrays.append(pa.array(vals, type=at, mask=mask))
        else:
            arrays.append(pa.array(col, type=at))
    return pa.Table.from_arrays(
        arrays, schema=pa.schema(
            [pa.field(f.name, dtype_to_arrow(f.dtype), f.nullable)
             for f in schema.fields]))


# ---------------------------------------------------------------------------
# query catalogue (BASELINE configs 1-5 shapes)
# ---------------------------------------------------------------------------


def _join(left, right, lkeys, rkeys, how, schema, mode, build="right"):
    """BHJ or forced-SMJ — the matrix axis (ref: tpcds.yml runs every query
    with and without autoBroadcastJoinThreshold=-1)."""
    if mode == "bhj":
        return P.bhj(left, P.broadcast_exchange(right), lkeys, rkeys, how,
                     build, schema)
    lx = P.shuffle_exchange(left, lkeys, 4)
    rx = P.shuffle_exchange(right, rkeys, 4)
    return P.smj(lx, rx, lkeys, rkeys, how, schema)


def q1_scan_filter_project(paths, frames, mode):
    """BASELINE config 1: scan + filter + project."""
    sc = P.scan(SS_SCHEMA, [(paths["store_sales"], [])])
    flt = P.filter_(sc, ir.Binary(
        BinOp.AND,
        ir.Binary(BinOp.LE, col("ss_quantity"), lit(50)),
        ir.Binary(BinOp.GT, col("ss_sales_price"), lit(10.0))))
    proj = P.project(
        flt,
        [col("ss_item_sk"),
         ir.Binary(BinOp.MUL, ir.Cast(col("ss_quantity"), T.FLOAT64),
                   col("ss_sales_price"))],
        ["item", "amount"],
        T.Schema([T.Field("item", T.INT64), T.Field("amount", T.FLOAT64)]))
    srt = P.sort(proj, [(col("item"), True, True),
                        (col("amount"), True, True)])

    def oracle():
        ss = frames["store_sales"]
        f = ss[(ss.ss_quantity <= 50) & (ss.ss_sales_price > 10.0)]
        out = pd.DataFrame({
            "item": f.ss_item_sk,
            "amount": f.ss_quantity.astype(np.float64) * f.ss_sales_price})
        return out.sort_values(["item", "amount"]).reset_index(drop=True)

    return srt, oracle


def q2_q06_core_agg(paths, frames, mode):
    """BASELINE config 2: scan + two-phase grouped agg (q06 core)."""
    sc = P.scan(SS_SCHEMA, [(paths["store_sales"], [])])
    flt = P.filter_(sc, ir.Binary(BinOp.GT, col("ss_ext_sales_price"),
                                  lit(100.0)))
    aggs = [{"fn": "sum", "args": [col("ss_ext_sales_price")],
             "dtype": T.FLOAT64, "name": "total"},
            {"fn": "count", "args": [col("ss_ext_sales_price")],
             "dtype": T.INT64, "name": "cnt"},
            {"fn": "avg", "args": [col("ss_sales_price")],
             "dtype": T.FLOAT64, "name": "avg_price"}]
    partial = P.hash_agg(flt, "partial", [col("ss_item_sk")], ["item"],
                         aggs, T.Schema([T.Field("item", T.INT64)]))
    x = P.shuffle_exchange(partial, [col("item")], 4)
    final = P.hash_agg(
        x, "final", [col("ss_item_sk")], ["item"], aggs,
        T.Schema([T.Field("item", T.INT64), T.Field("total", T.FLOAT64),
                  T.Field("cnt", T.INT64), T.Field("avg_price", T.FLOAT64)]))
    srt = P.sort(final, [(col("item"), True, True)])

    def oracle():
        ss = frames["store_sales"]
        f = ss[ss.ss_ext_sales_price > 100.0]
        g = f.groupby("ss_item_sk").agg(
            total=("ss_ext_sales_price", lambda s: s.sum(min_count=1)),
            cnt=("ss_ext_sales_price", "count"),
            avg_price=("ss_sales_price", "mean")).reset_index()
        g = g.rename(columns={"ss_item_sk": "item"})
        return g.sort_values("item").reset_index(drop=True)

    return srt, oracle


def q3_join_agg_sort(paths, frames, mode):
    """BASELINE config 3: q03 — ss x date_dim, grouped sum, sort desc."""
    ss = P.scan(SS_SCHEMA, [(paths["store_sales"], [])])
    dd = P.scan(DD_SCHEMA, [(paths["date_dim"], [])])
    ddf = P.filter_(dd, ir.Binary(BinOp.EQ, col("d_moy"), lit(11)))
    jschema = T.Schema(list(SS_SCHEMA.fields) + list(DD_SCHEMA.fields))
    j = _join(ss, ddf, [col("ss_sold_date_sk")], [col("d_date_sk")],
              "inner", jschema, mode)
    aggs = [{"fn": "sum", "args": [col("ss_ext_sales_price")],
             "dtype": T.FLOAT64, "name": "sumsales"}]
    partial = P.hash_agg(j, "partial",
                         [col("ss_item_sk"), col("d_year")],
                         ["item", "year"], aggs,
                         T.Schema([T.Field("item", T.INT64),
                                   T.Field("year", T.INT32)]))
    x = P.shuffle_exchange(partial, [col("item")], 4)
    final = P.hash_agg(
        x, "final", [col("ss_item_sk"), col("d_year")], ["item", "year"],
        aggs, T.Schema([T.Field("item", T.INT64), T.Field("year", T.INT32),
                        T.Field("sumsales", T.FLOAT64)]))
    srt = P.sort(final, [(col("sumsales"), False, True),
                         (col("item"), True, True)])

    def oracle():
        ssd, ddd = frames["store_sales"], frames["date_dim"]
        m = ssd.merge(ddd[ddd.d_moy == 11], left_on="ss_sold_date_sk",
                      right_on="d_date_sk")
        g = m.groupby(["ss_item_sk", "d_year"])["ss_ext_sales_price"].agg(
            lambda s: s.sum(min_count=1)).reset_index()
        g.columns = ["item", "year", "sumsales"]
        # nulls-first to match the plan's (desc, nulls_first) spec
        return g.sort_values(["sumsales", "item"],
                             ascending=[False, True],
                             na_position="first").reset_index(drop=True)

    return srt, oracle


def q4_repartition_sort(paths, frames, mode):
    """BASELINE config 4: repartition across 8 + per-partition sort +
    global order (q01 WITH-clause shape)."""
    sc = P.scan(SS_SCHEMA, [(paths["store_sales"], [])])
    proj = P.project(
        sc, [col("ss_customer_sk"), col("ss_store_sk"),
             col("ss_ext_sales_price")],
        ["customer", "store", "price"],
        T.Schema([T.Field("customer", T.INT64), T.Field("store", T.INT64),
                  T.Field("price", T.FLOAT64)]))
    x = P.shuffle_exchange(proj, [col("customer")], 8)
    srt = P.sort(x, [(col("customer"), True, True),
                     (col("store"), True, True),
                     (col("price"), False, True)])

    def oracle():
        ss = frames["store_sales"]
        out = pd.DataFrame({"customer": ss.ss_customer_sk,
                            "store": ss.ss_store_sk,
                            "price": ss.ss_ext_sales_price})
        return out.sort_values(["customer", "store", "price"],
                               ascending=[True, True, False],
                               na_position="first"
                               ).reset_index(drop=True)

    return srt, oracle


def q5_multijoin_limit(paths, frames, mode):
    """BASELINE config 5 (lite): 3-table multi-stage — ss x dd x item,
    grouped agg, sort, limit."""
    ss = P.scan(SS_SCHEMA, [(paths["store_sales"], [])])
    dd = P.scan(DD_SCHEMA, [(paths["date_dim"], [])])
    it = P.scan(ITEM_SCHEMA, [(paths["item"], [])])
    ddf = P.filter_(dd, ir.Binary(BinOp.EQ, col("d_year"), lit(1998)))
    j1s = T.Schema(list(SS_SCHEMA.fields) + list(DD_SCHEMA.fields))
    j1 = _join(ss, ddf, [col("ss_sold_date_sk")], [col("d_date_sk")],
               "inner", j1s, mode)
    j2s = T.Schema(list(j1s.fields) + list(ITEM_SCHEMA.fields))
    j2 = _join(j1, it, [col("ss_item_sk")], [col("i_item_sk")],
               "inner", j2s, mode)
    aggs = [{"fn": "sum", "args": [col("ss_ext_sales_price")],
             "dtype": T.FLOAT64, "name": "rev"},
            {"fn": "count", "args": [col("ss_item_sk")],
             "dtype": T.INT64, "name": "n"}]
    partial = P.hash_agg(j2, "partial", [col("i_category_id")], ["cat"],
                         aggs, T.Schema([T.Field("cat", T.INT32)]))
    x = P.shuffle_exchange(partial, [col("cat")], 4)
    final = P.hash_agg(
        x, "final", [col("i_category_id")], ["cat"], aggs,
        T.Schema([T.Field("cat", T.INT32), T.Field("rev", T.FLOAT64),
                  T.Field("n", T.INT64)]))
    srt = P.sort(final, [(col("rev"), False, True)])
    lim = P.limit(srt, 5, True)

    def oracle():
        ssd, ddd, itd = (frames["store_sales"], frames["date_dim"],
                         frames["item"])
        m = ssd.merge(ddd[ddd.d_year == 1998], left_on="ss_sold_date_sk",
                      right_on="d_date_sk")
        m = m.merge(itd, left_on="ss_item_sk", right_on="i_item_sk")
        g = m.groupby("i_category_id").agg(
            rev=("ss_ext_sales_price", lambda s: s.sum(min_count=1)),
            n=("ss_item_sk", "count")).reset_index()
        g.columns = ["cat", "rev", "n"]
        return g.sort_values("rev", ascending=False,
                             na_position="first").head(5).reset_index(
            drop=True)

    return lim, oracle


def q6_semi_join(paths, frames, mode):
    """LEFT SEMI over a filtered dimension (EXISTS subquery shape)."""
    ss = P.scan(SS_SCHEMA, [(paths["store_sales"], [])])
    dd = P.scan(DD_SCHEMA, [(paths["date_dim"], [])])
    ddf = P.filter_(dd, ir.Binary(BinOp.EQ, col("d_moy"), lit(12)))
    j = _join(ss, ddf, [col("ss_sold_date_sk")], [col("d_date_sk")],
              "left_semi", SS_SCHEMA, mode)
    aggs = [{"fn": "count", "args": [col("ss_item_sk")],
             "dtype": T.INT64, "name": "n"}]
    partial = P.hash_agg(j, "partial", [col("ss_store_sk")], ["store"],
                         aggs, T.Schema([T.Field("store", T.INT64)]))
    x = P.shuffle_exchange(partial, [col("store")], 4)
    final = P.hash_agg(x, "final", [col("ss_store_sk")], ["store"], aggs,
                       T.Schema([T.Field("store", T.INT64),
                                 T.Field("n", T.INT64)]))
    srt = P.sort(final, [(col("store"), True, True)])

    def oracle():
        ssd, ddd = frames["store_sales"], frames["date_dim"]
        keys = set(ddd[ddd.d_moy == 12].d_date_sk)
        f = ssd[ssd.ss_sold_date_sk.isin(keys)]
        g = f.groupby("ss_store_sk")["ss_item_sk"].count().reset_index()
        g.columns = ["store", "n"]
        return g.sort_values("store").reset_index(drop=True)

    return srt, oracle


def q7_left_outer_join(paths, frames, mode):
    """LEFT OUTER item x sales counts (null-extension correctness)."""
    it = P.scan(ITEM_SCHEMA, [(paths["item"], [])])
    ss = P.scan(SS_SCHEMA, [(paths["store_sales"], [])])
    ssf = P.filter_(ss, ir.Binary(BinOp.GT, col("ss_ext_sales_price"),
                                  lit(950.0)))
    jschema = T.Schema(list(ITEM_SCHEMA.fields) + list(SS_SCHEMA.fields))
    j = _join(it, ssf, [col("i_item_sk")], [col("ss_item_sk")], "left",
              jschema, mode)
    aggs = [{"fn": "count", "args": [col("ss_item_sk")],
             "dtype": T.INT64, "name": "n"}]
    partial = P.hash_agg(j, "partial", [col("i_item_sk")], ["item"],
                         aggs, T.Schema([T.Field("item", T.INT64)]))
    x = P.shuffle_exchange(partial, [col("item")], 4)
    final = P.hash_agg(x, "final", [col("i_item_sk")], ["item"], aggs,
                       T.Schema([T.Field("item", T.INT64),
                                 T.Field("n", T.INT64)]))
    srt = P.sort(final, [(col("item"), True, True)])

    def oracle():
        itd, ssd = frames["item"], frames["store_sales"]
        f = ssd[ssd.ss_ext_sales_price > 950.0]
        m = itd.merge(f, left_on="i_item_sk", right_on="ss_item_sk",
                      how="left")
        g = m.groupby("i_item_sk")["ss_item_sk"].count().reset_index()
        g.columns = ["item", "n"]
        return g.sort_values("item").reset_index(drop=True)

    return srt, oracle


def q8_category_like(paths, frames, mode):
    """String dim predicate: i_category LIKE 'S%' through the join, count
    + revenue by category (STRING group key end-to-end)."""
    ss = P.scan(SS_SCHEMA, [(paths["store_sales"], [])])
    it = P.scan(ITEM_SCHEMA, [(paths["item"], [])])
    itf = P.filter_(it, ir.Like(col("i_category"), b"S%"))
    jschema = T.Schema(list(SS_SCHEMA.fields) + list(ITEM_SCHEMA.fields))
    j = _join(ss, itf, [col("ss_item_sk")], [col("i_item_sk")], "inner",
              jschema, mode)
    aggs = [{"fn": "count", "args": [col("ss_item_sk")],
             "dtype": T.INT64, "name": "n"},
            {"fn": "sum", "args": [col("ss_ext_sales_price")],
             "dtype": T.FLOAT64, "name": "rev"}]
    partial = P.hash_agg(j, "partial", [col("i_category")], ["category"],
                         aggs, T.Schema([T.Field("category", T.STRING)]))
    x = P.shuffle_exchange(partial, [col("category")], 4)
    final = P.hash_agg(
        x, "final", [col("i_category")], ["category"], aggs,
        T.Schema([T.Field("category", T.STRING), T.Field("n", T.INT64),
                  T.Field("rev", T.FLOAT64)]))
    srt = P.sort(final, [(col("category"), True, True)])

    def oracle():
        ssd, itd = frames["store_sales"], frames["item"]
        f = itd[itd.i_category.str.startswith("S")]
        m = ssd.merge(f, left_on="ss_item_sk", right_on="i_item_sk")
        g = m.groupby("i_category").agg(
            n=("ss_item_sk", "count"),
            rev=("ss_ext_sales_price",
                 lambda s: s.sum(min_count=1))).reset_index()
        g.columns = ["category", "n", "rev"]
        return g.sort_values("category").reset_index(drop=True)

    return srt, oracle


def q9_substr_group(paths, frames, mode):
    """substr(i_category, 1, 3) as a computed STRING group key (the
    LIKE/substr axis of real TPC-DS string processing, e.g. q08's
    substr(ca_zip,1,5))."""
    ss = P.scan(SS_SCHEMA, [(paths["store_sales"], [])])
    it = P.scan(ITEM_SCHEMA, [(paths["item"], [])])
    jschema = T.Schema(list(SS_SCHEMA.fields) + list(ITEM_SCHEMA.fields))
    j = _join(ss, it, [col("ss_item_sk")], [col("i_item_sk")], "inner",
              jschema, mode)
    pschema = T.Schema([T.Field("cat3", T.STRING),
                        T.Field("qty", T.FLOAT64)])
    proj = P.project(
        j,
        [ir.ScalarFn("substring",
                     (col("i_category"), lit(1), lit(3)), T.STRING),
         ir.Cast(col("ss_quantity"), T.FLOAT64)],
        ["cat3", "qty"], pschema)
    aggs = [{"fn": "count", "args": [col("cat3")],
             "dtype": T.INT64, "name": "n"},
            {"fn": "avg", "args": [col("qty")],
             "dtype": T.FLOAT64, "name": "avg_qty"}]
    partial = P.hash_agg(proj, "partial", [col("cat3")], ["cat3"], aggs,
                         T.Schema([T.Field("cat3", T.STRING)]))
    x = P.shuffle_exchange(partial, [col("cat3")], 4)
    final = P.hash_agg(
        x, "final", [col("cat3")], ["cat3"], aggs,
        T.Schema([T.Field("cat3", T.STRING), T.Field("n", T.INT64),
                  T.Field("avg_qty", T.FLOAT64)]))
    srt = P.sort(final, [(col("cat3"), True, True)])

    def oracle():
        ssd, itd = frames["store_sales"], frames["item"]
        m = ssd.merge(itd, left_on="ss_item_sk", right_on="i_item_sk")
        m = m.assign(cat3=m.i_category.str[:3])
        g = m.groupby("cat3").agg(
            n=("cat3", "count"),
            avg_qty=("ss_quantity", "mean")).reset_index()
        return g.sort_values("cat3").reset_index(drop=True)

    return srt, oracle


QUERIES: Dict[str, Callable] = {
    "q1_scan_filter_project": q1_scan_filter_project,
    "q2_q06_core_agg": q2_q06_core_agg,
    "q3_join_agg_sort": q3_join_agg_sort,
    "q4_repartition_sort": q4_repartition_sort,
    "q5_multijoin_limit": q5_multijoin_limit,
    "q6_semi_join": q6_semi_join,
    "q7_left_outer_join": q7_left_outer_join,
    "q8_category_like": q8_category_like,
    "q9_substr_group": q9_substr_group,
}

# join-less queries run once (the axis changes nothing)
_JOINLESS = {"q1_scan_filter_project", "q2_q06_core_agg",
             "q4_repartition_sort"}


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Result:
    query: str
    mode: str
    ok: bool
    seconds: float
    error: Optional[str] = None
    diff: Optional[str] = None
    spill_count: int = 0
    spilled_bytes: int = 0
    run_info: Dict = dataclasses.field(default_factory=dict)


def _compare(got: pd.DataFrame, want: pd.DataFrame) -> Optional[str]:
    if len(got) != len(want):
        return f"row count {len(got)} != {len(want)}"
    for c in want.columns:
        if c not in got.columns:
            return f"missing column {c}"
        g = got[c].to_numpy()
        w = want[c].to_numpy()
        if _is_stringy(w):
            gs = np.array([x.decode() if isinstance(x, bytes) else x
                           for x in g], object)
            bad = gs != w.astype(object)
        elif w.dtype.kind == "f" or g.dtype.kind == "f" or \
                w.dtype.kind == "O" or g.dtype.kind == "O":
            # None/NaN-bearing numerics: object->float maps None to nan
            bad = ~np.isclose(_as_f64(g), _as_f64(w),
                              rtol=1e-6, equal_nan=True)
        else:
            bad = g.astype(np.int64) != w.astype(np.int64)
        if bad.any():
            i = int(np.argmax(bad))
            return (f"column {c}: {int(bad.sum())} mismatches, first at row "
                    f"{i}: got={g[i]} want={w[i]}")
    return None


def _is_stringy(w: np.ndarray) -> bool:
    if w.dtype.kind in ("U", "S"):
        return True
    if w.dtype.kind == "O":
        for x in w:
            if x is None:
                continue
            return isinstance(x, (str, bytes))
    return False


def _as_f64(a: np.ndarray) -> np.ndarray:
    if a.dtype.kind == "O":
        # None -> nan without a Python-level loop (SF10-sized columns)
        return np.where(pd.isna(a), np.nan, a).astype(np.float64)
    return a.astype(np.float64)


# run_info counters that stay zero unless something other than the
# compiled device path served (part of) the query: task retries, the
# degradation ladder up to the CPU row interpreter, breaker reroutes,
# the mesh->file / pool->thread degrades, process-pool stages
_FALLBACK_COUNTERS = ("retries", "degradations", "ladder_rung",
                      "task_fallbacks", "breaker_trips", "breaker_reroutes",
                      "bytes_copied_fallback", "pool_stages")


def fallback_evidence(run_info: Dict) -> Dict[str, int]:
    """The nonzero fallback counters of a finished run ({} = clean): an
    oracle-equal result proves the ANSWER, these say which engine gave
    it."""
    return {k: v for k, v in run_info.items()
            if (k in _FALLBACK_COUNTERS or k.startswith("errors.")) and v}


def _to_pandas(batch) -> pd.DataFrame:
    # numeric columns stay numpy arrays (no Python list of an SF10-sized
    # column); strings/lists come as Python lists already
    return pd.DataFrame({k: v if isinstance(v, np.ndarray) else list(v)
                         for k, v in batch.to_numpy().items()})


def _suite(suite: str):
    """(generate_tables, catalogue, joinless) of a suite: "core" = the
    BASELINE config shapes in this module; "tpcds" = the
    hand-constructed TPC-DS q01-q10 catalogue (spark/tpcds.py, the
    north-star queries)."""
    if suite == "tpcds":
        from blaze_tpu.spark import tpcds

        return tpcds.generate_tables, tpcds.QUERIES, tpcds.JOINLESS
    return generate_tables, QUERIES, _JOINLESS


def matrix_cells(suite: str = "core",
                 queries: Optional[List[str]] = None
                 ) -> List[Tuple[str, str]]:
    """The (query, join mode) cells of a suite, in catalogue order; a
    joinless query has one (the axis is inert)."""
    _, catalogue, joinless = _suite(suite)
    return [(name, mode) for name in catalogue
            if not queries or name in queries
            for mode in (["bhj"] if name in joinless else ["bhj", "smj"])]


def run_cell(paths, frames, name: str, mode: str,
             spill_budget: Optional[int] = None,
             suite: str = "core") -> Result:
    """One cell of the matrix over tables already generated: fresh plan
    through run_plan, diffed against its pandas oracle. `ok` refuses an
    oracle-equal answer that a fallback served, unless the cell asked
    for faults or forced spill (it is SUPPOSED to climb the ladder
    then)."""
    from blaze_tpu.config import conf
    from blaze_tpu.runtime import memory as M

    _, catalogue, _ = _suite(suite)
    strict = not spill_budget and not conf.fault_injection_spec
    t0 = time.time()
    mgr = M.init(spill_budget) if spill_budget else M.get_manager()
    # deltas, not totals: without spill_budget the SHARED global
    # manager carries counts from earlier cells/process activity
    sc0, sb0 = mgr.spill_count, mgr.spilled_bytes
    run_info: Dict = {}
    diff = error = None
    try:
        plan, oracle = catalogue[name](paths, frames, mode)
        out = run_plan(plan, num_partitions=4, run_info=run_info)
        # order-insensitive where the plan has no global sort tail
        diff = _compare(_to_pandas(out).reset_index(drop=True),
                        oracle().reset_index(drop=True))
        evidence = fallback_evidence(run_info) if strict else {}
        if diff is None and evidence:
            diff = f"oracle-equal, but served by a fallback: {evidence}"
    except Exception:
        error = traceback.format_exc(limit=8)
    return Result(
        name, mode, diff is None and error is None,
        time.time() - t0, error=error, diff=diff,
        spill_count=mgr.spill_count - sc0,
        spilled_bytes=mgr.spilled_bytes - sb0,
        run_info={k: v for k, v in run_info.items()
                  if isinstance(v, (int, float, str))})


def run_matrix(tmpdir: str, rows: int = 20_000,
               queries: Optional[List[str]] = None,
               spill_budget: Optional[int] = None,
               suite: str = "core") -> List[Result]:
    """spill_budget: when set, MemManager is (re)initialized to this many
    bytes before every cell so sort/agg/shuffle spill fires IN QUERY
    CONTEXT (the reference fuzz-gates a 1.23M-row external sort under
    MemManager::init(10000), sort_exec.rs:954) — each Result then records
    the spill counters the run produced."""
    generate, _, _ = _suite(suite)
    paths, frames = generate(tmpdir, rows=rows)
    results: List[Result] = []
    for name, mode in matrix_cells(suite, queries):
        r = run_cell(paths, frames, name, mode, spill_budget, suite)
        results.append(r)
        # incremental progress: long matrices run under timeouts in
        # background shells — per-cell lines must not be lost to a
        # buffered final report
        print(f"[cell] {r.query} {r.mode} "
              f"{'PASS' if r.ok else 'FAIL'} {r.seconds:.1f}s "
              f"spills={r.spill_count}", flush=True)
    return results


def print_report(results: List[Result]) -> bool:
    ok = True
    show_spill = any(r.spill_count for r in results)
    hdr = f"{'query':34s} {'join':5s} {'status':8s} {'sec':>6s}"
    print(hdr + ("  spills  spill_mb" if show_spill else ""))
    for r in results:
        status = "PASS" if r.ok else "FAIL"
        ok = ok and r.ok
        line = f"{r.query:34s} {r.mode:5s} {status:8s} {r.seconds:6.1f}"
        if show_spill:
            line += f"  {r.spill_count:6d}  {r.spilled_bytes / 1e6:8.1f}"
        print(line)
        if r.diff:
            print(f"    diff: {r.diff}")
        if r.error:
            print("    " + r.error.replace("\n", "\n    "))
    n_pass = sum(1 for r in results if r.ok)
    print(f"\n{n_pass}/{len(results)} passed")
    return ok
