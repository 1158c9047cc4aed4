"""Seeded TPC-DS tables at a configuration's own row counts and key domains.

Named by a configuration's `"generator": "tpcds"`. Independent of
`blaze_tpu`: numpy + pyarrow only. Everything the specification fixes per
scale factor (row counts, surrogate-key domains, the date range of sales)
is read from the configuration file; what dsdgen does that this does not
(ticket structure, seasonal date skew, the item hierarchy) is listed there
under `assumed`.

`generate(config, seed, out_dir, fact_rows=None)` writes one snappy parquet
file per table holding only the columns the benchmark's queries reference
(the configuration's `reduced.parquet_columns`) and returns
`(paths, frames)`; `frames` are pandas frames in which a nullable integer
column is float64 with NaN, the form the plain references read.
`fact_rows` cuts store_sales for a CPU rehearsal and nothing else.
"""

from __future__ import annotations

import os

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq

# dsdgen builds i_brand from two syllable lists and a number
# (`assumed`: lists from memory of the generator's item distributions)
_BRAND_A = ("amalg", "importo", "edu pack", "exporti")
_BRAND_B = ("amalg", "importo", "edu pack", "exporti", "scholar", "brand",
            "corp", "maxi", "univ", "nameless")

ARROW_TYPES = {"int64": pa.int64(), "int32": pa.int32(),
               "double": pa.float64(), "string": pa.string()}


def _nullable(rng, values: np.ndarray, frac: float) -> np.ndarray:
    out = values.astype(np.float64, copy=False)
    out[rng.random(len(out)) < frac] = np.nan
    return out


def date_dim(spec: dict) -> pd.DataFrame:
    """The calendar, not a draw: d_date_sk counts days from the first."""
    n = spec["rows"]
    days = (np.datetime64(spec["first_date"], "D")
            + np.arange(n).astype("timedelta64[D]"))
    return pd.DataFrame({
        "d_date_sk": spec["first_date_sk"] + np.arange(n, dtype=np.int64),
        "d_year": (days.astype("datetime64[Y]").astype(np.int64)
                   + 1970).astype(np.int32),
        "d_moy": (days.astype("datetime64[M]").astype(np.int64) % 12
                  + 1).astype(np.int32),
    })


def item(spec: dict, rng) -> pd.DataFrame:
    n = spec["rows"]
    a = rng.integers(0, len(_BRAND_A), n)
    b = rng.integers(0, len(_BRAND_B), n)
    num = rng.integers(1, spec["brand_numbers"] + 1, n)
    brands = np.array([f"{x}{y} #{k}" for x in _BRAND_A for y in _BRAND_B
                       for k in range(1, spec["brand_numbers"] + 1)], object)
    code = (a * len(_BRAND_B) + b) * spec["brand_numbers"] + (num - 1)
    return pd.DataFrame({
        "i_item_sk": np.arange(1, n + 1, dtype=np.int64),
        "i_brand_id": ((a + 1) * 1_000_000 + (b + 1) * 1_000
                       + num).astype(np.int32),
        "i_brand": brands[code],
        "i_manufact_id": rng.integers(
            1, spec["manufact_ids"] + 1, n).astype(np.int32),
    })


def store_sales(spec: dict, tables: dict, rng, rows: int) -> pd.DataFrame:
    dd = tables["date_dim"]
    lo = dd["first_date_sk"] + int(
        (np.datetime64(spec["sold_date_first"], "D")
         - np.datetime64(dd["first_date"], "D")).astype(np.int64))
    hi = dd["first_date_sk"] + int(
        (np.datetime64(spec["sold_date_last"], "D")
         - np.datetime64(dd["first_date"], "D")).astype(np.int64))
    nulls = spec["null_fraction"]
    # money in whole cents, divided once: the double nearest each
    # decimal(7,2) value, with no rounding pass
    cents = rng.integers(0, int(spec["max_sales_price"] * 100) + 1, rows)
    quantity = rng.integers(1, 101, rows)
    return pd.DataFrame({
        "ss_sold_date_sk": _nullable(
            rng, rng.integers(lo, hi + 1, rows), nulls),
        # part of the primary key: never null, uniform over item
        "ss_item_sk": rng.integers(1, tables["item"]["rows"] + 1, rows),
        "ss_sales_price": _nullable(rng, cents / 100.0, nulls),
        "ss_ext_sales_price": _nullable(
            rng, cents * quantity / 100.0, nulls),
    }, copy=False)


def _to_arrow(df: pd.DataFrame, columns: dict) -> pa.Table:
    arrays, fields = [], []
    for name, type_name in columns.items():
        typ, col = ARROW_TYPES[type_name], df[name].to_numpy()
        if pa.types.is_integer(typ) and col.dtype.kind == "f":
            mask = np.isnan(col)
            arr = pa.array(np.where(mask, 0, col).astype(np.int64),
                           type=typ, mask=mask)
        else:
            arr = pa.array(col, type=typ, from_pandas=True)
        arrays.append(arr)
        fields.append(pa.field(name, typ))
    return pa.Table.from_arrays(arrays, schema=pa.schema(fields))


def generate(config: dict, seed: int, out_dir: str, fact_rows=None):
    tables = config["tables"]
    # one stream per table, so a cut fact table leaves the dimensions as
    # they are
    streams = np.random.SeedSequence(int(seed)).spawn(2)
    rows = int(fact_rows or tables["store_sales"]["rows"])
    frames = {
        "date_dim": date_dim(tables["date_dim"]),
        "item": item(tables["item"], np.random.default_rng(streams[0])),
        "store_sales": store_sales(tables["store_sales"], tables,
                                   np.random.default_rng(streams[1]), rows),
    }
    paths = {}
    for name, df in frames.items():
        paths[name] = os.path.join(out_dir, f"{name}.parquet")
        pq.write_table(_to_arrow(df, tables[name]["columns"]), paths[name],
                       row_group_size=config["parquet"]["row_group_rows"],
                       compression=config["parquet"]["compression"])
    return paths, frames
