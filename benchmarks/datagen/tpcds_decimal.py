"""The TPC-DS tables of `datagen/tpcds.py` with money typed as the
specification types it: decimal(7,2).

Named by a configuration's `"generator": "tpcds_decimal"`. The draws are
`datagen/tpcds.py`'s own (that file is loaded by path and called, nothing in
it is edited), so for one seed this configuration and its double-typed twin
see the same keys, dates, nulls and prices. There money is drawn in whole
cents and divided by 100 once, so each value is the double nearest
cents / 100, and `rint(x * 100)` gives the drawn integer back (at most
2,000,000; benchmarks/tests/test_y_decimal_cell.py says so for every one).

`generate(config, seed, out_dir, fact_rows=None)` returns `(paths, frames)`
as the twin does. A column the configuration types `decimal(p,s)` is
written as `pa.decimal128(p, s)` built from the integer cents, never from a
double, and stored in parquet as Spark stores it with
`writeLegacyFormat=false` (INT32 for precision <= 9). In the returned frames
such a column holds **nullable integer cents** (pandas `Int64`: the
unscaled value, `<NA>` for null), the form an integer-arithmetic reference
reads; every other column is as the twin's frames have it.
"""

from __future__ import annotations

import importlib.util
import os
import re

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq

_DECIMAL = re.compile(r"^decimal\((\d+),(\d+)\)$")


def _twin():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tpcds.py")
    spec = importlib.util.spec_from_file_location("bench_datagen_tpcds", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def unscaled(values: np.ndarray, scale: int) -> pd.arrays.IntegerArray:
    """The twin's doubles (NaN = null) back to the integers they were drawn
    as: rint(x * 10^scale), exact for every value the generator makes."""
    null = np.isnan(values)
    ints = np.rint(np.where(null, 0.0, values) * 10 ** scale).astype(np.int64)
    return pd.arrays.IntegerArray(ints, null)


def decimal128(ints: pd.arrays.IntegerArray, typ: pa.DataType) -> pa.Array:
    """A decimal128 array from unscaled int64 values: the 16-byte little-endian
    two's-complement words are (value, sign extension)."""
    null = np.asarray(ints.isna())
    vals = ints.to_numpy(dtype=np.int64, na_value=0)
    assert len(vals) == 0 or int(np.abs(vals).max()) < 10 ** typ.precision
    words = np.empty(2 * len(vals), np.int64)
    words[0::2] = vals
    words[1::2] = vals >> 63
    return pa.Array.from_buffers(
        typ, len(vals), [pa.array(~null).buffers()[1], pa.py_buffer(words)],
        null_count=int(null.sum()))


def _to_arrow(twin, df: pd.DataFrame, columns: dict) -> pa.Table:
    plain = {n: t for n, t in columns.items() if not _DECIMAL.match(t)}
    table = twin._to_arrow(df, plain)
    for i, (name, type_name) in enumerate(columns.items()):
        m = _DECIMAL.match(type_name)
        if m:
            typ = pa.decimal128(int(m.group(1)), int(m.group(2)))
            table = table.add_column(
                i, pa.field(name, typ), decimal128(df[name].array, typ))
    return table


def generate(config: dict, seed: int, out_dir: str, fact_rows=None):
    twin = _twin()
    tables = config["tables"]
    # the twin's streams and the twin's order of draws
    streams = np.random.SeedSequence(int(seed)).spawn(2)
    rows = int(fact_rows or tables["store_sales"]["rows"])
    frames = {
        "date_dim": twin.date_dim(tables["date_dim"]),
        "item": twin.item(tables["item"], np.random.default_rng(streams[0])),
        "store_sales": twin.store_sales(
            tables["store_sales"], tables,
            np.random.default_rng(streams[1]), rows),
    }
    for name, df in frames.items():
        for column, type_name in tables[name]["columns"].items():
            m = _DECIMAL.match(type_name)
            if m:
                df[column] = unscaled(df[column].to_numpy(), int(m.group(2)))
    paths = {}
    for name, df in frames.items():
        paths[name] = os.path.join(out_dir, f"{name}.parquet")
        pq.write_table(_to_arrow(twin, df, tables[name]["columns"]),
                       paths[name],
                       row_group_size=config["parquet"]["row_group_rows"],
                       compression=config["parquet"]["compression"],
                       store_decimal_as_integer=True)
    return paths, frames
