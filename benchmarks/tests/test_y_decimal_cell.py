"""The decimal configuration's own parts (PR 32): its generator against its
double-typed twin's, the parquet it writes, the cell rehearsed on the CPU with
its two per-layer metrics on the traced line, and the SF10 q06core cell's
rehearsal. A rehearsal's numbers are the CPU's: presence and exactness are
checked, never a time. No child process.

The file's name sorts it after test_x4_cell.py: these one-chip rehearsals
compile `local_xchg` in this process, and that file reads the whole process's
first-call table to say the four-chip cell compiled none."""

import json
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from harness import loop
from harness.registry import Registry


@pytest.fixture(scope="module")
def reg():
    return Registry()


def test_rint_gives_back_every_drawn_integer():
    """The twin draws money in whole cents and divides by 100 once; the
    decimal generator multiplies back. Exact for every value it can draw:
    prices 0..20,000 cents, extended prices up to 100 times that."""
    cents = np.arange(0, 2_000_001, dtype=np.int64)
    assert (np.rint(cents / 100.0 * 100).astype(np.int64) == cents).all()


def test_the_decimal_generator_draws_what_its_twin_draws(reg, tmp_path):
    config = reg.data("configs", "tpcds_sf1_decimal")
    twin_config = reg.data("configs", "tpcds_sf1_nobhj")
    for d in ("dec", "twin", "again"):
        (tmp_path / d).mkdir()
    seed = 2 ** 31 + 5
    generate = reg.module("datagen", config["generator"]).generate
    paths, frames = generate(config, seed, str(tmp_path / "dec"), 30000)
    _, twin = reg.module("datagen", twin_config["generator"]).generate(
        twin_config, seed, str(tmp_path / "twin"), 30000)
    _, again = generate(config, seed, str(tmp_path / "again"), 30000)
    ss, tw = frames["store_sales"], twin["store_sales"]
    for table in ("date_dim", "item"):
        assert frames[table].equals(twin[table])
    for column in ("ss_sold_date_sk", "ss_item_sk"):
        assert ss[column].equals(tw[column])
    for column in ("ss_sales_price", "ss_ext_sales_price"):
        assert str(ss[column].dtype) == "Int64"         # nullable cents
        assert (ss[column].isna() == tw[column].isna()).all()
        cents = ss[column].to_numpy(dtype=np.int64, na_value=0)
        # the twin's double is the one nearest cents / 100
        assert (np.where(tw[column].isna(), 0.0, tw[column])
                == cents / 100.0).all()
        assert 0 <= cents.min() and cents.max() <= 2_000_000
        assert ss[column].equals(again["store_sales"][column])
    assert 0.03 < ss.ss_sales_price.isna().mean() < 0.06
    # everything but the money type is the twin's
    for key in ("scale_factor", "settings"):
        assert config[key] == twin_config[key]
    for table, spec in config["tables"].items():
        for key, value in spec.items():
            if key != "columns":
                assert value == twin_config["tables"][table][key]
    assert set(config["reduced"]) == {"parquet_columns", "shuffle_partitions"}
    # sums and counts are integers and compared exactly; the avg is a
    # double's rounding and gets a unit or two of its sixth place
    assert 0 < config["guarantees"]["float_rtol"] <= 3e-8
    # the file: decimal(7,2) from integer cents, stored as Spark stores it
    table = pq.read_table(paths["store_sales"])
    assert table.schema.field("ss_sales_price").type == pa.decimal128(7, 2)
    physical = {c.name: c.physical_type
                for c in pq.ParquetFile(paths["store_sales"]).schema}
    assert physical["ss_sales_price"] == "INT32"
    assert physical["ss_ext_sales_price"] == "INT32"
    got = table.column("ss_ext_sales_price").to_pylist()
    want = ss.ss_ext_sales_price
    for i in (0, 1, 2, 17, 29999):
        assert (got[i] is None) == bool(want.isna().iloc[i])
        if got[i] is not None:
            assert int(got[i].scaleb(2)) == int(want.iloc[i])


def _rehearse(cell: str, seed: int, rows: int):
    from blaze_tpu.config import conf

    traced = conf.trace_enabled
    lines = []
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr("builtins.print",
                   lambda *a, **k: lines.append(" ".join(map(str, a))))
        # the cell asks for one chip: show the program one device
        import jax

        real = jax.devices
        mp.setattr(jax, "devices", lambda *a, **k: real(*a, **k)[:1])
        rc = loop.run(cell, seed=seed, seconds=2.0, traced=True,
                      rehearse_rows=rows, t_start=time.perf_counter())
    conf.trace_enabled = traced
    assert rc == 0
    return lines


@pytest.fixture(scope="module")
def decimal_rehearsal():
    return _rehearse("sf1_q06core_agg_dec", 2147483659, 200_000)


def test_the_decimal_cell_rehearses_correct(decimal_rehearsal):
    line = json.loads(decimal_rehearsal[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4        # a window's query and three profiled
    assert line["device"]["platform"] == "cpu"
    assert not [ln for ln in decimal_rehearsal if "FAILED" in ln]


def test_the_decimal_cells_own_metrics_are_on_the_traced_line(
        decimal_rehearsal):
    metrics = json.loads(decimal_rehearsal[-1])["metrics"]
    # the money sum and the merged counts add integers, the avg's doubles
    assert metrics["decimal_reduction_share"]["unit"] == "%"
    assert 50.0 <= metrics["decimal_reduction_share"]["value"] <= 75.0
    assert metrics["decimal_decode_s"]["unit"] == "s"
    assert metrics["decimal_decode_s"]["value"] > 0
    assert metrics["compiles_in_window"]["value"] == 0
    # the lists of these two name the cells they were accepted with
    for name in ("exchange_s", "seg_scan_share", "device_idle_share",
                 "hbm_roofline_share", "peak_hbm_GB"):
        assert name not in metrics


def test_the_twin_reports_neither_decimal_metric(reg):
    names = [m["name"] for m in reg.metrics("sf1_q06core_agg", "per_layer")]
    assert "decimal_decode_s" not in names
    assert "decimal_reduction_share" not in names
    # and a program without the span or the counters reads as nothing
    empty = {"window": [{"seconds": 1.0, "spans": [], "query": "q"}],
             "profiled": [], "telemetry": {}}
    for name in ("decimal_decode_s", "decimal_reduction_share"):
        assert reg.module("metrics", name).read(empty) is None
    doubles = {"window": [], "profiled": [],
               "telemetry": {"seg_sums": 8, "seg_int_sums": 0}}
    assert reg.module("metrics", "decimal_reduction_share").read(
        doubles) == 0.0


def test_the_sf10_q06core_cell_rehearses_correct():
    lines = _rehearse("sf10_q06core_agg", 1000003, 200_000)
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert "decimal_reduction_share" not in line["metrics"]
