"""Parquet scan/sink: row-group pruning, partition values, roundtrip.

Ref: parquet_exec.rs (pruning :218-239, ignoreCorruptFiles :250) and
parquet_sink_exec.rs."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import ColumnBatch
from blaze_tpu.config import conf
from blaze_tpu.exprs import ir
from blaze_tpu.ops.basic import MemorySourceExec
from blaze_tpu.ops.parquet import ParquetScanExec, ParquetSinkExec
from blaze_tpu.runtime.executor import collect, execute_plan

FILE_SCHEMA = T.Schema([T.Field("a", T.INT64), T.Field("b", T.FLOAT64),
                        T.Field("s", T.STRING)])


def _write_file(path, n=1000, row_group_size=100, seed=0):
    rng = np.random.default_rng(seed)
    tbl = pa.table({
        "a": pa.array(np.arange(n), pa.int64()),   # sorted -> prunable
        "b": pa.array(rng.random(n)),
        "s": pa.array([f"row{i}" for i in range(n)]),
    })
    pq.write_table(tbl, path, row_group_size=row_group_size)
    return tbl


def test_scan_roundtrip(tmp_path, rng):
    path = str(tmp_path / "t.parquet")
    tbl = _write_file(path)
    scan = ParquetScanExec([(path, [])], FILE_SCHEMA, [0, 1, 2])
    out = collect(scan)
    assert int(out.num_rows) == 1000
    d = out.to_numpy()
    np.testing.assert_array_equal(np.asarray(d["a"]),
                                  tbl.column("a").to_numpy())


def test_scan_projection_and_partition_values(tmp_path):
    path = str(tmp_path / "t.parquet")
    _write_file(path)
    pschema = T.Schema([T.Field("year", T.INT32)])
    scan = ParquetScanExec([(path, [ir.Literal(T.INT32, 2024)])],
                           FILE_SCHEMA, [0], partition_schema=pschema)
    out = collect(scan)
    assert out.schema.names() == ["a", "year"]
    d = out.to_numpy()
    assert all(int(y) == 2024 for y in np.asarray(d["year"]))


def test_row_group_pruning(tmp_path):
    path = str(tmp_path / "t.parquet")
    _write_file(path, n=1000, row_group_size=100)
    # a >= 950 prunes 9 of 10 row groups
    scan = ParquetScanExec([(path, [])], FILE_SCHEMA, [0],
                           pruning_predicates=[
                               ir.Binary(ir.BinOp.GE, ir.col("a"),
                                         ir.lit(950))])
    out = collect(scan)
    assert scan.metrics["row_groups_pruned"] == 9
    assert int(out.num_rows) == 100  # pruning is coarse; filter comes later


def test_ignore_corrupt_files(tmp_path):
    good = str(tmp_path / "good.parquet")
    bad = str(tmp_path / "bad.parquet")
    _write_file(good, n=10)
    open(bad, "wb").write(b"not a parquet file")
    conf.ignore_corrupt_files = True
    try:
        scan = ParquetScanExec([(bad, []), (good, [])], FILE_SCHEMA,
                               [0, 1, 2])
        out = collect(scan)
        assert int(out.num_rows) == 10
    finally:
        conf.ignore_corrupt_files = False
    scan2 = ParquetScanExec([(bad, []), (good, [])], FILE_SCHEMA, [0, 1, 2])
    with pytest.raises(Exception):
        collect(scan2)


def test_sink_roundtrip(tmp_path, rng):
    n = 500
    b = ColumnBatch.from_numpy({
        "a": rng.integers(0, 100, n).astype(np.int64),
        "b": rng.random(n),
        "s": [f"x{i%13}" for i in range(n)],
    }, FILE_SCHEMA)
    path = str(tmp_path / "out.parquet")
    sink = ParquetSinkExec(MemorySourceExec([b], FILE_SCHEMA), path)
    stats = collect(sink).to_numpy()
    assert int(np.asarray(stats["num_rows"])[0]) == n
    back = pq.read_table(path)
    assert back.num_rows == n
    np.testing.assert_array_equal(back.column("a").to_numpy(),
                                  np.asarray(b.to_numpy()["a"]))
    assert back.column("s").to_pylist() == [
        s.decode() for s in b.to_numpy()["s"]]


def test_scans_read_through_the_system_pool():
    """Importing the scan sets Arrow's pool to the system allocator, unless
    the deployment named one: a child with the variable set keeps its own."""
    import os
    import subprocess
    import sys

    import blaze_tpu.ops.parquet  # noqa: F401

    assert pa.default_memory_pool().backend_name == "system"
    code = ("import pyarrow as pa, blaze_tpu.ops.parquet; "
            "print(pa.default_memory_pool().backend_name)")
    env = {**os.environ, "ARROW_DEFAULT_MEMORY_POOL": "mimalloc",
           "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, check=True)
    assert out.stdout.split()[-1] == "mimalloc"
