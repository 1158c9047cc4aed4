"""ICI-mesh shuffle: all_to_all exchange delivers every row to the partition
chosen by the Spark-compatible hash, with no loss and no duplication.

Runs on the virtual 8-device CPU mesh (conftest). Ref behavior being
replicated: shuffle/mod.rs:94-119 partitioning + the IPC block exchange of
SURVEY.md §3.3, collapsed into one in-HBM collective.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import ColumnBatch
from blaze_tpu.exprs.hash import SPARK_SHUFFLE_SEED, hash_columns, pmod
from blaze_tpu.parallel.shuffle import mesh_shuffle_batch, partition_ids

NDEV = 8
LOCAL_CAP = 64

SCHEMA = T.Schema([T.Field("k", T.INT64), T.Field("v", T.FLOAT64)])


def _make_local_batches(rng, rows_per_dev):
    batches = []
    for d in range(NDEV):
        n = rows_per_dev[d]
        k = rng.integers(0, 1000, size=n).astype(np.int64)
        v = rng.random(n)
        batches.append(ColumnBatch.from_numpy({"k": k, "v": v}, SCHEMA,
                                              capacity=LOCAL_CAP))
    return batches


def _stack_for_mesh(batches):
    """Concat per-device local batches along rows; num_rows as (NDEV,)."""
    cols = jax.tree_util.tree_map(
        lambda *xs: jnp.concatenate(xs, axis=0), *[b.columns for b in batches])
    num_rows = jnp.asarray([int(b.num_rows) for b in batches], jnp.int32)
    return cols, num_rows


@pytest.mark.parametrize("rows_per_dev", [
    [64, 64, 64, 64, 64, 64, 64, 64],      # full
    [10, 0, 64, 3, 17, 1, 0, 30],           # ragged + empty shards
])
def test_mesh_shuffle_roundtrip(rng, rows_per_dev):
    batches = _make_local_batches(rng, rows_per_dev)
    cols, num_rows = _stack_for_mesh(batches)
    mesh = Mesh(np.array(jax.devices()[:NDEV]), ("p",))

    def step(local_cols, local_num_rows):
        batch = ColumnBatch(SCHEMA, local_cols, local_num_rows[0], LOCAL_CAP)
        out, overflow = mesh_shuffle_batch(batch, [0], "p", NDEV,
                                           quota=LOCAL_CAP)
        return out.columns, out.num_rows[None], overflow[None]

    run = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P("p"), P("p")),
        out_specs=(P("p"), P("p"), P("p"))))
    out_cols, out_rows, overflow = run(cols, num_rows)
    assert int(jnp.sum(overflow)) == 0

    # reassemble per-device outputs
    out_cap = NDEV * LOCAL_CAP
    got = {}  # key -> list of (value, device)
    all_rows = []
    for d in range(NDEV):
        n = int(out_rows[d])
        b = ColumnBatch(
            SCHEMA,
            jax.tree_util.tree_map(
                lambda a: a[d * out_cap:(d + 1) * out_cap], out_cols),
            n, out_cap)
        np_out = b.to_numpy()
        for k, v in zip(np.asarray(np_out["k"]), np.asarray(np_out["v"])):
            all_rows.append((int(k), float(v), d))

    # 1. conservation: exactly the input rows survive
    expect = []
    for b in batches:
        d = b.to_numpy()
        expect += [(int(k), float(v)) for k, v in zip(d["k"], d["v"])]
    assert sorted((k, v) for k, v, _ in all_rows) == sorted(expect)

    # 2. placement: each row landed on pmod(murmur3(k), NDEV)
    kcol = ColumnBatch.from_numpy(
        {"k": np.array([k for k, _, _ in all_rows], np.int64),
         "v": np.zeros(len(all_rows))}, SCHEMA)
    h = hash_columns([kcol.columns[0]], SPARK_SHUFFLE_SEED,
                     row_mask=kcol.row_mask())
    want_pid = np.asarray(pmod(h, NDEV))[:len(all_rows)]
    got_pid = np.array([d for _, _, d in all_rows])
    np.testing.assert_array_equal(got_pid, want_pid)


def test_partition_ids_padding_sentinel(rng):
    b = _make_local_batches(rng, [5] * NDEV)[0]
    pid = partition_ids(b, [0], NDEV)
    assert np.all(np.asarray(pid)[5:] == NDEV)
    assert np.all(np.asarray(pid)[:5] < NDEV)


def test_stage_exchange_matches_file_path(rng, tmp_path):
    """The q3-shaped multistage plan produces identical results whether the
    exchanges ride the in-HBM mesh all_to_all or .data/.index files
    (VERDICT r1 #3 acceptance)."""
    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu.columnar import types as T
    from blaze_tpu.exprs import ir
    from blaze_tpu.spark import plan_model as P
    from blaze_tpu.spark.local_runner import run_plan

    n_ss, n_dd = 4000, 200
    ss = pa.table({
        "ss_sold_date_sk": pa.array(rng.integers(0, n_dd, n_ss), pa.int64()),
        "ss_item_sk": pa.array(rng.integers(0, 30, n_ss), pa.int64()),
        "ss_ext_sales_price": pa.array(rng.random(n_ss) * 100),
    })
    dd = pa.table({
        "d_date_sk": pa.array(np.arange(n_dd), pa.int64()),
        "d_moy": pa.array((np.arange(n_dd) // 30) % 12 + 1, pa.int32()),
    })
    ss_path, dd_path = str(tmp_path / "ss.parquet"), str(tmp_path / "dd.parquet")
    pq.write_table(ss, ss_path)
    pq.write_table(dd, dd_path)
    SS = T.Schema([T.Field("ss_sold_date_sk", T.INT64),
                   T.Field("ss_item_sk", T.INT64),
                   T.Field("ss_ext_sales_price", T.FLOAT64)])
    DD = T.Schema([T.Field("d_date_sk", T.INT64), T.Field("d_moy", T.INT32)])

    def build():
        ss_scan = P.scan(SS, [(ss_path, [])])
        dd_scan = P.scan(DD, [(dd_path, [])])
        dd_flt = P.filter_(dd_scan, ir.Binary(ir.BinOp.EQ, ir.col("d_moy"),
                                              ir.lit(3)))
        ss_x = P.shuffle_exchange(ss_scan, [ir.col("ss_sold_date_sk")], 4)
        dd_x = P.shuffle_exchange(dd_flt, [ir.col("d_date_sk")], 4)
        join_schema = T.Schema(list(SS.fields) + list(DD.fields))
        j = P.smj(ss_x, dd_x, [ir.col("ss_sold_date_sk")],
                  [ir.col("d_date_sk")], "inner", join_schema)
        partial = P.hash_agg(j, "partial", [ir.col("ss_item_sk")], ["item"],
                             [{"fn": "sum",
                               "args": [ir.col("ss_ext_sales_price")],
                               "dtype": T.FLOAT64, "name": "s"}],
                             T.Schema([T.Field("item", T.INT64)]))
        agg_x = P.shuffle_exchange(partial, [ir.col("item")], 4)
        final = P.hash_agg(agg_x, "final", [ir.col("item")], ["item"],
                           [{"fn": "sum",
                             "args": [ir.col("ss_ext_sales_price")],
                             "dtype": T.FLOAT64, "name": "s"}],
                           T.Schema([T.Field("item", T.INT64),
                                     T.Field("s", T.FLOAT64)]))
        return P.sort(final, [(ir.col("s"), False, True)])

    out_mesh = run_plan(build(), num_partitions=4, mesh_exchange="auto")
    out_file = run_plan(build(), num_partitions=4, mesh_exchange="off")

    dm, df_ = out_mesh.to_numpy(), out_file.to_numpy()
    np.testing.assert_array_equal(np.asarray(dm["item"]),
                                  np.asarray(df_["item"]))
    np.testing.assert_allclose(np.asarray(dm["s"]), np.asarray(df_["s"]),
                               rtol=1e-12)

    ssd, ddd = ss.to_pandas(), dd.to_pandas()
    m = ssd.merge(ddd[ddd.d_moy == 3], left_on="ss_sold_date_sk",
                  right_on="d_date_sk")
    want = m.groupby("ss_item_sk")["ss_ext_sales_price"].sum().sort_values(
        ascending=False)
    np.testing.assert_allclose([float(x) for x in dm["s"]],
                               want.to_numpy(), rtol=1e-9)


def test_stage_exchange_overflow_falls_back(rng, tmp_path):
    """A tiny staging quota with skewed keys overflows; the runner must
    silently fall back to the file path and stay correct."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu.columnar import types as T
    from blaze_tpu.exprs import ir
    from blaze_tpu.spark import plan_model as P
    from blaze_tpu.spark.local_runner import run_plan

    n = 1000
    t = pa.table({
        "k": pa.array(np.full(n, 7), pa.int64()),   # all rows -> one bucket
        "v": pa.array(rng.random(n)),
    })
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path)
    S = T.Schema([T.Field("k", T.INT64), T.Field("v", T.FLOAT64)])

    sc = P.scan(S, [(path, [])])
    x = P.shuffle_exchange(sc, [ir.col("k")], 4)
    final = P.hash_agg(x, "partial", [ir.col("k")], ["k"],
                       [{"fn": "sum", "args": [ir.col("v")],
                         "dtype": T.FLOAT64, "name": "s"}],
                       T.Schema([T.Field("k", T.INT64)]))
    info = {}
    out = run_plan(final, num_partitions=4, mesh_exchange="auto",
                   mesh_quota=8, run_info=info)
    d = out.to_numpy()
    from blaze_tpu.ops.agg import AGG_BUF_PREFIX
    assert int(out.num_rows) == 1
    # the stage stayed a mesh stage; the batch that overflowed went through
    # files in place, which makes it a file stage too (what a benchmark
    # cell's guarantee refuses), and those are the exchanged bytes that
    # crossed the host (16 bytes a live row and its share of the padding's
    # validity)
    assert info["mesh_stages"] == 1 and info["file_stages"] == 1
    assert info["mesh_host_bytes"] >= 16 * n
    np.testing.assert_allclose(float(np.asarray(d[f"{AGG_BUF_PREFIX}.0.sum"])[0]),
                               float(np.sum(t.column("v").to_numpy())),
                               rtol=1e-9)


def test_stage_exchange_streams_without_reexecution(rng, tmp_path):
    """Overflowing batches go to the file path IN PLACE: the map subplan
    runs exactly once per task, already-exchanged batches are kept, and
    the provider serves a mix of mesh parts and file segments
    (VERDICT r2 weak-3: no stage pooling, no double execution)."""
    from blaze_tpu.plan import plan_pb2 as pb
    from blaze_tpu.plan.to_proto import encode_schema
    from blaze_tpu.parallel.stage_exchange import run_mesh_shuffle_stage
    from blaze_tpu.runtime import resources

    calls = {"n": 0}
    # first batch exchanges cleanly; second is fully skewed -> overflows a
    # tiny quota and must spill to the file path without re-running the map
    b1 = ColumnBatch.from_numpy(
        {"k": rng.integers(0, 1000, 64).astype(np.int64),
         "v": rng.random(64)}, SCHEMA)
    b2 = ColumnBatch.from_numpy(
        {"k": np.full(64, 7, np.int64), "v": rng.random(64)}, SCHEMA)

    def provider():
        calls["n"] += 1
        return iter([b1, b2])

    rid = resources.register(provider)
    node = pb.PlanNode()
    w = node.shuffle_writer
    w.input.ffi_reader.schema.CopyFrom(encode_schema(SCHEMA))
    w.input.ffi_reader.export_iter_resource_id = rid
    w.partitioning.kind = pb.HashRepartition.HASH
    w.partitioning.num_partitions = 4
    ke = w.partitioning.keys.add()
    ke.column.name = "k"

    ok = run_mesh_shuffle_stage(node, stage_id=991, ntasks=1, quota=8,
                                work_dir=str(tmp_path))
    assert ok
    assert calls["n"] == 1, "map subplan must execute exactly once"

    # all 128 rows come back across the 4 partitions, once each
    reader = resources.get("shuffle:991")
    got = []
    for p in range(4):
        for b in reader(p):
            # the provider may yield host frames (serde.HostBatch) for
            # IpcReaderExec to coalesce — normalize for the assert
            if not hasattr(b, "to_numpy"):
                from blaze_tpu.ops.host_sort import host_to_device

                b = host_to_device(b)
            d = b.to_numpy()
            got += list(zip(np.asarray(d["k"]), [float(x) for x in d["v"]]))
    want = []
    for b in (b1, b2):
        d = b.to_numpy()
        want += list(zip(np.asarray(d["k"]), [float(x) for x in d["v"]]))
    assert sorted(got) == sorted(want)
    resources.pop("shuffle:991")
    resources.pop(rid)


def test_partitions_exceed_devices(rng, tmp_path):
    """P > D (VERDICT r4 #7): a 16-partition exchange over the 8-device
    mesh routes rows to owner devices (2 partitions each) with one
    all_to_all, then splits locally. Every row arrives exactly once at
    the partition the Spark hash chose."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from blaze_tpu.spark import plan_model as P
    from blaze_tpu.spark.local_runner import run_plan
    from blaze_tpu.exprs import ir

    n = 3000
    t = pa.table({
        "k": pa.array(rng.integers(0, 5000, n).astype(np.int64)),
        "v": pa.array(rng.random(n)),
    })
    path = str(tmp_path / "t16.parquet")
    pq.write_table(t, path)
    S = T.Schema([T.Field("k", T.INT64), T.Field("v", T.FLOAT64)])

    sc = P.scan(S, [(path, [])])
    x = P.shuffle_exchange(sc, [ir.col("k")], 16)
    srt = P.sort(x, [(ir.col("k"), True, True),
                     (ir.col("v"), True, True)])
    info = {}
    out = run_plan(srt, num_partitions=16, mesh_exchange="auto",
                   run_info=info)
    assert info["mesh_stages"] == 1, info  # the exchange rode the mesh
    d = out.to_numpy()
    got = sorted(zip(np.asarray(d["k"]), [float(x) for x in d["v"]]))
    want = sorted(zip(t.column("k").to_numpy(),
                      t.column("v").to_numpy()))
    assert len(got) == len(want)
    for (gk, gv), (wk, wv) in zip(got, want):
        assert gk == wk
        np.testing.assert_allclose(gv, wv, rtol=1e-12)
