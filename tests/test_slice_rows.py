"""A contiguous range of rows is copied, not gathered (PR 35):
`ColumnBatch.slice_rows` against `ColumnBatch.take` of the same range over
every kind of column and every way a range can lie in a batch;
`ops.common.slice_batch`, which takes the copy for every batch whose planes
are row-aligned and the gather for one with a list column, and counts which;
`exchange_local`, `deal_out` and `fit` of `run_mesh_shuffle_stage`, which cut
their partitions, shards and rounds with it."""

import decimal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import (
    Column, ColumnBatch, DictData, bucket_capacity,
)
from blaze_tpu.ops.common import slice_batch
from blaze_tpu.runtime import compile_service

CAP = 64     # the input's capacity
ROWS = 64    # live up to the last slot


def _strings(n):
    return [None if i % 7 == 3 else b"s%03d" % i * (1 + i % 3)
            for i in range(n)]


def _dict_column(n, cap):
    words = [b"", b"ab", b"cdef", b"g", b"hijkl"]
    width = 8
    db = np.zeros((8, width), np.uint8)
    dl = np.zeros((8,), np.int32)
    for i, w in enumerate(words):
        db[i, :len(w)] = np.frombuffer(w, np.uint8)
        dl[i] = len(w)
    codes = np.zeros((cap,), np.int32)
    codes[:n] = 1 + np.arange(n) % 4
    valid = np.zeros((cap,), bool)
    valid[:n] = np.arange(n) % 5 != 2
    codes[~valid] = 0
    return Column(T.STRING, DictData(jnp.asarray(codes), jnp.asarray(db),
                                     jnp.asarray(dl)), jnp.asarray(valid))


def _column(kind, n, cap):
    """(dtype, Column) of `n` live rows at capacity `cap`, distinct values
    row by row so a slice one row off shows."""
    i = np.arange(n)
    nulls = {"x": i % 6 != 4}
    simple = {
        "int32": (T.INT32, (i * 3 - 50).astype(np.int32), None),
        "int64": (T.INT64, i.astype(np.int64) << 33, None),
        "int64_nullable": (T.INT64, i.astype(np.int64) << 33, nulls),
        "double": (T.FLOAT64, i * 0.25 - 3.0, None),
        "double_nullable": (T.FLOAT64, i * 0.25 - 3.0, nulls),
        "bool": (T.BOOLEAN, i % 3 == 0, None),
        "bool_nullable": (T.BOOLEAN, i % 3 == 0, nulls),
        "decimal_7_2": (T.decimal(7, 2), (i * 101).astype(np.int64), None),
        "decimal_7_2_nullable": (T.decimal(7, 2),
                                 (i * 101).astype(np.int64), nulls),
        "string": (T.STRING, _strings(n), None),
        "struct": (T.struct_of([T.Field("a", T.INT64),
                                T.Field("s", T.STRING)]),
                   [None if k % 9 == 5 else (int(k), b"v%d" % k)
                    for k in range(n)], None),
        "wide_decimal": (T.decimal(30, 4),
                         [None if k % 8 == 1
                          else decimal.Decimal(k * 10 ** 20 + k).scaleb(-4)
                          for k in range(n)], None),
        "list": (T.list_of(T.INT64),
                 [None if k % 10 == 7 else list(range(k % 4))
                  for k in range(n)], None),
    }
    if kind == "dict_string":
        return T.STRING, _dict_column(n, cap)
    dtype, raw, validity = simple[kind]
    b = ColumnBatch.from_numpy({"x": raw}, T.Schema([T.Field("x", dtype)]),
                               capacity=cap, validity=validity)
    return dtype, b.columns[0]


def _batch(kinds, n=ROWS, cap=CAP):
    fields, cols = [], []
    for j, kind in enumerate(kinds):
        dtype, col = _column(kind, n, cap)
        fields.append(T.Field(f"c{j}", dtype))
        cols.append(col)
    return ColumnBatch(T.Schema(fields), cols, jnp.asarray(n, jnp.int32),
                       cap)


def _live(batch):
    """The live rows as plain Python values, column by column."""
    out = {}
    for name, vals in batch.to_numpy().items():
        out[name] = [v.tolist() if isinstance(v, np.ndarray) else v
                     for v in (vals.tolist() if isinstance(vals, np.ndarray)
                               else vals)]
    return out


ROW_ALIGNED = ["int32", "int64", "int64_nullable", "double",
               "double_nullable", "bool", "bool_nullable", "decimal_7_2",
               "decimal_7_2_nullable", "string", "dict_string", "struct",
               "wide_decimal"]
# (start, cap, count): where a range can lie in a batch of 64 live rows
RANGES = {
    "from_row_0": (0, 16, 13),
    "inside": (20, 16, 16),
    "past_the_capacity": (45, 32, 19),     # 45 + 32 > 64, live to slot 63
    "count_0": (30, 16, 0),
    "start_past_num_rows": (64, 16, 5),
    "cap_is_the_capacity": (7, 64, 57),
}


@pytest.mark.parametrize("where", sorted(RANGES))
@pytest.mark.parametrize("kind", ROW_ALIGNED)
def test_the_copy_holds_the_rows_take_gathers(kind, where):
    start, cap, count = RANGES[where]
    b = _batch([kind])
    assert b.row_aligned
    rows = min(max(ROWS - start, 0), count)
    want = b.take(jnp.arange(cap, dtype=jnp.int32) + start, rows)
    got = jax.jit(lambda x, s: x.slice_rows(s, cap, rows))(
        b, jnp.asarray(start, jnp.int32))
    assert got.capacity == want.capacity == cap
    assert int(got.num_rows) == int(want.num_rows) == rows
    assert got.shape_key() == want.shape_key()
    assert _live(got) == _live(want)
    if kind == "dict_string":   # the dictionary is shared, never copied
        np.testing.assert_array_equal(
            np.asarray(got.columns[0].data.dict_bytes),
            np.asarray(b.columns[0].data.dict_bytes))


def _counters():
    v = compile_service.TELEMETRY.snapshot()
    return v.get("slice_copies", 0), v.get("slice_gathers", 0)


@pytest.mark.parametrize("kinds, copies", [
    (["int64_nullable", "double_nullable", "int64_nullable"], True),   # q3
    (["string", "dict_string", "struct", "wide_decimal", "bool"], True),
    (["int64", "list"], False),
    (["list"], False),
])
def test_slice_batch_copies_unless_a_list_column_is_there(kinds, copies):
    b = _batch(kinds, n=50)
    assert b.row_aligned == copies
    c0, g0 = _counters()
    cuts = [(0, 9), (17, 33), (40, 10), (50, 3), (12, 0)]
    for start, count in cuts:
        got = slice_batch(b, start, count)
        rows = min(max(50 - start, 0), count)
        assert got.capacity == bucket_capacity(count)
        assert int(got.num_rows) == rows
        want = b.take(jnp.arange(got.capacity, dtype=jnp.int32) + start,
                      rows)
        assert _live(got) == _live(want)
    c1, g1 = _counters()
    assert (c1 - c0, g1 - g0) == ((len(cuts), 0) if copies
                                  else (0, len(cuts)))


def test_slice_batch_at_a_given_capacity():
    """`deal_out`'s cut: a share of the rows at the round's capacity, which
    may pass the batch's own."""
    b = _batch(["int64_nullable", "string"], n=60)
    got = slice_batch(b, 48, 12, 32)
    assert got.capacity == 32 and int(got.num_rows) == 12
    want = b.take(jnp.arange(32, dtype=jnp.int32) + 48, 12)
    assert _live(got) == _live(want)


@pytest.mark.parametrize("kind", ["int64_nullable", "string", "struct"])
@pytest.mark.parametrize("cap", [16, 64, 256])
def test_fit_by_copy_is_fit_by_take(kind, cap):
    """`fit`'s program, a batch at another capacity (smaller or larger)
    where it lies, against the parent's form."""
    b = _batch([kind], n=11)
    want = b.take(jnp.minimum(jnp.arange(cap, dtype=jnp.int32),
                              b.capacity - 1), b.num_rows)
    got = jax.jit(lambda x: x.slice_rows(0, cap, x.num_rows))(b)
    assert got.capacity == cap and int(got.num_rows) == 11
    assert got.shape_key() == want.shape_key()
    assert _live(got) == _live(want)


SCHEMA = T.Schema([T.Field("k", T.INT64), T.Field("v", T.FLOAT64),
                   T.Field("s", T.STRING)])


def _writer_node(batches, partitions):
    from blaze_tpu.plan import plan_pb2 as pb
    from blaze_tpu.plan.to_proto import encode_schema
    from blaze_tpu.runtime import resources

    rid = resources.register(lambda: iter(batches))
    node = pb.PlanNode()
    w = node.shuffle_writer
    w.input.ffi_reader.schema.CopyFrom(encode_schema(SCHEMA))
    w.input.ffi_reader.export_iter_resource_id = rid
    w.partitioning.kind = pb.HashRepartition.HASH
    w.partitioning.num_partitions = partitions
    w.partitioning.keys.add().column.name = "k"
    return node, rid


def _exchange(monkeypatch, ndev, sizes, stage_id):
    """`run_mesh_shuffle_stage` over batches of `sizes` rows on `ndev`
    chips: the batches with each row's partition id, and what the provider
    hands each of the four partitions."""
    from blaze_tpu.parallel.shuffle import partition_ids
    from blaze_tpu.parallel.stage_exchange import run_mesh_shuffle_stage
    from blaze_tpu.runtime import resources

    real = jax.devices
    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: real(*a, **k)[:ndev])
    rng = np.random.default_rng(35)
    batches, base = [], 0
    for n in sizes:
        keys = rng.integers(0, 5000, n).astype(np.int64)
        batches.append(ColumnBatch.from_numpy(
            {"k": keys, "v": np.arange(base, base + n) * 0.5,
             "s": [b"r%d" % r for r in range(base, base + n)]}, SCHEMA,
            validity={"k": rng.random(n) > 0.1}))
        base += n
    pids = [np.asarray(partition_ids(b, [0], 4))[:n]
            for b, n in zip(batches, sizes)]
    node, rid = _writer_node(batches, 4)
    stats = {}
    c0 = _counters()
    assert run_mesh_shuffle_stage(node, stage_id=stage_id, ntasks=1,
                                  stats=stats)
    reader = resources.get(f"shuffle:{stage_id}")
    got = [[_live(b) for b in reader(p)] for p in range(4)]
    resources.pop(f"shuffle:{stage_id}")
    resources.pop(rid)
    c1 = _counters()
    return batches, pids, got, stats, (c1[0] - c0[0], c1[1] - c0[1])


def test_exchange_local_keeps_each_partitions_rows_in_sorted_order(
        monkeypatch):
    """One chip: a batch is grouped by partition id, each row scattered to
    its partition's start plus its rank among that partition's rows (so a
    partition's rows keep the batch's order), and cut at the bounds, one
    slice a non-empty partition a batch, in the order the batches came;
    what a partition is handed is those slices' rows in that order, in
    fewer batches where slices of one layout were packed (the first batch's
    strings are narrower than the third's)."""
    sizes = [700, 64, 1500]
    batches, pids, got, stats, (copies, gathers) = _exchange(
        monkeypatch, 1, sizes, 935)
    assert stats["slices_cut"] == copies == 12 and gathers == 0
    assert stats["slices"] < 12 and stats["slices_packed"] > 0
    assert stats["slice_rows"] == sum(sizes)
    for p in range(4):
        want = {name: [] for name in ("k", "v", "s")}
        for b, pid in zip(batches, pids):
            full = _live(b)
            for name, vals in full.items():
                want[name] += [vals[r] for r in np.flatnonzero(pid == p)]
        have = {name: [v for piece in got[p] for v in piece[name]]
                for name in want}
        assert have == want


def test_deal_out_and_the_chips_cuts_keep_every_row(monkeypatch):
    """Four chips: a batch is dealt out from chip 0 in four cuts at one
    capacity (3001 rows: the last share is the short one), and each chip
    cuts its partition out of its received shard: each partition holds
    exactly its rows."""
    sizes = [3001, 130]
    batches, pids, got, stats, (copies, gathers) = _exchange(
        monkeypatch, 4, sizes, 936)
    assert stats["devices"] == 4 and gathers == 0
    assert copies == 4 * len(sizes) + stats["slices_cut"]
    assert stats["slices"] <= stats["slices_cut"]
    for p in range(4):
        # `v` is the row's number, unique and never null
        want = sorted(
            ((v, k, s) for b, pid in zip(batches, pids)
             for k, v, s, q in zip(*_live(b).values(), pid) if q == p))
        have = sorted((v, k, s) for piece in got[p]
                      for k, v, s in zip(*piece.values()))
        assert have == want and len(want) > 0
