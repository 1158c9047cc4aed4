"""The per-group reductions of ops/segment.py compiled at real capacities
for a described TPU v5e (the chip's compiler, no chip attached): what the
chip would run holds no scatter, and the conditional of `_at_group_rows` is
there only past `seg._PLAIN` slots. A compile that passes is not a chip
run: times are in PERF.md, from the chip.

The topology is described inside a fixture, never at import: one process at
a time may load the TPU's library, and under xdist only the worker that is
given this file should."""

import os

import jax
import jax.numpy as jnp
import pytest

from blaze_tpu.ops import segment as seg


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to hold
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _q06core_reductions(starts, gid, num_groups, start_idx, end_idx,
                        row_mask, group_mask, ext, ext_valid, price,
                        price_valid):
    layout = seg.GroupLayout(starts, gid, num_groups, start_idx, end_idx,
                             row_mask, group_mask)
    return (seg.seg_sum(ext, layout, ext_valid),
            seg.seg_count(ext_valid, layout),
            seg.seg_sum(price, layout, price_valid),
            seg.seg_count(price_valid, layout))


@pytest.mark.parametrize("cap", [seg._PLAIN, 1 << 21])
def test_seg_reductions_compile_for_v5e_without_scatter(one_chip, cap):
    def arg(dtype, shape=(cap,)):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, i32, f64 = jnp.bool_, jnp.int32, jnp.float64
    text = jax.jit(_q06core_reductions).lower(
        arg(b), arg(i32), arg(i32, ()), arg(i32), arg(i32), arg(b), arg(b),
        arg(f64), arg(b), arg(f64), arg(b)).compile().as_text()
    assert " scatter(" not in text
    assert " while(" in text  # the blocked scans
    assert text.count(" conditional(") == (4 if cap > seg._PLAIN else 0)


def test_integer_compaction_compiles_for_v5e_without_a_gather(one_chip):
    """The filter's compaction of q06-core's decimal batch at 2^21 rows, as
    the chip's compiler leaves it: scatters of one 32-bit operand each (a
    64-bit scatter is one program of two operands, fifteen times dearer a
    row on the chip), no gather."""
    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import Column, ColumnBatch

    cap = 1 << 21

    def arg(dtype, shape=(cap,)):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    money = T.decimal(7, 2)
    schema = T.Schema([T.Field("k", T.INT64), T.Field("p", money),
                       T.Field("e", money)])
    batch = ColumnBatch(schema, [
        Column(T.INT64, arg(jnp.int64), None),
        Column(money, arg(jnp.int64), arg(jnp.bool_)),
        Column(money, arg(jnp.int64), arg(jnp.bool_))],
        arg(jnp.int32, ()), cap)

    def keep_dear(b):
        c = b.columns[2]
        return b.compact((c.data > 10000) & c.valid_mask())

    text = jax.jit(keep_dear).lower(batch).compile().as_text()
    assert " gather(" not in text
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert scatters and all(
        line.split(" scatter(")[0].count("[2097152]") == 1
        for line in scatters), scatters[:2]


def test_the_masked_work_program_compiles_for_v5e_without_moving_a_row(
        one_chip):
    """q06-core's filter, carried by its partial aggregate (ops/agg
    `_mask_filter`): the program that builds the work batch at 2^21 rows
    evaluates the predicate beside the planes it passes on, and holds no
    gather, no scatter and no sort: the double planes the filter's own
    program gathered (41-55 ms each on the chip) are not moved before the
    collapse's sort moves them once."""
    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import Column, ColumnBatch
    from blaze_tpu.exprs import ir
    from blaze_tpu.ops.agg import KEEP_PLANE, AggCall, AggExec, AggMode
    from blaze_tpu.ops.basic import FilterExec, MemorySourceExec

    cap = 1 << 21

    def arg(dtype, shape=(cap,)):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    schema = T.Schema([T.Field("ss_item_sk", T.INT64),
                       T.Field("ss_sales_price", T.FLOAT64),
                       T.Field("ss_ext_sales_price", T.FLOAT64)])
    batch = ColumnBatch(schema, [
        Column(T.INT64, arg(jnp.int64), None),
        Column(T.FLOAT64, arg(jnp.float64), arg(jnp.bool_)),
        Column(T.FLOAT64, arg(jnp.float64), arg(jnp.bool_))],
        arg(jnp.int32, ()), cap)
    filt = FilterExec(MemorySourceExec([], schema), [ir.Binary(
        ir.BinOp.GT, ir.col("ss_ext_sales_price"), ir.lit(100.0))])
    partial = AggExec(filt, [ir.col("ss_item_sk")], ["item"], [
        AggCall("sum", (ir.col("ss_ext_sales_price"),), T.FLOAT64, "total"),
        AggCall("count", (ir.col("ss_ext_sales_price"),), T.INT64, "cnt"),
        AggCall("avg", (ir.col("ss_sales_price"),), T.FLOAT64, "avg_price"),
    ], AggMode.PARTIAL)
    assert partial._mask_filter() is filt
    lowered = jax.jit(partial._work_fn(filt)).lower(batch)
    work, kept = lowered.out_info
    assert work.schema.names()[-1] == KEEP_PLANE and kept.shape == ()
    text = lowered.compile().as_text()
    for moved in (" gather(", " scatter(", " sort("):
        assert moved not in text, moved


def test_slice_batch_compiles_for_v5e_to_copies(one_chip, monkeypatch):
    """The cut of one partition out of q3's exchanged fact batch (three
    nullable 8-byte columns, 2^21 slots grouped by partition, to the
    partition's bucket 2^20, at a traced start), as the chip's compiler
    leaves `ops.common.slice_batch`'s program: dynamic slices of the padded
    planes, fused; no gather (100 ms on the chip for this shape, against
    0.15 ms: PERF.md) and no scatter."""
    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import Column, ColumnBatch
    from blaze_tpu.ops.common import slice_batch
    from blaze_tpu.runtime import jit_cache

    cap = 1 << 21

    def arg(dtype, shape=(cap,)):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    schema = T.Schema([T.Field("ss_sold_date_sk", T.INT64),
                       T.Field("ss_item_sk", T.INT64),
                       T.Field("ss_ext_sales_price", T.FLOAT64)])
    batch = ColumnBatch(schema, [
        Column(T.INT64, arg(jnp.int64), arg(jnp.bool_)),
        Column(T.INT64, arg(jnp.int64), arg(jnp.bool_)),
        Column(T.FLOAT64, arg(jnp.float64), arg(jnp.bool_))],
        arg(jnp.int32, ()), cap)
    made = {}

    def capture(key, make):
        made[key[:2]] = make()
        return lambda b, start, count: (start.dtype, count.dtype)

    monkeypatch.setattr(jit_cache, "get_or_compile", capture)
    assert slice_batch(batch, 1_563_500, 533_600) == (jnp.int32, jnp.int32)
    lowered = jax.jit(made["slice", 1 << 20]).lower(batch, arg(jnp.int32, ()),
                                 arg(jnp.int32, ()))
    assert lowered.out_info.capacity == 1 << 20
    text = lowered.compile().as_text()
    assert " gather(" not in text and " scatter(" not in text
    assert " dynamic-slice(" in text


@pytest.mark.parametrize("caps, out", [
    # a partition's fact slices of q3 at SF10, four to a 2^21 batch
    ([1 << 20, 1 << 19, 1 << 20, 1 << 19], 1 << 21),
    # sixteen date-join outputs' slices, one 2^20 probe of the item join
    ([1 << 16] * 16, 1 << 20),
])
def test_pack_slices_compiles_for_v5e_to_copies(one_chip, monkeypatch, caps,
                                                out):
    """A reduce partition's kept slices packed into one batch (three
    nullable 8-byte columns, offsets traced), as the chip's compiler leaves
    `stage_exchange.pack_slices`' program: dynamic update slices of whole
    planes; no gather and no scatter (`concat_batches` gathers every plane
    of the concatenated capacities by a computed index: 16-34 ms a 2^21
    plane on the chip, PERF.md)."""
    import numpy as np

    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import Column, ColumnBatch
    from blaze_tpu.parallel.stage_exchange import pack_slices
    from blaze_tpu.runtime import jit_cache

    def arg(dtype, shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    schema = T.Schema([T.Field("ss_sold_date_sk", T.INT64),
                       T.Field("ss_item_sk", T.INT64),
                       T.Field("ss_ext_sales_price", T.FLOAT64)])

    def batch(cap):
        return ColumnBatch(schema, [
            Column(f.dtype, arg(f.dtype.np_dtype(), (cap,)),
                   arg(jnp.bool_, (cap,))) for f in schema.fields],
            arg(jnp.int32, ()), cap)

    slices = [(batch(cap), cap * 5 // 8) for cap in caps]
    made = {}

    def capture(key, make):
        made[key[:2]] = make()
        return lambda starts, *bs: starts

    monkeypatch.setattr(jit_cache, "get_or_compile", capture)
    starts = pack_slices(slices, schema)
    assert starts.dtype == np.int32 and starts[-1] == sum(caps) * 5 // 8
    lowered = jax.jit(made["exchange_pack", out]).lower(
        arg(jnp.int32, starts.shape), *[b for b, _ in slices])
    assert lowered.out_info.capacity == out
    text = lowered.compile().as_text()
    assert " gather(" not in text and " scatter(" not in text
    assert " dynamic-update-slice(" in text


def test_the_exchanges_grouping_compiles_for_v5e_without_a_sort(one_chip):
    """q3's fact batch grouped by partition on one chip (three nullable
    8-byte columns, 2^21 slots, murmur3 over the date key, four
    partitions), as the chip's compiler leaves `local_xchg`'s program
    (`stage_exchange.group_by_partition`): the ranks by blocked scans and
    no sort of the program's own (the compiler sorts the indices of a
    scatter of flags, and only those); every scatter of one operand; a
    gather for the double."""
    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import Column, ColumnBatch
    from blaze_tpu.parallel.shuffle import partition_ids
    from blaze_tpu.parallel.stage_exchange import group_by_partition

    cap = 1 << 21

    def arg(dtype, shape=(cap,)):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    schema = T.Schema([T.Field("ss_sold_date_sk", T.INT64),
                       T.Field("ss_item_sk", T.INT64),
                       T.Field("ss_ext_sales_price", T.FLOAT64)])
    batch = ColumnBatch(schema, [
        Column(f.dtype, arg(f.dtype.np_dtype()), arg(jnp.bool_))
        for f in schema.fields], arg(jnp.int32, ()), cap)

    def grouped(b):
        return group_by_partition(b, partition_ids(b, [0], 4), 4)

    text = jax.jit(grouped).lower(batch).compile().as_text()
    assert " while(" in text
    sorts = [line for line in text.splitlines() if " sort(" in line]
    assert all('/scatter"' in line for line in sorts), sorts[:1]
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert scatters and all(
        line.split(" scatter(")[0].count("[2097152]") == 1
        for line in scatters), scatters[:2]
    assert text.count(" gather(") >= 1
