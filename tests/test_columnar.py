"""Columnar batch model + Arrow interop tests.

Ref test analog: arrow round-trips exercised implicitly by batch_serde tests
(datafusion-ext-commons io/batch_serde.rs roundtrip pattern).
"""

from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

from blaze_tpu.columnar import (
    ColumnBatch, Schema, Field, INT32, INT64, FLOAT64, STRING, BOOLEAN, decimal,
)
from blaze_tpu.columnar.arrow_io import batch_from_arrow, batch_to_arrow


def test_from_numpy_roundtrip():
    schema = Schema([Field("a", INT32), Field("b", FLOAT64), Field("s", STRING)])
    batch = ColumnBatch.from_numpy(
        {"a": np.array([1, 2, 3]), "b": np.array([1.5, -2.5, 0.0]),
         "s": ["foo", "barbaz", ""]},
        schema,
    )
    assert batch.capacity >= 3
    out = batch.to_numpy()
    np.testing.assert_array_equal(out["a"], [1, 2, 3])
    np.testing.assert_allclose(out["b"], [1.5, -2.5, 0.0])
    assert out["s"] == [b"foo", b"barbaz", b""]


def test_nulls_normalized():
    schema = Schema([Field("a", INT64)])
    batch = ColumnBatch.from_numpy(
        {"a": np.array([10, 99, 30])}, schema,
        validity={"a": np.array([True, False, True])},
    )
    col = batch.columns[0]
    # invalid slots are zeroed (canonical form)
    assert np.asarray(col.data)[1] == 0
    out = batch.to_numpy()
    assert list(out["a"]) == [10, None, 30]


def test_compact():
    schema = Schema([Field("a", INT32), Field("s", STRING)])
    batch = ColumnBatch.from_numpy(
        {"a": np.arange(10, dtype=np.int32), "s": [f"r{i}" for i in range(10)]}, schema)
    keep = np.asarray(np.arange(batch.capacity) % 2 == 0)
    import jax.numpy as jnp

    out = batch.compact(jnp.asarray(keep))
    r = out.to_numpy()
    np.testing.assert_array_equal(r["a"], [0, 2, 4, 6, 8])
    assert r["s"] == [b"r0", b"r2", b"r4", b"r6", b"r8"]


@pytest.mark.parametrize("n,size,fill,density", [
    (1000, 1000, 0, 0.4), (1000, 300, 7, 0.5), (64, 128, 63, 0.9),
    (1024, 1024, 1023, 0.0), (1024, 1024, 0, 1.0)])
def test_nonzero_i32_equals_jnp_nonzero(n, size, fill, density):
    """The 32-bit formulation against the library one it replaces:
    ordering, padding with fill_value, truncation past size."""
    import jax
    import jax.numpy as jnp

    from blaze_tpu.columnar.batch import nonzero_i32

    mask = jnp.asarray(np.random.default_rng(n + size).random(n) < density)
    (want,) = jnp.nonzero(mask, size=size, fill_value=fill)
    got = jax.jit(lambda m: nonzero_i32(m, size, fill))(mask)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_arrow_roundtrip():
    rb = pa.record_batch({
        "i": pa.array([1, None, 3], pa.int32()),
        "l": pa.array([10**12, 2, None], pa.int64()),
        "f": pa.array([1.25, None, -3.5], pa.float64()),
        "s": pa.array(["hello", None, "x" * 33], pa.string()),
        "b": pa.array([True, False, None], pa.bool_()),
        "d": pa.array([None, Decimal("123.45"), Decimal("-0.01")], pa.decimal128(10, 2)),
    })
    batch = batch_from_arrow(rb)
    assert int(batch.num_rows) == 3
    back = batch_to_arrow(batch)
    assert back.column(0).to_pylist() == [1, None, 3]
    assert back.column(1).to_pylist() == [10**12, 2, None]
    assert back.column(2).to_pylist() == [1.25, None, -3.5]
    assert back.column(3).to_pylist() == ["hello", None, "x" * 33]
    assert back.column(4).to_pylist() == [True, False, None]
    assert [str(v) if v is not None else None for v in back.column(5).to_pylist()] == [
        None, "123.45", "-0.01"]


def test_take_with_index_valid():
    import jax.numpy as jnp

    schema = Schema([Field("a", INT32)])
    batch = ColumnBatch.from_numpy({"a": np.array([5, 6, 7])}, schema)
    idx = jnp.asarray(np.zeros(batch.capacity, np.int32))
    iv = jnp.asarray(np.array([True, False] + [False] * (batch.capacity - 2)))
    out = batch.take(idx, 2, index_valid=iv)
    r = out.to_numpy()
    assert list(r["a"]) == [5, None]


# ---- compact: flag and integer planes go to their ranks by a scatter of
# 32-bit words, everything else by a gather at the kept rows' positions ----

_COMPACT_PLANES = {
    "int64": lambda rng, n: rng.integers(-2**62, 2**62, n),
    "int64_edges": lambda rng, n: rng.choice(np.array(
        [-2**63, 2**63 - 1, -1, 0, 1, 2**32, -2**32, 2**31, 0xFFFFFFFF],
        dtype=np.int64), n),
    "uint64": lambda rng, n: rng.integers(0, 2**64, n, dtype=np.uint64),
    "int32": lambda rng, n: rng.integers(-2**31, 2**31, n).astype(np.int32),
    "bool": lambda rng, n: rng.random(n) < 0.5,
    "float64": lambda rng, n: rng.normal(0, 1e9, n),
    "int16": lambda rng, n: rng.integers(-2**15, 2**15, n).astype(np.int16),
}


@pytest.mark.parametrize("num_rows", [0, 1, 1000, 1024])
@pytest.mark.parametrize("plane", sorted(_COMPACT_PLANES))
def test_compact_planes_vs_numpy(plane, num_rows):
    import jax
    import jax.numpy as jnp

    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import Column, _ranks_well

    cap = 1024
    rng = np.random.default_rng(len(plane) * 1000 + num_rows)
    data = _COMPACT_PLANES[plane](rng, cap)
    valid, keep = rng.random(cap) < 0.9, rng.random(cap) < 0.5
    dtype = {"bool": T.BOOLEAN, "int32": T.INT32, "float64": T.FLOAT64,
             "int16": T.INT16}.get(plane, T.INT64)
    batch = ColumnBatch(Schema([Field("x", dtype)]),
                        [Column(dtype, jnp.asarray(data), jnp.asarray(valid))],
                        jnp.asarray(num_rows, jnp.int32), cap)
    assert _ranks_well(batch.columns[0].data) == (
        plane not in ("float64", "int16"))
    out = jax.jit(lambda b, k: b.compact(k))(batch, jnp.asarray(keep))
    kept = np.flatnonzero(keep[:num_rows])
    assert int(out.num_rows) == len(kept) and out.capacity == cap
    got = np.asarray(out.columns[0].data)
    assert got.dtype == data.dtype
    np.testing.assert_array_equal(got[:len(kept)], data[kept])
    np.testing.assert_array_equal(
        np.asarray(out.columns[0].validity)[:len(kept)], valid[kept])


def test_compact_integer_batch_lowers_without_a_gather():
    """A batch of flags and 32/64-bit integers is compacted by scatters
    alone; a double beside them brings the positions and one gather."""
    import jax
    import jax.numpy as jnp

    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import Column

    cap = 1 << 12
    money = decimal(7, 2)

    def batch(money_dtype, plane_dtype):
        def s(dt):
            return jax.ShapeDtypeStruct((cap,), dt)
        fields = [Field("k", INT64), Field("p", money_dtype)]
        cols = [Column(INT64, s(jnp.int64), None),
                Column(money_dtype, s(plane_dtype), s(jnp.bool_))]
        return ColumnBatch(Schema(fields), cols,
                           jax.ShapeDtypeStruct((), jnp.int32), cap)

    def keep_positive(b):
        return b.compact(b.columns[1].data > 0)

    text = jax.jit(keep_positive).lower(batch(money, jnp.int64)).as_text()
    assert "gather" not in text and "scatter" in text
    text = jax.jit(keep_positive).lower(
        batch(T.FLOAT64, jnp.float64)).as_text()
    assert text.count("gather") >= 1 and "scatter" in text
