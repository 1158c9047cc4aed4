"""The one-chip exchange groups a batch by scattering rows to their ranks
(`stage_exchange.group_by_partition`): a row's slot is its partition's start
plus the number of earlier rows of its partition, found by running counts up
to `COUNTED_PARTITIONS` partitions and by inverting a stable sort's
permutation past it, and the planes go there by `ColumnBatch.place_rows`.
Checked against the program it replaced (a stable sort by partition id, a
gather of every plane by the permutation, the bounds by a second sort), plane
for plane over the live rows, for every kind of column, partition count and
way the rows can fall; `compact`, which shares `place_rows`, traces as it
did; `local_xchg` adds its planes to TELEMETRY once a dispatch."""

import decimal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import (
    Column, ColumnBatch, _ranks_well, rows_to_ranks,
)
from blaze_tpu.ops.join import sort_batch_by_keys
from blaze_tpu.parallel.shuffle import partition_ids
from blaze_tpu.parallel.stage_exchange import (
    COUNTED_PARTITIONS, group_by_partition, run_mesh_shuffle_stage,
)
from blaze_tpu.runtime import compile_service, resources
from test_slice_rows import SCHEMA, _dict_column, _writer_node


def _column(kind, n, cap, rng):
    """(dtype, Column) of `n` live rows at capacity `cap`."""
    i = np.arange(n)
    nulls = rng.random(n) > 0.15
    if kind == "dict_string":
        return T.STRING, _dict_column(n, cap)
    if kind == "double_specials":
        x = rng.normal(size=n) * 1e3
        x[::7] = np.nan
        x[1::11] = np.inf
        x[2::13] = -np.inf
        x[3::5] = -0.0
        raw, dtype = x, T.FLOAT64
    else:
        dtype, raw = {
            "int64": (T.INT64, rng.integers(-2 ** 62, 2 ** 62, n)),
            "int32": (T.INT32, rng.integers(-2 ** 31, 2 ** 31, n)
                      .astype(np.int32)),
            "bool": (T.BOOLEAN, rng.random(n) > 0.5),
            "string": (T.STRING, [b"r%d" % (k * 7919 % 1000) * (1 + k % 3)
                                  for k in range(n)]),
            "decimal_7_2": (T.decimal(7, 2),
                            rng.integers(-10 ** 7 + 1, 10 ** 7, n)),
            "wide_decimal": (T.decimal(30, 4),
                             [decimal.Decimal(int(k) * 10 ** 20 + 3 * int(k))
                              .scaleb(-4) for k in i]),
        }[kind]
    b = ColumnBatch.from_numpy({"x": raw}, T.Schema([T.Field("x", dtype)]),
                               capacity=cap, validity={"x": nulls})
    return dtype, b.columns[0]


KINDS = ["int64", "int32", "double_specials", "bool", "string", "dict_string",
         "decimal_7_2", "wide_decimal"]


def _batch(kinds, n, cap, seed=40):
    rng = np.random.default_rng(seed)
    fields, cols = [], []
    for j, kind in enumerate(kinds):
        dtype, col = _column(kind, n, cap, rng)
        fields.append(T.Field(f"c{j}", dtype))
        cols.append(col)
    return ColumnBatch(T.Schema(fields), cols, jnp.asarray(n, jnp.int32), cap)


def _sorted_and_gathered(b, pid, partitions):
    """The program `group_by_partition` replaced, as it was."""
    sb = sort_batch_by_keys(b, [pid.astype(jnp.uint32)])
    bounds = jnp.searchsorted(
        jnp.sort(pid), jnp.arange(partitions + 1, dtype=jnp.int32))
    return sb, bounds


def _assert_same_planes(got, want, n):
    """Every plane equal over the live rows, bit for bit (a double's NaN
    and -0.0 included); a dictionary, shared, equal whole."""
    assert jax.tree.structure(got.columns) == jax.tree.structure(want.columns)
    for g, w in zip(jax.tree.leaves(got.columns),
                    jax.tree.leaves(want.columns)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape and g.dtype == w.dtype
        if g.dtype == np.float64:
            g, w = g.view(np.uint64), w.view(np.uint64)
        np.testing.assert_array_equal(g[:n], w[:n])


def _grouped_both_ways(b, pid, partitions):
    got, bounds = jax.jit(group_by_partition, static_argnums=2)(
        b, pid, partitions)
    want, want_bounds = jax.jit(_sorted_and_gathered, static_argnums=2)(
        b, pid, partitions)
    assert bounds.dtype == jnp.int32 and bounds.shape == (partitions + 1,)
    np.testing.assert_array_equal(np.asarray(bounds), np.asarray(want_bounds))
    assert int(got.num_rows) == int(b.num_rows)
    _assert_same_planes(got, want, int(b.num_rows))
    return np.asarray(bounds)


@pytest.mark.parametrize("kind", KINDS)
def test_each_kind_of_column_lands_where_the_sort_put_it(kind):
    """The column is its own key: murmur3 over it (nulls included) picks
    the partition, as `exchange_local` picks it."""
    b = _batch([kind], n=900, cap=1 << 10)
    pid = jax.jit(lambda x: partition_ids(x, [0], 4))(b)
    assert _grouped_both_ways(b, pid, 4)[-1] == 900


PARTITIONS = [2, 4, COUNTED_PARTITIONS + 8]
# where a batch's rows fall: pid of the live rows from (rng, P, n)
FALLS = {
    "spread": lambda rng, p, n: rng.integers(0, p, n),
    "all_in_one_partition": lambda rng, p, n: np.full(n, p - 1),
    "empty_partitions": lambda rng, p, n: 2 * rng.integers(0, (p + 1) // 2,
                                                           n) % p,
    "runs_of_one_partition": lambda rng, p, n: (np.arange(n) // 97) % p,
}


@pytest.mark.parametrize("cap", [1 << 10, 1 << 16])
@pytest.mark.parametrize("fall", sorted(FALLS))
@pytest.mark.parametrize("partitions", PARTITIONS)
def test_a_batch_of_every_kind_groups_as_the_sort_grouped_it(
        partitions, fall, cap):
    """Every kind in one batch, fewer live rows than slots (padding's pid
    is P, as `partition_ids` gives it), or every slot live."""
    n = cap - cap // 3 if fall != "spread" else cap
    b = _batch(KINDS, n=n, cap=cap)
    rng = np.random.default_rng(partitions * 31 + cap)
    pid = np.full(cap, partitions, np.int32)
    pid[:n] = FALLS[fall](rng, partitions, n)
    bounds = _grouped_both_ways(b, jnp.asarray(pid), partitions)
    np.testing.assert_array_equal(
        np.diff(bounds), np.bincount(pid[:n], minlength=partitions + 1)[:-1])


@pytest.mark.parametrize("partitions, sorts", [
    (4, False), (COUNTED_PARTITIONS, False), (COUNTED_PARTITIONS + 1, True)])
def test_the_ranks_are_counted_up_to_the_threshold_and_sorted_past_it(
        partitions, sorts):
    b = _batch(["int64", "double_specials"], n=100, cap=128)
    pid = jnp.zeros((128,), jnp.int32)
    jaxpr = str(jax.make_jaxpr(
        lambda x, p: group_by_partition(x, p, partitions))(b, pid))
    assert (" sort[" in jaxpr) == sorts


def _compact_as_it_was(self, keep):
    """`ColumnBatch.compact` before `place_rows` was factored out of it."""
    mask = keep & self.row_mask()
    n = jnp.sum(mask, dtype=jnp.int32)
    dest = jnp.where(mask, jnp.cumsum(mask, dtype=jnp.int32) - 1,
                     self.capacity)
    idx, cols = None, []
    for c in self.columns:
        if _ranks_well(c.data):
            v = c.validity
            cols.append(Column(c.dtype, rows_to_ranks(c.data, dest),
                               None if v is None
                               else rows_to_ranks(v, dest)))
            continue
        if idx is None:
            idx = rows_to_ranks(
                jnp.arange(self.capacity, dtype=jnp.int32), dest)
        cols.append(c.take(idx))
    return ColumnBatch(self.schema, cols, n, self.capacity)


def test_compact_traces_the_program_it_traced_before():
    b = _batch(KINDS, n=200, cap=256)
    keep = jnp.asarray(np.arange(256) % 3 != 1)
    now = jax.make_jaxpr(lambda x, k: x.compact(k))(b, keep)
    before = jax.make_jaxpr(_compact_as_it_was)(b, keep)
    assert str(now) == str(before)


def test_local_xchg_adds_its_planes_once_a_dispatch(monkeypatch):
    """Three batches through `run_mesh_shuffle_stage` on one chip: a batch
    is `k` int64 with nulls (data and validity ranked), `v` double and `s`
    string, neither with nulls (one plane each, gathered)."""
    real = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a, **k: real(*a, **k)[:1])
    rng = np.random.default_rng(40)
    batches = []
    for n in (300, 64, 1000):
        batches.append(ColumnBatch.from_numpy(
            {"k": rng.integers(0, 5000, n), "v": rng.random(n),
             "s": [b"x%d" % r for r in range(n)]}, SCHEMA,
            validity={"k": rng.random(n) > 0.1}))
    node, rid = _writer_node(batches, 4)
    names = ("exchange_planes_ranked", "exchange_planes_gathered")
    before = compile_service.TELEMETRY.snapshot()
    try:
        assert run_mesh_shuffle_stage(node, stage_id=940, ntasks=1)
    finally:
        resources.pop("shuffle:940")
        resources.pop(rid)
    after = compile_service.TELEMETRY.snapshot()
    assert [after[k] - before.get(k, 0) for k in names] == [6, 6]
