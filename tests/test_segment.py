"""Segmented-scan grouping utilities vs numpy oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import Column, ColumnBatch
from blaze_tpu.ops import segment as seg
from blaze_tpu.ops.sort_keys import SortSpec, sort_batch

SCHEMA = T.Schema([T.Field("k", T.INT64), T.Field("v", T.FLOAT64)])


def _sorted_batch(rng, n, nulls=False, nkeys=7):
    k = rng.integers(0, nkeys, n).astype(np.int64)
    v = rng.random(n) * 10
    validity = {"v": rng.random(n) > 0.3} if nulls else None
    b = ColumnBatch.from_numpy({"k": k, "v": v}, SCHEMA, validity=validity)
    return sort_batch(b, [SortSpec(0)])


def test_group_layout_counts(rng):
    b = _sorted_batch(rng, 500)
    layout = seg.group_layout(b, [0])
    d = b.to_numpy()
    uniq = np.unique(np.asarray(d["k"][: 500]))
    assert int(layout.num_groups) == len(uniq)


def test_seg_sum_count_min_max(rng):
    b = _sorted_batch(rng, 400, nulls=True)
    layout = seg.group_layout(b, [0])
    vcol = b.columns[1]
    valid = vcol.valid_mask()
    sums = np.asarray(seg.seg_sum(vcol.data, layout, valid))
    counts = np.asarray(seg.seg_count(valid & b.row_mask(), layout))
    mins, mins_ok = seg.seg_min(vcol.data, layout, valid)
    maxs, maxs_ok = seg.seg_max(vcol.data, layout, valid)
    mins, maxs = np.asarray(mins), np.asarray(maxs)

    d = b.to_numpy()
    ks = np.asarray([k for k in d["k"]])
    vs = d["v"]
    G = int(layout.num_groups)
    uniq = sorted(set(ks.tolist()))
    assert G == len(uniq)
    for g, kv in enumerate(uniq):
        idx = [i for i in range(len(ks)) if ks[i] == kv]
        vals = [vs[i] for i in idx if vs[i] is not None]
        np.testing.assert_allclose(sums[g], sum(vals) if vals else 0.0,
                                   rtol=1e-12)
        assert counts[g] == len(vals)
        if vals:
            np.testing.assert_allclose(mins[g], min(vals))
            np.testing.assert_allclose(maxs[g], max(vals))
            assert bool(np.asarray(mins_ok)[g])
        else:
            assert not bool(np.asarray(mins_ok)[g])


def test_seg_first(rng):
    b = _sorted_batch(rng, 300, nulls=True)
    layout = seg.group_layout(b, [0])
    vcol = b.columns[1]
    valid = vcol.valid_mask()
    fv, fok = seg.seg_first(vcol.data, layout, valid, ignores_null=False)
    iv, iok = seg.seg_first(vcol.data, layout, valid, ignores_null=True)
    d = b.to_numpy()
    ks, vs = list(d["k"]), d["v"]
    uniq = sorted(set(ks))
    for g, kv in enumerate(uniq):
        group_vals = [vs[i] for i in range(len(ks)) if ks[i] == kv]
        # first (with nulls): first element, validity = not-null
        if group_vals[0] is None:
            assert not bool(np.asarray(fok)[g])
        else:
            assert bool(np.asarray(fok)[g])
            np.testing.assert_allclose(np.asarray(fv)[g], group_vals[0])
        nonnull = [x for x in group_vals if x is not None]
        if nonnull:
            assert bool(np.asarray(iok)[g])
            np.testing.assert_allclose(np.asarray(iv)[g], nonnull[0])
        else:
            assert not bool(np.asarray(iok)[g])


def test_global_group(rng):
    b = _sorted_batch(rng, 100)
    layout = seg.group_layout(b, [])
    assert int(layout.num_groups) == 1
    sums = seg.seg_sum(b.columns[1].data, layout, b.columns[1].valid_mask())
    d = b.to_numpy()
    np.testing.assert_allclose(np.asarray(sums)[0], np.sum(d["v"]), rtol=1e-12)


def test_string_group_boundaries(rng):
    schema = T.Schema([T.Field("s", T.STRING), T.Field("v", T.FLOAT64)])
    s = ["aa", "aa", "ab", "b", "b", "b", "", ""]
    v = np.arange(8.0)
    b = ColumnBatch.from_numpy({"s": s, "v": v}, schema)
    b = sort_batch(b, [SortSpec(0)])
    layout = seg.group_layout(b, [0])
    assert int(layout.num_groups) == 4  # "", aa, ab, b


def test_seg_minmax_nan_inf_semantics(rng):
    # Spark: NaN is the greatest value; nulls skipped; inf preserved
    k = np.array([0, 0, 1, 1, 2, 2, 3, 3], np.int64)
    v = np.array([1.0, np.nan, np.nan, np.nan, np.inf, 5.0, -np.inf, 2.0])
    validity = {"v": np.array([True, True, True, False, False, True,
                               True, True])}
    b = ColumnBatch.from_numpy({"k": k, "v": v}, SCHEMA, validity=validity)
    b = sort_batch(b, [SortSpec(0)])
    layout = seg.group_layout(b, [0])
    vcol = b.columns[1]
    mins, mok = seg.seg_min(vcol.data, layout, vcol.valid_mask())
    maxs, xok = seg.seg_max(vcol.data, layout, vcol.valid_mask())
    mins, maxs = np.asarray(mins), np.asarray(maxs)
    # group 0: {1.0, NaN} -> min 1.0, max NaN
    assert mins[0] == 1.0 and np.isnan(maxs[0])
    # group 1: {NaN, NULL} -> min NaN, max NaN
    assert np.isnan(mins[1]) and np.isnan(maxs[1])
    # group 2: {NULL, 5.0} -> 5.0 / 5.0
    assert mins[2] == 5.0 and maxs[2] == 5.0
    # group 3: {-inf, 2.0} -> -inf / 2.0
    assert mins[3] == -np.inf and maxs[3] == 2.0
    assert all(np.asarray(mok)[:4]) and all(np.asarray(xok)[:4])


# ---- seg_sum / seg_count / seg_any: the scan forms against numpy ----

# holds 18,000 keys and is past seg._PLAIN, so `_at_group_rows` takes its
# conditional: narrow for one group and 18 keys, every slot for the rest
CAP = 1 << 17


def _keys(kind, rng):
    if kind == "one":
        return np.zeros(CAP, np.int64)
    if kind == "each":
        return np.arange(CAP, dtype=np.int64)
    return np.sort(rng.integers(0, int(kind), CAP)).astype(np.int64)


def _values(dtype, rng):
    if dtype == "f64":
        return np.round(rng.uniform(0.01, 200.0, CAP), 2)
    if dtype == "i64":
        return rng.integers(-10**12, 10**12, CAP).astype(np.int64)
    return rng.random(CAP) < 0.5


@jax.jit
def _reduce_all(keys, values, valid, num_rows):
    b = ColumnBatch(T.Schema([T.Field("k", T.INT64)]),
                    [Column(T.INT64, keys, None)], num_rows, keys.shape[0])
    layout = seg.group_layout(b, [0])
    return (layout.num_groups, seg.seg_sum(values, layout, valid),
            seg.seg_count(valid, layout), seg.seg_any(valid, layout))


def _reference(keys, values, valid, n):
    """Per-group totals of the first n rows, in slot order of the sorted
    keys: (num_groups, sums as longdouble or int64, counts, anys)."""
    live = valid[:n]
    _, gid = np.unique(keys[:n], return_inverse=True)
    groups = int(gid.max()) + 1 if n else 0
    if values.dtype == np.bool_:
        terms, sums = (values[:n] & live).astype(np.int32), np.zeros(
            CAP, np.int32)
    elif values.dtype == np.int64:
        terms, sums = np.where(live, values[:n], 0), np.zeros(CAP, np.int64)
    else:
        terms, sums = (np.where(live, values[:n], 0).astype(np.longdouble),
                       np.zeros(CAP, np.longdouble))
    counts = np.zeros(CAP, np.int64)
    np.add.at(sums, gid, terms)
    np.add.at(counts, gid, live.astype(np.int64))
    return groups, sums, counts, counts > 0


@pytest.mark.parametrize("dtype", ["f64", "i64", "bool"])
@pytest.mark.parametrize("num_rows", [0, 1, CAP, CAP - 3])
@pytest.mark.parametrize("keys", ["one", "each", "18", "18000"])
@pytest.mark.parametrize("null_share", [0.0, 0.045, 1.0])
def test_seg_scan_forms_vs_numpy(rng, null_share, keys, num_rows, dtype):
    k, v = _keys(keys, rng), _values(dtype, rng)
    valid = rng.random(CAP) >= null_share
    groups, sums, counts, anys = _reduce_all(k, v, valid,
                                             np.int32(num_rows))
    g, rsums, rcounts, ranys = _reference(k, v, valid, num_rows)
    sums, counts, anys = map(np.asarray, (sums, counts, anys))
    assert int(groups) == g
    assert counts.dtype == np.int64 and anys.dtype == np.bool_
    np.testing.assert_array_equal(counts, rcounts)  # zeros past g too
    np.testing.assert_array_equal(anys, ranys)
    if dtype == "f64":
        np.testing.assert_allclose(sums, rsums.astype(np.float64),
                                   rtol=1e-12, atol=0)
    else:
        assert sums.dtype == rsums.dtype
        np.testing.assert_array_equal(sums, rsums)
    assert not sums[g:].any()  # exact zeros in every slot past num_groups


def test_seg_sum_inf_and_nan_stay_in_their_group():
    keys = np.repeat(np.arange(8, dtype=np.int64), CAP // 8)
    v = np.ones(CAP)
    at = CAP // 8 * 2  # group 2's first row
    v[at + 5], v[at + 700] = np.inf, np.nan
    v[CAP // 8 * 5 + 1] = np.inf
    _, sums, _, _ = _reduce_all(keys, v, np.ones(CAP, bool), np.int32(CAP))
    sums = np.asarray(sums)
    assert np.isnan(sums[2]) and sums[5] == np.inf
    np.testing.assert_array_equal(sums[[0, 1, 3, 4, 6, 7]], CAP // 8)
    assert not sums[8:].any()


def test_seg_sum_int64_wraps_like_a_scatter_add():
    keys = np.repeat(np.arange(4, dtype=np.int64), CAP // 4)
    v = np.ones(CAP, np.int64)
    v[CAP // 4: CAP // 4 + 3] = np.iinfo(np.int64).max  # group 1 wraps
    _, sums, _, _ = _reduce_all(keys, v, np.ones(CAP, bool), np.int32(CAP))
    want = np.zeros(CAP, np.int64)
    with np.errstate(over="ignore"):
        np.add.at(want, keys, v)
    assert want[1] < 0  # the reference wrapped
    np.testing.assert_array_equal(np.asarray(sums), want)


# ---- group_first_rows: a group's key off its run's first row ----

@jax.jit
def _first_rows(keys, valid, num_rows):
    b = ColumnBatch(T.Schema([T.Field("k", T.INT64)]),
                    [Column(T.INT64, keys, valid)], num_rows, keys.shape[0])
    layout = seg.group_layout(b, [0])
    col = seg.group_first_rows(b.columns[0], layout)
    return layout.num_groups, col.data, col.validity


@pytest.mark.parametrize("num_rows", [0, 1, CAP, CAP - 3])
@pytest.mark.parametrize("keys", ["one", "each", "18", "18000"])
def test_group_first_rows_vs_numpy(rng, keys, num_rows):
    """Both branches of the conditional (18,000 keys fit the first
    sixteenth of 2^17 slots, `each` does not), a null key as a group of its
    own, and nothing read past the groups."""
    k = _keys(keys, rng)
    valid = np.ones(CAP, np.bool_)
    if keys != "one":
        valid[k == k[0]] = False     # the first run is the null group
    groups, data, validity = _first_rows(k, valid, np.int32(num_rows))
    want, first = np.unique(k[:num_rows], return_index=True)
    assert int(groups) == len(want)
    np.testing.assert_array_equal(np.asarray(data)[:len(want)], want)
    np.testing.assert_array_equal(np.asarray(validity)[:len(want)],
                                  valid[first])


def test_group_first_rows_takes_strings_whole(rng):
    n = 64
    words = sorted(f"k{i:02d}" for i in rng.integers(0, 9, n))
    b = ColumnBatch.from_numpy({"s": words},
                               T.Schema([T.Field("s", T.STRING)]))
    layout = seg.group_layout(b, [0])
    col = seg.group_first_rows(b.columns[0], layout)
    got = ColumnBatch(b.schema, [col], layout.num_groups,
                      b.capacity).to_numpy()["s"]
    assert got == [w.encode() for w in sorted(set(words))]
