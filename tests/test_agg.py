"""AggExec vs pandas oracle — partial/merge/final pipelines, nulls, strings.

Mirrors the reference's agg_exec.rs:528 e2e tests over MemoryExec plus the
partial/final pairing contract (NativeAggBase, SURVEY.md §2.2)."""

import numpy as np
import pandas as pd
import pytest

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import ColumnBatch
from blaze_tpu.exprs import ir
from blaze_tpu.ops.agg import AggCall, AggExec, AggMode
from blaze_tpu.ops.basic import MemorySourceExec
from blaze_tpu.runtime.executor import collect

SCHEMA = T.Schema([
    T.Field("k", T.INT64),
    T.Field("v", T.FLOAT64),
    T.Field("n", T.INT32),
    T.Field("s", T.STRING),
])


def _batches(rng, sizes, null_frac=0.0, nkeys=9):
    out = []
    for i, n in enumerate(sizes):
        data = {
            "k": rng.integers(0, nkeys, n).astype(np.int64),
            "v": rng.random(n) * 10 - 5,
            "n": rng.integers(-100, 100, n).astype(np.int32),
            "s": [f"s{j}" for j in rng.integers(0, 30, n)],
        }
        validity = None
        if null_frac:
            validity = {c: rng.random(n) > null_frac for c in ("v", "n", "s")}
        out.append(ColumnBatch.from_numpy(data, SCHEMA, validity=validity))
    return out


def _to_df(batches):
    frames = []
    for b in batches:
        d = b.to_numpy()
        frames.append(pd.DataFrame({
            "k": np.asarray(d["k"]),
            "v": [x for x in d["v"]],
            "n": [x for x in d["n"]],
            "s": [x.decode() if x is not None else None for x in d["s"]],
        }))
    return pd.concat(frames, ignore_index=True)


def _agg_plan(src, mode_pairs, aggs):
    """Build partial -> (partial_merge ->) final chain."""
    node = src
    for mode in mode_pairs:
        node = AggExec(node, [ir.col("k")] if mode_groups else [], ["k"],
                       aggs, mode)
    return node


CALLS = [
    AggCall("sum", (ir.col("v"),), T.FLOAT64, "sum_v"),
    AggCall("count", (ir.col("v"),), T.INT64, "cnt_v"),
    AggCall("avg", (ir.col("v"),), T.FLOAT64, "avg_v"),
    AggCall("min", (ir.col("n"),), T.INT32, "min_n"),
    AggCall("max", (ir.col("n"),), T.INT32, "max_n"),
    AggCall("min", (ir.col("s"),), T.STRING, "min_s"),
    AggCall("max", (ir.col("s"),), T.STRING, "max_s"),
    AggCall("first", (ir.col("v"),), T.FLOAT64, "first_v"),
    AggCall("first_ignores_null", (ir.col("v"),), T.FLOAT64, "firstnn_v"),
]

mode_groups = True


@pytest.mark.parametrize("null_frac", [0.0, 0.35])
@pytest.mark.parametrize("chain", [
    [AggMode.PARTIAL, AggMode.FINAL],
    [AggMode.PARTIAL, AggMode.PARTIAL_MERGE, AggMode.FINAL],
])
def test_grouped_agg_vs_pandas(rng, null_frac, chain):
    batches = _batches(rng, [200, 57, 130], null_frac=null_frac)
    node = MemorySourceExec(batches, SCHEMA)
    for mode in chain:
        node = AggExec(node, [ir.col("k")], ["k"], CALLS, mode)
    out = collect(node)
    d = out.to_numpy()
    got = pd.DataFrame({
        "k": np.asarray(d["k"]),
        "sum_v": [x for x in d["sum_v"]],
        "cnt_v": np.asarray(d["cnt_v"]),
        "avg_v": [x for x in d["avg_v"]],
        "min_n": [x for x in d["min_n"]],
        "max_n": [x for x in d["max_n"]],
        "min_s": [x.decode() if x is not None else None for x in d["min_s"]],
        "max_s": [x.decode() if x is not None else None for x in d["max_s"]],
    }).sort_values("k").reset_index(drop=True)

    df = _to_df(batches)
    want = df.groupby("k").agg(
        sum_v=("v", lambda x: x.dropna().sum() if x.notna().any() else None),
        cnt_v=("v", lambda x: x.notna().sum()),
        avg_v=("v", lambda x: x.dropna().mean() if x.notna().any() else None),
        min_n=("n", lambda x: x.dropna().min() if x.notna().any() else None),
        max_n=("n", lambda x: x.dropna().max() if x.notna().any() else None),
        min_s=("s", lambda x: x.dropna().min() if x.notna().any() else None),
        max_s=("s", lambda x: x.dropna().max() if x.notna().any() else None),
    ).reset_index().sort_values("k").reset_index(drop=True)

    assert got["k"].tolist() == want["k"].tolist()
    for c in ("sum_v", "avg_v"):
        for g, w in zip(got[c], want[c]):
            if w is None or (isinstance(w, float) and np.isnan(w)):
                assert g is None
            else:
                np.testing.assert_allclose(float(g), float(w), rtol=1e-9)
    assert got["cnt_v"].tolist() == want["cnt_v"].tolist()
    for c in ("min_n", "max_n", "min_s", "max_s"):
        got_l = [None if x is None else x for x in got[c]]
        want_l = [None if (w is None or (isinstance(w, float) and np.isnan(w)))
                  else w for w in want[c]]
        assert got_l == want_l, c


def test_first_semantics(rng):
    # first = first value in stream order (validity preserved)
    data = {"k": np.array([1, 1, 2, 2], np.int64),
            "v": np.array([9.0, 1.0, 3.0, 4.0]),
            "n": np.zeros(4, np.int32), "s": ["a", "b", "c", "d"]}
    validity = {"v": np.array([False, True, True, True])}
    b = ColumnBatch.from_numpy(data, SCHEMA, validity=validity)
    node = MemorySourceExec([b], SCHEMA)
    calls = [AggCall("first", (ir.col("v"),), T.FLOAT64, "f"),
             AggCall("first_ignores_null", (ir.col("v"),), T.FLOAT64, "fnn")]
    for mode in (AggMode.PARTIAL, AggMode.FINAL):
        node = AggExec(node, [ir.col("k")], ["k"], calls, mode)
    d = collect(node).to_numpy()
    by_k = {int(k): (f, fnn) for k, f, fnn in zip(d["k"], d["f"], d["fnn"])}
    assert by_k[1][0] is None          # first v of k=1 is null
    assert float(by_k[1][1]) == 1.0    # first non-null is 1.0
    assert float(by_k[2][0]) == 3.0
    assert float(by_k[2][1]) == 3.0


def test_global_agg(rng):
    batches = _batches(rng, [100, 50])
    node = MemorySourceExec(batches, SCHEMA)
    calls = [AggCall("sum", (ir.col("v"),), T.FLOAT64, "s"),
             AggCall("count", (ir.lit(1),), T.INT64, "c")]
    for mode in (AggMode.PARTIAL, AggMode.FINAL):
        node = AggExec(node, [], [], calls, mode)
    out = collect(node)
    assert int(out.num_rows) == 1
    d = out.to_numpy()
    df = _to_df(batches)
    np.testing.assert_allclose(float(d["s"][0]), df["v"].sum(), rtol=1e-9)
    assert int(np.asarray(d["c"])[0]) == len(df)


def test_global_agg_empty_input():
    node = MemorySourceExec([], SCHEMA)
    calls = [AggCall("sum", (ir.col("v"),), T.FLOAT64, "s"),
             AggCall("count", (ir.lit(1),), T.INT64, "c")]
    for mode in (AggMode.PARTIAL, AggMode.FINAL):
        node = AggExec(node, [], [], calls, mode)
    out = collect(node)
    assert int(out.num_rows) == 1
    d = out.to_numpy()
    assert d["s"][0] is None
    assert int(np.asarray(d["c"])[0]) == 0


def test_grouped_agg_empty_input():
    node = MemorySourceExec([], SCHEMA)
    calls = [AggCall("sum", (ir.col("v"),), T.FLOAT64, "s")]
    for mode in (AggMode.PARTIAL, AggMode.FINAL):
        node = AggExec(node, [ir.col("k")], ["k"], calls, mode)
    out = collect(node)
    assert int(out.num_rows) == 0


def test_streaming_collapse(rng):
    # small collapse threshold forces the hierarchical fold path; pin the
    # streaming executor (the stage compiler would take this whole plan in
    # one dispatch and never collapse)
    from blaze_tpu.config import conf

    batches = _batches(rng, [64] * 10)
    node = MemorySourceExec(batches, SCHEMA)
    calls = [AggCall("sum", (ir.col("v"),), T.FLOAT64, "s"),
             AggCall("count", (ir.col("v"),), T.INT64, "c")]
    p = AggExec(node, [ir.col("k")], ["k"], calls, AggMode.PARTIAL,
                collapse_threshold=100)
    f = AggExec(p, [ir.col("k")], ["k"], calls, AggMode.FINAL)
    conf.enable_stage_compiler = False
    try:
        d = collect(f).to_numpy()
    finally:
        conf.enable_stage_compiler = True
    df = _to_df(batches)
    want = df.groupby("k")["v"].sum()
    got = {int(k): float(s) for k, s in zip(d["k"], d["s"])}
    for k, w in want.items():
        np.testing.assert_allclose(got[int(k)], w, rtol=1e-9)
    assert p.metrics["collapses"] >= 1


def test_final_agg_single_external_state_batch_merges():
    """A single shuffle-read state batch can hold several partial states
    for the same group (mesh exchange delivers all map outputs in one
    batch) — FINAL mode must still merge them, not pass rows through."""
    import jax.numpy as jnp

    from blaze_tpu.columnar import types as T
    from blaze_tpu.columnar.batch import ColumnBatch
    from blaze_tpu.exprs import ir
    from blaze_tpu.ops.agg import AGG_BUF_PREFIX, AggCall, AggExec, AggMode
    from blaze_tpu.ops.base import ExecContext
    from blaze_tpu.ops.basic import MemorySourceExec

    S = T.Schema([T.Field("item", T.INT64),
                  T.Field(f"{AGG_BUF_PREFIX}.0.sum", T.FLOAT64),
                  T.Field(f"{AGG_BUF_PREFIX}.0.nonempty", T.BOOLEAN)])
    items = np.array([2, 2, 4, 5, 2, 4, 5, 5, 2, 4, 4, 5], np.int64)
    sums = np.arange(12, dtype=np.float64)
    b = ColumnBatch.from_numpy(
        {"item": items, f"{AGG_BUF_PREFIX}.0.sum": sums,
         f"{AGG_BUF_PREFIX}.0.nonempty": np.ones(12, bool)}, S,
        capacity=4096)
    src = MemorySourceExec([b], schema=S)
    agg = AggExec(src, [ir.col("item")], ["item"],
                  [AggCall("sum", (ir.col("x"),), T.FLOAT64, "s")],
                  AggMode.FINAL)
    (out,) = list(agg.execute(ExecContext(partition=0, num_partitions=1)))
    n = int(out.num_rows)
    d = out.to_numpy()
    got = dict(zip(np.asarray(d["item"])[:n].tolist(),
                   np.asarray(d["s"])[:n].tolist()))
    want = {2: float(sums[items == 2].sum()),
            4: float(sums[items == 4].sum()),
            5: float(sums[items == 5].sum())}
    assert n == 3
    assert got == want


def test_q06core_partial_collapse_reads_scans_not_scatters(rng, monkeypatch):
    """q06-core's partial aggregate (sum f64, count, avg) over a 2^14-slot
    batch: no scatter under collapse.accumulate_raw in the lowered program,
    and TELEMETRY's seg_* counters move by the program's tally at every
    dispatch (the second dispatch is a cache hit and traces nothing)."""
    import re

    import jax

    from blaze_tpu.config import conf
    from blaze_tpu.ops import agg as agg_mod
    from blaze_tpu.runtime import compile_service, jit_cache

    schema = T.Schema([T.Field("ss_item_sk", T.INT64),
                       T.Field("ss_sales_price", T.FLOAT64),
                       T.Field("ss_ext_sales_price", T.FLOAT64)])
    n = 1 << 14
    data = {"ss_item_sk": rng.integers(1, 300, n).astype(np.int64),
            "ss_sales_price": np.round(rng.uniform(0, 200, n), 2),
            "ss_ext_sales_price": np.round(rng.uniform(0, 2000, n), 2)}
    validity = {c: rng.random(n) > 0.045
                for c in ("ss_sales_price", "ss_ext_sales_price")}
    calls = [AggCall("sum", (ir.col("ss_ext_sales_price"),), T.FLOAT64,
                     "total"),
             AggCall("count", (ir.col("ss_ext_sales_price"),), T.INT64,
                     "cnt"),
             AggCall("avg", (ir.col("ss_sales_price"),), T.FLOAT64,
                     "avg_price")]

    seen = {}
    real = jit_cache.get_or_compile

    def spy(key, make_fn, **kw):
        fn = real(key, make_fn, **kw)
        if key[0] != "agg_collapse":
            return fn

        def call(batch):
            seen.setdefault("programs", []).append((make_fn, batch))
            return fn(batch)
        return call

    monkeypatch.setattr(agg_mod.jit_cache, "get_or_compile", spy)

    def run_once():
        b = ColumnBatch.from_numpy(data, schema, validity=validity)
        assert b.capacity == n
        p = AggExec(MemorySourceExec([b], schema), [ir.col("ss_item_sk")],
                    ["item"], calls, AggMode.PARTIAL)
        before = compile_service.TELEMETRY.snapshot()
        out = collect(p)
        after = compile_service.TELEMETRY.snapshot()
        return out, {k: after[k] - before[k] for k in
                     ("seg_scan_reductions", "seg_scatter_reductions",
                      "compile_count")}

    conf.enable_stage_compiler = False
    try:
        out, first = run_once()
        _, second = run_once()
    finally:
        conf.enable_stage_compiler = True

    # sum + avg's sum, and two counts: sum's non-empty flag and count(x)
    # are over one column and are counted once
    want = {"seg_scan_reductions": 4, "seg_scatter_reductions": 0}
    assert {k: first[k] for k in want} == want
    assert second == {**want, "compile_count": 0}

    (make_fn, batch), = seen["programs"][:1]
    text = jax.jit(make_fn()).lower(batch).as_text(debug_info=True)
    ops = re.findall(r'loc\("(jit\([^"]*)"', text)
    acc = [op for op in ops if "/collapse.accumulate_raw/" in op]
    assert acc and not [op for op in acc if "scatter" in op]
    # the text does name a scatter where one is: group_layout's nonzero_i32
    assert [op for op in ops
            if "/collapse.group_layout/" in op and "scatter" in op]

    d = out.to_numpy()
    ext = np.where(validity["ss_ext_sales_price"],
                   data["ss_ext_sales_price"], 0.0)
    want_total = pd.Series(ext).groupby(data["ss_item_sk"]).sum()
    got = dict(zip(np.asarray(d["item"]).tolist(), list(d.values())[1]))
    for k, w in want_total.items():
        np.testing.assert_allclose(got[int(k)], w, rtol=1e-12)
