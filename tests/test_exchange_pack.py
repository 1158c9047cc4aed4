"""The exchange hands a reduce task batches, not slivers (PR 37): what
`run_mesh_shuffle_stage` keeps for a partition is packed, as it is kept, into
batches of up to `adaptive_batch_rows(schema)` live rows
(`stage_exchange.KeptPartitions`, `pack_slices`): whole slices, in order, by
contiguous copies at offsets the host already holds; a group of one, a list
column and a layout of its own are left as they are; the pinned bytes count
what is alive."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import ColumnBatch, bucket_capacity
from blaze_tpu.config import conf
from blaze_tpu.ops.common import adaptive_batch_rows
from blaze_tpu.parallel.stage_exchange import (
    KeptPartitions, pack_slices, packable, run_mesh_shuffle_stage,
    slice_layout,
)
from blaze_tpu.runtime import compile_service, jit_cache, placement
from blaze_tpu.runtime.memory import batch_nbytes
from test_slice_rows import _batch, _live, _writer_node

# (live rows, capacity) of a group's slices: capacities mixed, one slice
# full to its last slot, one nearly empty, the last one's padding passing
# the output's capacity (400 + 30 + 512 + 50 = 992 -> 1,024; the last
# slice's 1,024 slots are written at 942)
SLICES = [(400, 512), (30, 64), (512, 512), (50, 1024)]

# the columns of one slice, by `test_slice_rows._column`'s kinds
LAYOUTS = {
    "q3_nullable": ["int64_nullable", "double_nullable", "int64_nullable"],
    "no_validity": ["int64", "double", "int32"],
    "mixed_validity": ["int64", "double_nullable", "bool_nullable"],
    "decimal_int64_plane": ["decimal_7_2", "decimal_7_2_nullable"],
    "string": ["string", "int64"],
    "struct_and_wide_decimal": ["struct", "wide_decimal", "bool"],
}


def _group(kinds, sizes=SLICES):
    """`sizes` slices of one layout, each with its rows as a host int."""
    return [(_batch(kinds, n=n, cap=cap), n) for n, cap in sizes]


def _rows(slices):
    """The live rows of `slices` in order, column by column."""
    out = {}
    for b, n in slices:
        live = _live(b)
        assert all(len(v) == n for v in live.values())
        for name, vals in live.items():
            out.setdefault(name, []).extend(vals)
    return out


def _strings_batch(width_of, n, cap):
    """A string column whose values are `width_of` bytes wide."""
    schema = T.Schema([T.Field("s", T.STRING), T.Field("k", T.INT64)])
    return ColumnBatch.from_numpy(
        {"s": [b"%0*d" % (width_of, i) for i in range(n)],
         "k": np.arange(n, dtype=np.int64)}, schema, capacity=cap)


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_the_packed_batch_is_its_slices_live_rows_in_order(name):
    group = _group(LAYOUTS[name])
    schema = group[0][0].schema
    assert all(packable(b) for b, _ in group)
    assert len({slice_layout(b) for b, _ in group}) == 1
    got = pack_slices(group, schema)
    total = sum(n for n, _ in SLICES)
    assert got.capacity == bucket_capacity(total) == 1024
    assert int(got.num_rows) == total
    assert slice_layout(got) == slice_layout(group[0][0])
    assert _live(got) == _rows(group)


@pytest.mark.parametrize("width", [3, 20])
def test_strings_of_each_width_pack_at_their_width(width):
    group = [(_strings_batch(width, n, cap), n) for n, cap in SLICES]
    got = pack_slices(group, group[0][0].schema)
    assert got.columns[0].data.width == group[0][0].columns[0].data.width
    assert _live(got) == _rows(group)


def test_strings_of_two_widths_do_not_share_a_group():
    kept = KeptPartitions(_strings_batch(3, 1, 16).schema, 1, 1, 1)
    narrow = [_strings_batch(3, n, cap) for n, cap in SLICES[:2]]
    wide = [_strings_batch(20, n, cap) for n, cap in SLICES[:2]]
    assert slice_layout(narrow[0]) != slice_layout(wide[0])
    for b in (narrow[0], wide[0], wide[1], narrow[1]):
        kept.keep(0, b, int(b.num_rows))
    kept.seal_all()
    (first, n0), (packed, n1), (last, n2) = kept.parts[0]
    assert first is narrow[0] and last is narrow[1]
    assert (n0, n1, n2) == (400, 430, 30)
    assert _live(packed) == _rows([(wide[0], 400), (wide[1], 30)])
    assert (kept.cut, kept.packed) == (4, 2)


def test_a_single_slice_is_returned_as_the_same_object():
    kept = KeptPartitions(_batch(["int64"]).schema, 2, 1, 2)
    a, b = _batch(["int64"], n=9, cap=16), _batch(["int64"], n=5, cap=16)
    kept.keep(0, a, 9)
    kept.keep(1, b, 5)
    kept.seal_all()
    assert kept.parts[0][0][0] is a and kept.parts[1][0][0] is b
    assert [len(p) for p in kept.parts] == [1, 1]
    assert (kept.cut, kept.packed) == (2, 0)


@pytest.mark.parametrize("kinds", [["int64", "list"], ["list"],
                                   ["dict_string", "int64"]])
def test_a_list_column_and_a_dictionary_of_its_own_are_left_unpacked(kinds):
    group = _group(kinds)
    assert not any(packable(b) for b, _ in group)
    kept = KeptPartitions(group[0][0].schema, 1, 1, 1)
    c0 = compile_service.TELEMETRY.snapshot().get("compile_count", 0)
    for b, n in group:
        kept.keep(0, b, n)
    kept.seal_all()
    assert [b for b, _ in kept.parts[0]] == [b for b, _ in group]
    assert (kept.cut, kept.packed) == (len(group), 0)
    assert compile_service.TELEMETRY.snapshot().get(
        "compile_count", 0) == c0
    assert kept.pinned == [sum(batch_nbytes(b) for b, _ in group)]


def test_an_unpackable_slice_seals_the_group_before_it():
    """Order inside a partition is kept: rows that came before a batch
    with a dictionary of its own are handed before it."""
    a, b = (_batch(["string"], n=7, cap=16) for _ in range(2))
    own = _batch(["dict_string"], n=4, cap=16)
    c = _batch(["string"], n=2, cap=16)
    assert a.schema == own.schema
    kept = KeptPartitions(a.schema, 1, 1, 1)
    for x in (a, b, own, c):
        kept.keep(0, x, int(x.num_rows))
    kept.seal_all()
    (ab, n_ab), (got_own, _), (got_c, _) = kept.parts[0]
    assert n_ab == 14 and got_own is own and got_c is c
    assert _live(ab) == _rows([(a, 7), (b, 7)])


@pytest.fixture
def small_batches(monkeypatch):
    """A scan's batch is 4,096 rows: `adaptive_batch_rows` says so."""
    monkeypatch.setattr(conf, "max_batch_rows", 4096)
    monkeypatch.setattr(conf, "batch_size", 4096)


def test_a_group_never_passes_the_size_a_scan_hands_on(small_batches):
    kinds = LAYOUTS["q3_nullable"]
    schema = _batch(kinds).schema
    target = adaptive_batch_rows(schema)
    assert target == 4096
    rng = np.random.default_rng(37)
    sizes = [int(n) for n in rng.integers(1, 1800, 60)] + [4096, 5000, 90]
    kept = KeptPartitions(schema, 2, 1, 2)
    cut = [[], []]
    for i, n in enumerate(sizes):
        b = _batch(kinds, n=n, cap=bucket_capacity(n))
        cut[i % 2].append((b, n))
        kept.keep(i % 2, b, n)
    kept.seal_all()
    assert kept.cut == len(sizes)
    for p in range(2):
        groups, todo = [], list(cut[p])
        for b, n in kept.parts[p]:
            # greedy, whole slices: the group is the longest run of the
            # slices next in line that stays within the target
            took = 0
            members = []
            while todo and (not members or took + todo[0][1] <= target):
                members.append(todo.pop(0))
                took += members[-1][1]
            assert n == took == int(b.num_rows)
            assert n <= target or len(members) == 1   # a slice is never cut
            assert b.capacity == bucket_capacity(n)
            if len(members) == 1:
                assert b is members[0][0]
            groups.append(members)
        assert not todo
        assert _rows(kept.parts[p]) == _rows(cut[p])
        assert sum(len(g) for g in groups if len(g) > 1) > 0
    assert kept.packed == sum(
        1 for p in range(2) for b, _ in cut[p]
        if not any(b is k for k, _ in kept.parts[p]))


def test_pinned_counts_what_is_alive_and_the_high_water_both(small_batches):
    kinds = LAYOUTS["q3_nullable"]
    schema = _batch(kinds).schema
    kept = KeptPartitions(schema, 4, 2, 2)   # chip 0: p0, p1; chip 1: p2, p3
    one = batch_nbytes(_batch(kinds, n=1000, cap=1024))
    for i in range(9):      # four slices fill a group: 4,000 of 4,096
        kept.keep(0, _batch(kinds, n=1000, cap=1024), 1000)
        # before a group is sealed its slices are pinned as they were cut
        assert kept.pinned[0] == sum(
            batch_nbytes(b) for b, _ in kept.parts[0]) + (i % 4 + 1) * one
    kept.keep(3, _batch(kinds, n=10, cap=1024), 10)
    kept.seal_all()
    assert [n for _, n in kept.parts[0]] == [4000, 4000, 1000]
    assert kept.pinned == [
        sum(batch_nbytes(b) for p in (0, 1) for b, _ in kept.parts[p]),
        sum(batch_nbytes(b) for p in (2, 3) for b, _ in kept.parts[p])]
    packed = batch_nbytes(kept.parts[0][0][0])
    assert packed == 4 * one    # 4,096 slots
    # sealing the second group held the first packed batch, the group's
    # four slices, the slice that would not fit and the new packed batch
    assert kept.high_water == packed + 5 * one + packed > max(kept.pinned)


def test_a_packed_group_lies_on_its_slices_chip():
    dev = jax.devices()[2]
    kinds = LAYOUTS["q3_nullable"]
    group = [(jax.device_put(b, dev), n) for b, n in _group(kinds)]
    jit_cache.clear()
    kept = KeptPartitions(group[0][0].schema, 4, 4, 1)
    for b, n in group:
        kept.keep(2, b, n)
    kept.seal_all()
    ((packed, n),) = kept.parts[2]
    assert placement.device_of(packed.columns) == dev
    assert _live(packed) == _rows(group)
    assert kept.pinned == [0, 0, batch_nbytes(packed), 0]
    with jit_cache._lock:
        (key,) = list(jit_cache._cache)
    # the chip's own executable
    assert key[0] == "exchange_pack" and key[-1] == ("@dev", dev.id)


def test_the_pack_program_holds_no_gather_and_no_scatter(monkeypatch):
    made = {}

    def capture(key, make):
        made[key[0]] = make()
        return lambda starts, *bs: bs[0]

    group = _group(LAYOUTS["q3_nullable"]) + _group(LAYOUTS["q3_nullable"])
    monkeypatch.setattr(jit_cache, "get_or_compile", capture)
    pack_slices(group, group[0][0].schema)
    text = jax.jit(made["exchange_pack"]).lower(
        jnp.zeros((len(group) + 1,), jnp.int32),
        *[b for b, _ in group]).as_text()
    assert "gather" not in text and "scatter" not in text
    assert "dynamic_update_slice" in text


def _stage(sizes, stage_id, seed):
    """One exchange of batches of `sizes` rows to four partitions on one
    chip: its stats, what the provider hands each partition, the batches."""
    from blaze_tpu.runtime import resources
    from test_slice_rows import SCHEMA

    rng = np.random.default_rng(seed)
    batches, base = [], 0
    for n in sizes:
        batches.append(ColumnBatch.from_numpy(
            {"k": rng.integers(0, 5000, n).astype(np.int64),
             "v": np.arange(base, base + n) * 0.5,
             "s": [b"%06d" % r for r in range(base, base + n)]}, SCHEMA,
            validity={"k": rng.random(n) > 0.1}))
        base += n
    node, rid = _writer_node(batches, 4)
    stats = {}
    assert run_mesh_shuffle_stage(node, stage_id=stage_id, ntasks=1,
                                  stats=stats)
    reader = resources.get(f"shuffle:{stage_id}")
    got = [list(reader(p)) for p in range(4)]
    resources.pop(f"shuffle:{stage_id}")
    resources.pop(rid)
    return stats, got, batches


@pytest.fixture
def one_chip(monkeypatch):
    real = jax.devices
    monkeypatch.setattr(jax, "devices", lambda *a, **k: real(*a, **k)[:1])


def test_a_stage_packs_pins_and_counts(one_chip, small_batches):
    stats, got, batches = _stage([2400, 2500, 2300, 2450, 2350], 941, 1)
    assert stats["slices_cut"] == 20
    # ~600 rows a slice: five fit in 4,096
    assert stats["slices"] == 4 and stats["slices_packed"] == 20
    assert stats["slice_rows"] == 12_000 == sum(
        int(b.num_rows) for part in got for b in part)
    assert all(b.capacity == 4096 for part in got for b in part)
    held = sum(batch_nbytes(b) for part in got for b in part)
    cut = 20 * batch_nbytes(batches[0]) * 1024 // batches[0].capacity
    # the high-water: every slice and, in passing, one packed batch
    assert held < cut < stats["pinned_bytes"] <= cut + held // 4
    # every row went where its key sends it, in the order it came
    v = [np.concatenate([np.asarray(b.to_numpy()["v"]) for b in part])
         for part in got]
    assert sorted(np.concatenate(v).tolist()) == [
        r * 0.5 for r in range(12_000)]
    assert all((np.diff(x) > 0).all() for x in v)


def test_a_second_stage_with_other_rows_compiles_nothing(
        one_chip, small_batches):
    _stage([3000, 3100, 2900], 942, 2)
    before = compile_service.TELEMETRY.snapshot()["compile_count"]
    stats, got, _ = _stage([2950, 3075, 3020], 943, 3)   # same capacities
    assert stats["slices_packed"] == 12 and stats["slices"] == 4
    assert compile_service.TELEMETRY.snapshot()["compile_count"] == before
