"""Segment (group-run) utilities over key-sorted batches.

The TPU-native replacement for the reference's open-addressing agg hash
tables (agg_tables.rs): rows are first sorted by their grouping key, after
which every grouped computation is a *segmented scan* — boundary detection by
neighbor equality, group ids by cumsum, sums and counts by a scan that restarts
at each run's first row + one gather at its last (`seg_sum`). No data-dependent
shapes; the scatters that remain are `nonzero_i32` in `group_layout` and the
min / max / first reductions (`jax.ops.segment_min` / `segment_max`).

Used by agg (group-by), window (partition boundaries) and SMJ (run-length
matching).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from blaze_tpu.columnar.batch import Column, ColumnBatch, nonzero_i32

Array = jax.Array


def _col_neighbor_eq(col: Column) -> Array:
    """eq[i] = row i equals row i-1 in this column (eq[0] = False).

    Null == null here (Spark grouping/ordering semantics: null is its own
    group; NaN normalization is the sort encoder's job and cumsum-grouping
    only ever runs on sort output).
    """
    cap = col.capacity
    valid = col.valid_mask()
    vprev = jnp.roll(valid, 1)
    both_valid = valid & vprev
    both_null = (~valid) & (~vprev)
    if col.is_string:
        b, l = col.data.bytes, col.data.lengths
        lprev = jnp.roll(l, 1)
        bprev = jnp.roll(b, 1, axis=0)
        w = b.shape[1]
        pos = jnp.arange(w, dtype=jnp.int32)[None, :]
        in_len = pos < l[:, None]
        data_eq = (l == lprev) & jnp.all(
            jnp.where(in_len, b == bprev, True), axis=1)
    elif col.is_struct:
        # struct-backed storage (incl. wide decimals' limb planes):
        # rows equal when every child plane is equal
        data_eq = jnp.ones((cap,), jnp.bool_)
        for ch in col.data.children:
            data_eq = data_eq & (ch.data == jnp.roll(ch.data, 1))
    else:
        data_eq = col.data == jnp.roll(col.data, 1)
        if jnp.issubdtype(col.data.dtype, jnp.floating):
            # NaN == NaN for grouping (Spark), -0.0 == 0.0
            d, p = col.data, jnp.roll(col.data, 1)
            data_eq = data_eq | (jnp.isnan(d) & jnp.isnan(p))
    eq = jnp.where(both_valid, data_eq, both_null)
    return eq.at[0].set(False) if cap > 0 else eq


def group_starts(batch: ColumnBatch, key_indices: Sequence[int]) -> Array:
    """True at the first live row of each key run; False at padding rows.

    Requires the batch to be sorted by the keys (padding compacted last).
    """
    mask = batch.row_mask()
    if not key_indices:
        # single global group: one start at row 0 if any rows
        return (jnp.arange(batch.capacity, dtype=jnp.int32) == 0) & mask
    eq = None
    for i in key_indices:
        e = _col_neighbor_eq(batch.columns[i])
        eq = e if eq is None else (eq & e)
    return (~eq) & mask


@dataclasses.dataclass
class GroupLayout:
    """Everything downstream aggs need about the runs of a sorted batch."""
    starts: Array      # bool (cap,) — first row of each group
    gid: Array         # int32 (cap,) — group index per row (garbage at padding)
    num_groups: Array  # int32 scalar
    start_idx: Array   # int32 (cap,) — row index of group g's first row
    end_idx: Array     # int32 (cap,) — row index of group g's last row
    row_mask: Array    # bool (cap,) — live rows
    group_mask: Array  # bool (cap,) — slots < num_groups


def group_layout(batch: ColumnBatch, key_indices: Sequence[int]) -> GroupLayout:
    cap = batch.capacity
    mask = batch.row_mask()
    starts = group_starts(batch, key_indices)
    gid = jnp.cumsum(starts.astype(jnp.int32)) - 1
    num_groups = jnp.sum(starts, dtype=jnp.int32)
    start_idx = nonzero_i32(starts, cap)
    # end of group g = start of g+1 minus 1; last group ends at num_rows-1
    nxt = jnp.concatenate([start_idx[1:], jnp.zeros((1,), jnp.int32)])
    gslot = jnp.arange(cap, dtype=jnp.int32)
    end_idx = jnp.where(gslot == num_groups - 1, batch.num_rows - 1, nxt - 1)
    group_mask = gslot < num_groups
    end_idx = jnp.where(group_mask, end_idx, 0)
    return GroupLayout(starts, gid, num_groups, start_idx, end_idx, mask,
                       group_mask)


def segmented_scan(values: Array, starts: Array,
                   combine: Callable[[Array, Array], Array]) -> Array:
    """Inclusive scan of `combine` restarting at each segment start."""
    def op(a, b):
        fa, va = a
        fb, vb = b
        return (fa | fb, jnp.where(fb, vb, combine(va, vb)))

    _, out = lax.associative_scan(op, (starts, values))
    return out


def element_rows(offsets: Array, cap: int, ecap: int):
    """Map flat element slots back to their owning rows.

    `offsets` is an int32 (>= cap+1,) monotone element-offset array. Returns
    (slot, row, within, live): for element slot e, the owning row index,
    the position within that row's range, and whether the slot is below the
    total element count. Shared by list gather/concat, collect-state merge
    and map lookup (one copy of a subtle clamped-searchsorted construction).
    """
    slot = jnp.arange(ecap, dtype=jnp.int32)
    row = jnp.clip(
        jnp.searchsorted(offsets[1:cap + 1], slot,
                         side="right").astype(jnp.int32), 0, cap - 1)
    within = slot - offsets[row]
    live = slot < offsets[cap]
    return slot, row, within, live


# ---- per-group reductions (results compacted to slots [0, num_groups)) ----
#
# Sums and counts take a layout from `group_layout` over a key-sorted batch,
# so a group is a run of rows: its total is a running sum that restarts at
# `layout.starts` (`_restarting_sum`), read at the run's last row and brought
# to slot g by a gather at `layout.end_idx` (`_at_group_rows`). One form for
# every dtype, a mask counted as int32 (a batch has at most 2^21 slots), a
# group's own terms only: never a difference of global prefixes, which
# subtracts prefixes of ~10^10 to get sums of ~10^6 and lets one inf or NaN
# reach every later group. Integers wrap as a scatter-add's do.
#
# Chip readings (v5e, 2^21 slots, 18,000 groups; PERF.md section 6, PR 31):
# `jax.ops.segment_sum` 144-154 ms whatever the dtype; the blocked scan
# 1.3 ms and ~1 s of compile; `associative_scan` on (flag, f64) 61 ms and
# 256 s of compile; int32 `cumsum` + `cummax` as fast as the blocked scan
# and 35-44 s of compile; a gather of every slot 16 ms (int32) to 34 ms
# (f64), of the first sixteenth 3-4 ms. min, max and first stay
# scatter-based (`jax.ops.segment_min` / `segment_max`): no cell runs them.

_BLOCK = 256   # rows one lane of `_restarting_sum` walks (at 128: 22 ms, not 1.3)
_FEW = 16      # `_at_group_rows`: groups in the first 1/_FEW of the slots
_PLAIN = 1 << 16   # `_at_group_rows`: no conditional up to this many slots

_FORMS = threading.local()


@contextlib.contextmanager
def count_forms():
    """Tally the per-group reductions traced inside the scope by the form
    they were built in, and the sums among them by what `seg_sum` was handed
    to add: yields {"scan": n, "scatter": n, "sums": n, "int_sums": n}
    (`sums`: every seg_sum over numbers, flags counted aside; `int_sums`:
    those over an integer array). A trace-time count of this thread;
    `ops/agg` keeps it per program and adds it to
    `compile_service.TELEMETRY` at every dispatch."""
    outer = getattr(_FORMS, "tally", None)
    tally = _FORMS.tally = {"scan": 0, "scatter": 0, "sums": 0,
                            "int_sums": 0}
    try:
        yield tally
    finally:
        _FORMS.tally = outer


def _note_form(form: str) -> None:
    tally = getattr(_FORMS, "tally", None)
    if tally is not None:
        tally[form] += 1


def _seg_ids(layout: GroupLayout, extra_mask: Array = None) -> Array:
    """Per-row segment id for scatter ops: gid for contributing rows, an
    out-of-range id (dropped by num_segments) for padding/masked rows.
    Every scatter-based reduction takes its ids here once, so this is
    where `count_forms` hears of one."""
    _note_form("scatter")
    mask = layout.row_mask if extra_mask is None else (
        layout.row_mask & extra_mask)
    cap = layout.gid.shape[0]
    return jnp.where(mask, layout.gid, jnp.int32(cap))


def _restarting_sum(v: Array, starts: Array) -> Array:
    """Inclusive running sum of `v` that restarts where `starts` is True.

    Two-level and blocked: the rows are cut into lanes of `_BLOCK`
    consecutive rows and one `lax.scan` of `_BLOCK` steps walks all lanes at
    once, carrying each lane's running sum and whether it has met a start
    (elementwise work only, so f64 and int64 compile in seconds where an
    `associative_scan` of 2^21 emulated 64-bit rows does not); the same scan
    over the lane totals gives what enters each lane; rows before a lane's
    first start take that in. Only a group's own terms are ever added."""
    n = v.shape[0]
    zero = jnp.zeros((), v.dtype)

    def step(carry, x):
        acc, seen = carry
        vj, fj = x
        acc = jnp.where(fj, vj, acc + vj)
        seen = seen | fj
        return (acc, seen), (acc, seen)

    if n <= _BLOCK:
        _, (out, _) = lax.scan(step, (zero, jnp.zeros((), jnp.bool_)),
                               (v, starts))
        return out
    pad = -n % _BLOCK
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
        starts = jnp.concatenate([starts, jnp.zeros((pad,), jnp.bool_)])
    lanes = (n + pad) // _BLOCK
    (totals, any_start), (local, seen) = lax.scan(
        step, (jnp.zeros((lanes,), v.dtype), jnp.zeros((lanes,), jnp.bool_)),
        (v.reshape(lanes, _BLOCK).T, starts.reshape(lanes, _BLOCK).T))
    entering = jnp.concatenate(
        [jnp.zeros((1,), v.dtype), _restarting_sum(totals, any_start)[:-1]])
    out = local + jnp.where(seen, zero, entering[None, :])
    return out.T.reshape(n + pad)[:n]


def running_count(flags: Array) -> Array:
    """Inclusive running count of the True entries of `flags` (int32): the
    blocked scan of `_restarting_sum` with no restart, where an int32
    `cumsum` of 2^21 rows costs ~12 s of compile on this chip."""
    return _restarting_sum(flags.astype(jnp.int32),
                           jnp.zeros(flags.shape, jnp.bool_))


def _at_group_rows(x: Array, idx: Array, layout: GroupLayout) -> Array:
    """`x` at row `idx[g]` in slot g, exactly 0 past `num_groups`: with
    `layout.end_idx` a run's last row (`seg_sum`), with `layout.start_idx`
    its first (the group's key).

    A gather by computed index costs by the slots it fills, live or not
    (8-16 ns each on a v5e), so where the groups fit the first 1/`_FEW` of
    the slots, which is seen at run time, only those are gathered. The
    other branch fills every slot and pays ~60 % over a plain gather for
    sitting in a conditional (PERF.md section 6, PR 31). Up to `_PLAIN`
    slots every one is gathered outright: that is under a millisecond,
    less than the conditional is worth (it doubles the reduction's compile
    on the CPU backend, where the tests' watchdogs count compile as
    silence)."""
    cap = x.shape[0]
    few = cap // _FEW
    zero = jnp.zeros((), x.dtype)

    def every_slot(_):
        return jnp.where(layout.group_mask, x[idx], zero)

    def first_slots(_):
        head = jnp.where(layout.group_mask[:few], x[idx[:few]], zero)
        return jnp.concatenate([head, jnp.zeros((cap - few,), x.dtype)])

    if cap <= _PLAIN:
        return every_slot(None)
    return lax.cond(layout.num_groups <= few, first_slots, every_slot, None)


def group_first_rows(col: Column, layout: GroupLayout) -> Column:
    """Each run's first row of `col` in slot g: a group's key, off the
    key-sorted batch. A flat column goes plane by plane through
    `_at_group_rows`, so 18,000 groups in 2^21 slots gather 2^17 of them and
    not every one (where all the slots past the groups read row 0 the gather
    took 37 to 106 ms by where its buffers lay: PERF.md section 6, PR 32);
    strings, lists and structs are taken whole."""
    idx = jnp.clip(layout.start_idx, 0, col.capacity - 1)
    if col.is_string or col.is_list or col.is_struct:
        return col.take(idx)
    v = col.validity
    return Column(col.dtype, _at_group_rows(col.data, idx, layout),
                  None if v is None else _at_group_rows(v, idx, layout))


def seg_sum(values: Array, layout: GroupLayout, valid: Array) -> Array:
    """Per-group sum of `values` over rows that are live and `valid`, in
    slot g for group g and exactly 0 in every slot past `num_groups`. bool
    values are counted (int32); other dtypes sum in their own dtype,
    integers wrapping."""
    _note_form("scan")
    live = valid & layout.row_mask
    if values.dtype == jnp.bool_:
        v = (values & live).astype(jnp.int32)
    else:
        # the dtype the numbers are added in, read off the array itself: a
        # decimal's unscaled integers cast to double upstream show here
        _note_form("sums")
        if jnp.issubdtype(values.dtype, jnp.integer):
            _note_form("int_sums")
        v = jnp.where(live, values, jnp.zeros((), values.dtype))
    return _at_group_rows(_restarting_sum(v, layout.starts), layout.end_idx,
                          layout)


def seg_count(valid: Array, layout: GroupLayout) -> Array:
    """Per-group count of the live rows where `valid` holds (int64)."""
    return seg_sum(valid, layout, valid).astype(jnp.int64)


def seg_any(flags: Array, layout: GroupLayout) -> Array:
    """Per-group OR (compacted to group slots)."""
    return seg_sum(flags, layout, flags) > 0


def seg_min(values, layout, valid):
    """Per-group MIN skipping nulls, Spark NaN semantics (NaN is the
    GREATEST value: min picks non-NaN when one exists, NaN only when the
    group is all-NaN)."""
    cap = values.shape[0]
    any_valid = seg_any(valid, layout)
    if jnp.issubdtype(values.dtype, jnp.floating):
        nonnan = valid & ~jnp.isnan(values)
        inf = jnp.asarray(jnp.inf, values.dtype)
        v = jnp.where(nonnan & layout.row_mask, values, inf)
        mins = jax.ops.segment_min(v, _seg_ids(layout, nonnan),
                                   num_segments=cap)
        any_nonnan = seg_any(nonnan, layout)
        nan = jnp.asarray(jnp.nan, values.dtype)
        out = jnp.where(any_nonnan, mins,
                        jnp.where(any_valid, nan,
                                  jnp.zeros((), values.dtype)))
        return out, any_valid
    ident = jnp.asarray(jnp.iinfo(values.dtype).max, values.dtype)
    v = jnp.where(valid & layout.row_mask, values, ident)
    mins = jax.ops.segment_min(v, _seg_ids(layout, valid), num_segments=cap)
    return jnp.where(any_valid, mins, jnp.zeros((), values.dtype)), any_valid


def seg_max(values, layout, valid):
    """Per-group MAX skipping nulls; the max combiner propagates NaN, which
    IS Spark's answer (NaN greatest)."""
    cap = values.shape[0]
    any_valid = seg_any(valid, layout)
    if jnp.issubdtype(values.dtype, jnp.floating):
        ninf = jnp.asarray(-jnp.inf, values.dtype)
        v = jnp.where(valid & layout.row_mask, values, ninf)
        maxs = jax.ops.segment_max(v, _seg_ids(layout, valid),
                                   num_segments=cap)
        # scatter-max fill/combine may pick non-NaN over NaN; enforce
        # Spark's NaN-greatest explicitly
        has_nan = seg_any(valid & jnp.isnan(values), layout)
        nan = jnp.asarray(jnp.nan, values.dtype)
        out = jnp.where(has_nan, nan,
                        jnp.where(any_valid, maxs,
                                  jnp.zeros((), values.dtype)))
        return out, any_valid
    ident = jnp.asarray(jnp.iinfo(values.dtype).min, values.dtype)
    v = jnp.where(valid & layout.row_mask, values, ident)
    maxs = jax.ops.segment_max(v, _seg_ids(layout, valid), num_segments=cap)
    return jnp.where(any_valid, maxs, jnp.zeros((), values.dtype)), any_valid


def _any(flags, layout):
    return seg_any(flags, layout)


def seg_first(values: Array, layout: GroupLayout, valid: Array,
              ignores_null: bool) -> Tuple[Array, Array]:
    """First (optionally first non-null) value per group (ref agg/first.rs,
    first_ignores_null.rs): scatter-min of the qualifying row index, then a
    gather."""
    if not ignores_null:
        first_vals = values[layout.start_idx]
        first_valid = (valid & layout.row_mask)[layout.start_idx]
        return first_vals, first_valid
    cap = values.shape[0]
    live_valid = valid & layout.row_mask
    iota = jnp.arange(cap, dtype=jnp.int32)
    idx = jax.ops.segment_min(jnp.where(live_valid, iota, jnp.int32(cap)),
                              _seg_ids(layout, live_valid),
                              num_segments=cap)
    has = idx < cap
    val = values[jnp.clip(idx, 0, cap - 1)]
    return jnp.where(has, val, jnp.zeros((), values.dtype)), has
