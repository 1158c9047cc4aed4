"""Device-resident columnar batches with static (bucketed) shapes.

This is the engine's unit of data flow — the TPU-native replacement for the
reference's Arrow `RecordBatch` streaming (every operator there is a stream of
RecordBatches re-chunked by CoalesceStream, streams/coalesce_stream.rs). XLA
wants static shapes, so a batch here is:

  * a static `capacity` (bucketed power of two — the jit-cache key),
  * a traced `num_rows` scalar: rows [0, num_rows) are live, the rest padding,
  * one `Column` per field: dense device array + optional validity mask;
    strings/binary are fixed-width uint8 matrices (capacity, W) + lengths,
    with W bucketed as well.

Invariants ops may rely on:
  * invalid slots among LIVE rows contain the dtype's zero (see
    `Column.normalized`), so hashing/sorting null slots is deterministic;
  * padding rows (>= num_rows) have UNSPECIFIED content — any op that
    reduces, hashes, sorts, or serializes full-capacity arrays MUST mask
    with `row_mask()` first;
  * `validity is None` means all live rows valid.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from blaze_tpu.config import conf
from blaze_tpu.columnar.types import (
    INT64, DataType, Field, Schema, TypeKind,
)

Array = jax.Array


def bucket_capacity(n: int) -> int:
    """Round row count up to a power-of-two capacity bucket."""
    cap = max(int(conf.min_capacity), 1)
    while cap < n:
        cap <<= 1
    return cap


# -- the control pulls' seam ---------------------------------------------------
# Between two device programs the driver reads a small value back to decide
# what to run next: a batch's live rows, an exchange's bounds or counts, a
# join's pair total. Every such read goes through `pull_rows` / `pull_array`,
# so that with tracing on each is one `wait` span (runtime/trace.SPAN_KINDS)
# named by its `site`: a static string, `<layer>.<purpose>`, listed in PERF.md
# section 3. Off, the cost is the call and one truthiness check.


def pull_rows(batch: "ColumnBatch", site: str) -> int:
    """`batch`'s live rows as a Python int. The host blocks here until the
    device has made `num_rows` and the value has crossed."""
    if conf.trace_enabled:
        return _waited(batch.num_rows, site, int)
    return int(batch.num_rows)


def pull_array(x, site: str) -> np.ndarray:
    """A small device array (bounds, counts, a total) as numpy; blocks like
    `pull_rows`."""
    if conf.trace_enabled:
        return _waited(x, site, np.asarray)
    return np.asarray(x)


def _waited(x, site: str, pull):
    """`pull(x)` inside a `wait` span. `ready` is read just before the
    pull: True, the device had finished when the host asked and the span
    is the round trip alone; False, the host got there first and stands
    still for the device's work as well. A value that is a host number
    already (or a tracer, whose pull raises as it always did) opens no
    span."""
    is_ready = getattr(x, "is_ready", None)
    if is_ready is None:
        return pull(x)
    from blaze_tpu.runtime import trace

    with trace.span("wait", site=site, ready=bool(is_ready())):
        return pull(x)


def nonzero_i32(mask: Array, size: int, fill_value: int = 0) -> Array:
    """`jnp.nonzero(mask, size=size, fill_value=fill_value)[0]` in 32-bit
    arithmetic: int32 (size,) positions of the True entries in order,
    padded with `fill_value`, truncated past `size`.

    jnp.nonzero runs two cumsums and a div/mod in the default int, which
    under x64 is int64 — emulated on TPU, where that program took 56 s to
    compile at 2^21 rows against 11 s for this form (one reading, PR 21
    chip run). One int32 cumsum ranks the True rows; one scatter places
    their positions (False rows and overflow aim past the end and drop)."""
    pos = jnp.cumsum(mask, dtype=jnp.int32) - 1
    out = jnp.full((size,), fill_value, jnp.int32)
    return out.at[jnp.where(mask, pos, size)].set(
        jnp.arange(mask.shape[0], dtype=jnp.int32), mode="drop")


def rows_to_ranks(x: Array, dest: Array) -> Array:
    """1-D plane `x` with row i in slot `dest[i]`: rows aimed past the end
    drop, slots no row reaches are 0. What a filter's compaction does to a
    plane once `dest` holds each kept row's rank.

    Written as a scatter of 32-bit words: on a v5e a scatter of one 32-bit
    operand at rising positions costs 5 ns a row whatever the buffers'
    addresses, where the gather of the same plane by computed index costs
    22 ns a row a word and, for the word that lands in fast memory, 20-27
    depending on where the allocator put the source (43-57 ms at 2^21: a
    level a process keeps; PERF.md section 6, PR 32). A 64-bit scatter is
    one program of two operands at 70 ns a row, so an int64 goes as its
    two halves (mask and shift, `bits64.i64_halves`: no 64-bit bitcast)."""
    if x.dtype.itemsize == 8:
        from blaze_tpu.columnar.bits64 import i64_halves

        hi, lo = i64_halves(x.astype(jnp.int64))
        hi, lo = rows_to_ranks(hi, dest), rows_to_ranks(lo, dest)
        whole = (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)
        return whole.astype(x.dtype)
    return jnp.zeros(x.shape, x.dtype).at[dest].set(x, mode="drop")


def _ranks_well(x) -> bool:
    """Planes `rows_to_ranks` takes: 1-D flags and 32- or 64-bit integers
    (a date, a timestamp, a compact decimal's unscaled value among them).
    A double has no 64-bit bitcast on the chip (bits64.py), narrower
    integers and floats have not been measured: those are gathered."""
    if isinstance(x, (StringData, DictData, ListData, StructData)):
        return False
    return x.ndim == 1 and (x.dtype == jnp.bool_ or (
        jnp.issubdtype(x.dtype, jnp.integer) and x.dtype.itemsize >= 4))


def bucket_width(w: int) -> int:
    """Round string byte-width up to a power-of-two bucket (min 4).

    Raises beyond conf.max_string_width — a single huge value would otherwise
    inflate the whole (capacity, width) matrix; such columns must take a host
    fallback path instead.
    """
    b = max(int(conf.min_string_width), 4)
    while b < w:
        b <<= 1
    if b > conf.max_string_width:
        raise ValueError(
            f"string width {w} (bucket {b}) exceeds max_string_width="
            f"{conf.max_string_width}")
    return b


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StringData:
    """Fixed-width string/binary storage: (capacity, width) uint8 + lengths."""

    bytes: Array    # uint8 (capacity, width)
    lengths: Array  # int32 (capacity,)

    @property
    def capacity(self) -> int:
        return self.bytes.shape[0]

    @property
    def width(self) -> int:
        return self.bytes.shape[1]

    def tree_flatten(self):
        return (self.bytes, self.lengths), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def bucket_dict_rows(k: int) -> int:
    """Round dictionary entry count up to a power-of-two bucket (min 8).

    Dictionaries are small by construction (dict_max_cardinality caps
    them), so they get their own bucket ladder instead of min_capacity —
    padding a 12-entry dict to 1024 rows would erase the encoding win.
    """
    cap = 8
    while cap < k:
        cap <<= 1
    return cap


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DictData:
    """Dictionary-encoded string/binary storage: per-row int32 codes into
    a small (dict_capacity, width) uint8 dictionary.

    INVARIANT: dictionary entry 0 is ALWAYS the empty string (all-zero
    row, length 0). Encoders must guarantee it; `Column.normalized` and
    padding rows rely on it to null-out a row by pointing its code at 0.

    The lazy `bytes`/`lengths` properties expand to the StringData layout
    via an in-jit gather, so every existing `.data.bytes`/`.data.lengths`
    call site (hash, compare, sort keys) works on the encoded form
    without a host round-trip."""

    codes: Array         # int32 (capacity,)
    dict_bytes: Array    # uint8 (dict_capacity, width)
    dict_lengths: Array  # int32 (dict_capacity,)

    @property
    def capacity(self) -> int:
        return self.codes.shape[0]

    @property
    def width(self) -> int:
        return self.dict_bytes.shape[1]

    @property
    def dict_capacity(self) -> int:
        return self.dict_bytes.shape[0]

    @property
    def bytes(self) -> Array:
        return self.dict_bytes[self.codes]

    @property
    def lengths(self) -> Array:
        return self.dict_lengths[self.codes]

    def tree_flatten(self):
        return (self.codes, self.dict_bytes, self.dict_lengths), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ListData:
    """list<T> storage: per-row [offset, offset+length) into a flat element
    column. Element storage has its own (bucketed) capacity; rows beyond
    num_rows have length 0. The layout mirrors Arrow's offsets+child but
    with static capacities so explode/collect stay jit-compilable."""

    offsets: Array        # int32 (capacity + 1,), monotone
    elements: "Column"    # flat element column

    @property
    def capacity(self) -> int:
        return self.offsets.shape[0] - 1

    def lengths(self) -> Array:
        return self.offsets[1:] - self.offsets[:-1]

    def tree_flatten(self):
        return (self.offsets, self.elements), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StructData:
    """struct<...> storage: one row-aligned child Column per field.

    MAP columns do not get their own container — a map is stored as
    list<struct<key, value>> (Arrow's map layout, types.storage_element),
    so all list machinery (take/concat/serde/spill) covers maps."""

    children: List["Column"]

    @property
    def capacity(self) -> int:
        return self.children[0].capacity

    def tree_flatten(self):
        return tuple(self.children), len(self.children)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(list(children))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Column:
    dtype: DataType
    data: Union[Array, StringData, DictData, ListData, StructData]
    validity: Optional[Array] = None  # bool (capacity,); None = all valid

    @property
    def capacity(self) -> int:
        if isinstance(self.data, (StringData, DictData, ListData,
                                  StructData)):
            return self.data.capacity
        return self.data.shape[0]

    @property
    def is_string(self) -> bool:
        return isinstance(self.data, (StringData, DictData))

    @property
    def is_dict(self) -> bool:
        return isinstance(self.data, DictData)

    @property
    def is_list(self) -> bool:
        return isinstance(self.data, ListData)

    @property
    def is_struct(self) -> bool:
        return isinstance(self.data, StructData)

    def valid_mask(self) -> Array:
        if self.validity is None:
            return jnp.ones((self.capacity,), dtype=jnp.bool_)
        return self.validity

    def normalized(self) -> "Column":
        """Zero out data in invalid slots (canonical form for hash/sort/serde)."""
        if self.dtype.wide_decimal and self.validity is not None:
            v = self.validity
            planes = [Column(ch.dtype, jnp.where(v, ch.data, jnp.int64(0)),
                             None) for ch in self.data.children]
            return Column(self.dtype, StructData(planes), v)
        if self.validity is None or self.is_list or self.is_struct:
            return self
        if self.is_dict:
            # dict entry 0 is the empty string (DictData invariant), so
            # nulling a row is a code rewrite — the dictionary itself
            # stays shared and untouched
            v = self.validity
            codes = jnp.where(v, self.data.codes, jnp.int32(0))
            return Column(self.dtype, DictData(
                codes, self.data.dict_bytes, self.data.dict_lengths), v)
        if self.is_string:
            v = self.validity
            b = jnp.where(v[:, None], self.data.bytes, jnp.uint8(0))
            l = jnp.where(v, self.data.lengths, jnp.int32(0))
            return Column(self.dtype, StringData(b, l), v)
        zero = jnp.zeros((), dtype=self.data.dtype)
        return Column(self.dtype, jnp.where(self.validity, self.data, zero), self.validity)

    def take(self, indices: Array, *, index_valid: Optional[Array] = None) -> "Column":
        """Gather rows by index. `index_valid=False` slots become null.

        List columns: element storage capacity is preserved — valid for
        permutations/subsets (sort, filter, limit), NOT for fan-out takes
        (join expansion over list columns would overflow it).
        """
        idx = jnp.clip(indices, 0, self.capacity - 1)
        v = self.validity
        if self.is_list:
            data = _list_take(self.data, idx)
        elif self.is_struct:
            data = StructData([ch.take(idx) for ch in self.data.children])
        elif self.is_dict:
            # gather codes only — the column stays encoded through
            # filter/sort/join/limit; the dictionary is shared as-is
            data = DictData(self.data.codes[idx], self.data.dict_bytes,
                            self.data.dict_lengths)
        elif self.is_string:
            data = StringData(self.data.bytes[idx], self.data.lengths[idx])
        else:
            data = self.data[idx]
        v = v[idx] if v is not None else None
        if index_valid is not None:
            v = index_valid if v is None else (v & index_valid)
        return Column(self.dtype, data, v)

    @property
    def row_aligned(self) -> bool:
        """Every plane holds row r at index r: true of all storage but a
        list's elements (its children's, where a struct holds one)."""
        if self.is_struct:
            return all(ch.row_aligned for ch in self.data.children)
        return not self.is_list

    def slice_rows(self, start: Array, cap: int) -> "Column":
        """Rows [start, start + cap) as a column of capacity `cap`: a
        contiguous copy of every plane (`_row_range`), where `take` of the
        same range is a gather by computed index. `start` is a traced
        int32 in [0, capacity]; the column must be `row_aligned`."""
        v = self.validity
        if self.is_struct:
            data = StructData([ch.slice_rows(start, cap)
                               for ch in self.data.children])
        elif self.is_dict:
            # the codes alone, as in `take`: the dictionary stays shared
            data = DictData(_row_range(self.data.codes, start, cap),
                            self.data.dict_bytes, self.data.dict_lengths)
        elif self.is_string:
            data = StringData(_row_range(self.data.bytes, start, cap),
                              _row_range(self.data.lengths, start, cap))
        else:
            assert not self.is_list, "a list's elements are not row-aligned"
            data = _row_range(self.data, start, cap)
        return Column(self.dtype, data,
                      None if v is None else _row_range(v, start, cap))

    def tree_flatten(self):
        return (self.data, self.validity), self.dtype

    @classmethod
    def tree_unflatten(cls, dtype, children):
        data, validity = children
        return cls(dtype, data, validity)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ColumnBatch:
    schema: Schema
    columns: List[Column]
    num_rows: Array  # int32 scalar (traced)
    capacity: int    # static

    # ---- construction ----
    @staticmethod
    def make(schema: Schema, columns: Sequence[Column], num_rows) -> "ColumnBatch":
        cap = columns[0].capacity if columns else bucket_capacity(0)
        return ColumnBatch(schema, list(columns), jnp.asarray(num_rows, jnp.int32), cap)

    @staticmethod
    def empty(schema: Schema, capacity: Optional[int] = None) -> "ColumnBatch":
        cap = capacity or bucket_capacity(0)
        cols = [_zero_column(f.dtype, cap) for f in schema]
        return ColumnBatch(schema, cols, jnp.asarray(0, jnp.int32), cap)

    @staticmethod
    def from_numpy(data: Dict[str, np.ndarray], schema: Schema,
                   capacity: Optional[int] = None,
                   validity: Optional[Dict[str, np.ndarray]] = None) -> "ColumnBatch":
        """Test/ingest helper: numpy (or list-of-str) per field -> device batch."""
        n = len(next(iter(data.values()))) if data else 0
        cap = capacity or bucket_capacity(n)
        cols = []
        for f in schema:
            raw = data[f.name]
            v_np = None if validity is None else validity.get(f.name)
            cols.append(_host_to_column(f.dtype, raw, cap, v_np))
        return ColumnBatch(schema, cols, jnp.asarray(n, jnp.int32), cap)

    # ---- views ----
    def column(self, i: int) -> Column:
        return self.columns[i]

    def by_name(self, name: str) -> Column:
        return self.columns[self.schema.index_of(name)]

    def row_mask(self) -> Array:
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    def shape_key(self) -> tuple:
        """Jit-cache shape-bucket signature (capacity, per-column layout)."""
        parts: list = [self.capacity]
        for c in self.columns:
            parts.append(_col_shape_key(c))
        return tuple(parts)

    def live_valid(self, i: int) -> Array:
        """validity AND row-liveness for column i."""
        return self.columns[i].valid_mask() & self.row_mask()

    # ---- transforms ----
    def with_columns(self, schema: Schema, columns: Sequence[Column]) -> "ColumnBatch":
        return ColumnBatch(schema, list(columns), self.num_rows, self.capacity)

    def with_num_rows(self, num_rows) -> "ColumnBatch":
        return ColumnBatch(self.schema, self.columns, jnp.asarray(num_rows, jnp.int32), self.capacity)

    def select(self, indices: Sequence[int]) -> "ColumnBatch":
        fields = [self.schema.fields[i] for i in indices]
        cols = [self.columns[i] for i in indices]
        return ColumnBatch(Schema(fields), cols, self.num_rows, self.capacity)

    def take(self, indices: Array, num_rows, *, index_valid: Optional[Array] = None) -> "ColumnBatch":
        # output capacity = len(indices): callers must pass bucket-sized index
        # arrays (compact/sort/join all do) to preserve the jit-cache invariant
        cols = [c.take(indices, index_valid=index_valid) for c in self.columns]
        cap = int(indices.shape[0])
        return ColumnBatch(self.schema, cols, jnp.asarray(num_rows, jnp.int32), cap)

    @property
    def row_aligned(self) -> bool:
        return all(c.row_aligned for c in self.columns)

    def slice_rows(self, start, cap: int, num_rows) -> "ColumnBatch":
        """Rows [start, start + cap) as a batch of static capacity `cap`
        holding `num_rows` live rows, cut out by contiguous copies: what
        `take(arange(cap) + start, num_rows)` gathers row by row. `start`
        may be traced, and `start + cap` may pass the capacity: the window
        is never moved back over rows before `start`, and slots past the
        input's last are, like all past `num_rows`, nobody's to read.
        Only for a `row_aligned` batch (`ops.common.slice_batch` asks)."""
        start = jnp.asarray(start, jnp.int32)
        cols = [c.slice_rows(start, cap) for c in self.columns]
        return ColumnBatch(self.schema, cols,
                           jnp.asarray(num_rows, jnp.int32), cap)

    def compact(self, keep: Array) -> "ColumnBatch":
        """Filter: keep rows where `keep & row_mask`, compacted to the front.

        Static-shape: output capacity equals input capacity (a later
        coalesce can re-bucket downward). The kept rows go to their ranks
        by `place_rows`.
        """
        mask = keep & self.row_mask()
        n = jnp.sum(mask, dtype=jnp.int32)
        # a kept row's rank among the kept; a dropped row aims past the end
        dest = jnp.where(mask, jnp.cumsum(mask, dtype=jnp.int32) - 1,
                         self.capacity)
        return self.place_rows(dest, n)

    def place_rows(self, dest: Array, num_rows,
                   tally: Optional[dict] = None) -> "ColumnBatch":
        """Row i of every column in slot `dest[i]`, holding `num_rows`
        live rows: a filter's compaction when `dest` is a kept row's rank
        among the kept, the exchange's grouping by partition when it is a
        row's partition's start plus its rank there. Rows aimed past the
        end drop; no two rows may share a slot.

        A column of flags or 32/64-bit integers is scattered plane by plane
        (`rows_to_ranks`); any other is gathered whole at the row that
        lands in each slot, found by one scatter of the row numbers. Slots
        no row reaches hold zeros and nulls in the first case, row 0 in the
        second, and are nobody's to read. `tally`, where given, gains each
        column's planes (its data, and its validity if any) under "ranked"
        or "gathered": what the program moved each way."""
        idx, cols = None, []
        for c in self.columns:
            ranked = _ranks_well(c.data)
            if ranked:
                v = c.validity
                cols.append(Column(c.dtype, rows_to_ranks(c.data, dest),
                                   None if v is None
                                   else rows_to_ranks(v, dest)))
            else:
                if idx is None:   # the row that lands in each slot
                    idx = rows_to_ranks(
                        jnp.arange(self.capacity, dtype=jnp.int32), dest)
                cols.append(c.take(idx))
            if tally is not None:
                form = "ranked" if ranked else "gathered"
                tally[form] = (tally.get(form, 0)
                               + (1 if c.validity is None else 2))
        return ColumnBatch(self.schema, cols,
                           jnp.asarray(num_rows, jnp.int32), self.capacity)

    def normalized(self) -> "ColumnBatch":
        return self.with_columns(self.schema, [c.normalized() for c in self.columns])

    # ---- host export (tests / serde) ----
    def to_numpy(self) -> Dict[str, object]:
        """Pull live rows to host. Strings -> list[bytes|None]; lists ->
        list[list|None]; numerics -> numpy masked to live rows with None
        for nulls (object arrays). A decimal comes back as its UNSCALED
        integer (a compact one, p <= 18, as int64; a wide one as Python
        ints): the scale is the schema's, not the array's, so 12.34 typed
        decimal(7,2) reads 1234 and a caller that drops the schema cannot
        tell cents from millionths."""
        # the ordered-collect path (local_runner) materializes on host
        # and caches the pylike dict so the driver does not pull the
        # same rows through the (slow) device->host link twice
        cached = getattr(self, "_host_numpy", None)
        if cached is not None:
            return cached
        if not conf.trace_enabled:
            return self._pull_numpy()
        from blaze_tpu.runtime import memory, trace

        # a d2h span; the batch run_plan returned remembers its query
        # (the caller pulls after that query's context is popped), and
        # its pull is the query's `final` one. bytes: np.asarray pulls a
        # column's whole capacity, which is what batch_nbytes counts
        qid = getattr(self, "_query_id", None)
        with trace.context(query_id=qid), \
                trace.span("d2h", what="to_numpy", final=qid is not None,
                           bytes=memory.batch_nbytes(self)) as sp:
            out = self._pull_numpy()
            sp.set(rows=len(next(iter(out.values()), ())))
        return out

    def _pull_numpy(self) -> Dict[str, object]:
        n = pull_rows(self, "d2h.numpy_rows")
        out: Dict[str, object] = {}
        for f, c in zip(self.schema, self.columns):
            valid = np.asarray(c.valid_mask())[:n]
            if c.is_list:
                offs = np.asarray(c.data.offsets)
                esub = ColumnBatch(
                    Schema([Field("e", c.data.elements.dtype)]),
                    [c.data.elements],
                    jnp.asarray(int(offs[n]), jnp.int32),
                    c.data.elements.capacity)
                elems = esub._pull_numpy()["e"]
                if f.dtype.kind == TypeKind.MAP:
                    # entries are (key, value) structs -> dict per row
                    vals = [dict(elems[offs[i]:offs[i + 1]]) if valid[i]
                            else None for i in range(n)]
                else:
                    vals = [list(elems[offs[i]:offs[i + 1]]) if valid[i]
                            else None for i in range(n)]
                out[f.name] = vals
                continue
            if f.dtype.wide_decimal:
                from blaze_tpu.columnar import int128 as i128

                hi = np.asarray(c.data.children[0].data)[:n]
                lo = np.asarray(c.data.children[1].data)[:n]
                ints = i128.ints_from_np(hi, lo)
                out[f.name] = [ints[i] if valid[i] else None
                               for i in range(n)]
                continue
            if c.is_struct:
                sub = ColumnBatch(
                    Schema([Field(sf.name, sf.dtype)
                            for sf in c.dtype.fields]),
                    list(c.data.children), self.num_rows, c.capacity)
                cols = sub._pull_numpy()
                vals = [tuple(cols[sf.name][i] for sf in c.dtype.fields)
                        if valid[i] else None for i in range(n)]
                out[f.name] = vals
                continue
            if c.is_dict:
                # decode at the result-merge edge: pull codes + the small
                # dictionary, expand host-side (never materializes the
                # (n, W) matrix on device)
                codes = np.asarray(c.data.codes)[:n]
                db = np.asarray(c.data.dict_bytes)
                dl = np.asarray(c.data.dict_lengths)
                vals = [bytes(db[codes[i], : dl[codes[i]]]) if valid[i]
                        else None for i in range(n)]
                out[f.name] = vals
            elif c.is_string:
                b = np.asarray(c.data.bytes)[:n]
                l = np.asarray(c.data.lengths)[:n]
                vals = [bytes(b[i, : l[i]]) if valid[i] else None for i in range(n)]
                out[f.name] = vals
            else:
                d = np.asarray(c.data)[:n]
                if valid.all():
                    out[f.name] = d
                else:
                    o = d.astype(object)
                    o[~valid] = None
                    out[f.name] = o
        return out

    def tree_flatten(self):
        return (self.columns, self.num_rows), (self.schema, self.capacity)

    @classmethod
    def tree_unflatten(cls, aux, children):
        schema, capacity = aux
        columns, num_rows = children
        return cls(schema, list(columns), num_rows, capacity)


def _col_shape_key(c: Column) -> tuple:
    if c.is_list:
        return ("l", c.data.elements.capacity,
                _col_shape_key(c.data.elements), c.validity is not None)
    if c.is_struct:
        return ("t", tuple(_col_shape_key(ch) for ch in c.data.children),
                c.validity is not None)
    if c.is_dict:
        return ("d", c.data.width, c.data.dict_capacity,
                c.validity is not None)
    if c.is_string:
        return ("s", c.data.width, c.validity is not None)
    return (str(c.data.dtype), c.validity is not None)


def _row_range(x: Array, start: Array, cap: int) -> Array:
    """`x[start : start + cap]` along the row axis for a traced `start` in
    [0, len(x)]. `lax.dynamic_slice` alone clamps a window that passes the
    end back over the rows before `start`; over the plane padded by `cap`
    it never has to, and XLA:TPU compiles the pair to copies."""
    pad = [(0, cap)] + [(0, 0)] * (x.ndim - 1)
    return jax.lax.dynamic_slice_in_dim(jnp.pad(x, pad), start, cap, axis=0)


def _list_take(ld: ListData, idx: Array) -> ListData:
    """Gather list rows: rebuild offsets from gathered lengths and compact
    the referenced element ranges to the front of the element storage."""
    from blaze_tpu.ops.segment import element_rows

    lens = ld.lengths()[idx]
    new_off = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(lens, dtype=jnp.int32)])
    ecap = ld.elements.capacity
    out_rows = idx.shape[0]
    _, row, within, live = element_rows(new_off, out_rows, ecap)
    src = ld.offsets[idx[row]] + within
    elems = ld.elements.take(jnp.where(live, src, 0))
    return ListData(new_off, elems)


def _zero_column(dtype: DataType, cap: int) -> Column:
    from blaze_tpu.columnar.types import storage_element

    if dtype.wide_decimal:
        z = jnp.zeros((cap,), jnp.int64)
        return Column(dtype, StructData(
            [Column(INT64, z, None), Column(INT64, z, None)]), None)
    if dtype.is_string_like:
        w = bucket_width(1)
        return Column(dtype, StringData(jnp.zeros((cap, w), jnp.uint8),
                                        jnp.zeros((cap,), jnp.int32)), None)
    if dtype.kind in (TypeKind.LIST, TypeKind.MAP):
        return Column(dtype, ListData(jnp.zeros((cap + 1,), jnp.int32),
                                      _zero_column(storage_element(dtype),
                                                   bucket_capacity(0))),
                      None)
    if dtype.kind == TypeKind.STRUCT:
        return Column(dtype, StructData(
            [_zero_column(f.dtype, cap) for f in dtype.fields]), None)
    if dtype.kind == TypeKind.NULL:
        return Column(dtype, jnp.zeros((cap,), jnp.int8), jnp.zeros((cap,), jnp.bool_))
    return Column(dtype, jnp.zeros((cap,), dtype.jnp_dtype()), None)


def _host_to_column(dtype: DataType, raw, cap: int, validity_np: Optional[np.ndarray]) -> Column:
    from blaze_tpu.columnar.types import storage_element

    if dtype.wide_decimal:
        import decimal as _dec

        from blaze_tpu.columnar import int128 as i128

        vals = list(raw)
        if validity_np is None and any(v is None for v in vals):
            validity_np = np.array([v is not None for v in vals], bool)
        ints = []
        for v in vals:
            if v is None:
                ints.append(0)
            elif isinstance(v, _dec.Decimal):
                ints.append(int(v.scaleb(dtype.scale)))
            else:
                ints.append(int(v))  # already-unscaled int
        n = len(ints)
        hi_np, lo_np = i128.np_from_ints(ints)
        hi = np.zeros((cap,), np.int64)
        lo = np.zeros((cap,), np.int64)
        hi[:n], lo[:n] = hi_np, lo_np
        return Column(dtype, StructData(
            [Column(INT64, jnp.asarray(hi), None),
             Column(INT64, jnp.asarray(lo), None)]),
            _pad_validity(validity_np, n, cap)).normalized()
    if dtype.kind in (TypeKind.LIST, TypeKind.MAP):
        vals = list(raw)
        if validity_np is None and any(v is None for v in vals):
            validity_np = np.array([v is not None for v in vals], bool)
        if dtype.kind == TypeKind.MAP:
            # accept dicts (or (k, v) pair lists); store entries as structs
            vals = [(list(v.items()) if isinstance(v, dict) else list(v))
                    if v is not None else [] for v in vals]
        else:
            vals = [v if v is not None else [] for v in vals]
        n = len(vals)
        lens = np.zeros((cap,), np.int32)
        lens[:n] = [len(v) for v in vals]
        offsets = np.zeros((cap + 1,), np.int32)
        offsets[1:] = np.cumsum(lens)
        flat = [x for v in vals for x in v]
        ecap = bucket_capacity(len(flat))
        elems = _host_to_column(storage_element(dtype), flat, ecap, None)
        return Column(dtype,
                      ListData(jnp.asarray(offsets), elems),
                      _pad_validity(validity_np, n, cap))
    if dtype.kind == TypeKind.STRUCT:
        vals = list(raw)
        if validity_np is None and any(v is None for v in vals):
            validity_np = np.array([v is not None for v in vals], bool)
        n = len(vals)
        children = []
        for fi, f in enumerate(dtype.fields):
            fvals = []
            for v in vals:
                if v is None:
                    fvals.append(None)
                elif isinstance(v, dict):
                    fvals.append(v.get(f.name))
                else:
                    fvals.append(v[fi])
            children.append(_host_to_column(f.dtype, fvals, cap, None))
        return Column(dtype, StructData(children),
                      _pad_validity(validity_np, n, cap))
    if dtype.is_string_like:
        vals = [v if v is not None else b"" for v in raw]
        vals = [v.encode() if isinstance(v, str) else bytes(v) for v in vals]
        if validity_np is None and any(v is None for v in raw):
            validity_np = np.array([v is not None for v in raw], bool)
        n = len(vals)
        w = bucket_width(max((len(v) for v in vals), default=1) or 1)
        mat = np.zeros((cap, w), np.uint8)
        lens = np.zeros((cap,), np.int32)
        for i, v in enumerate(vals):
            mat[i, : len(v)] = np.frombuffer(v, np.uint8)
            lens[i] = len(v)
        col = Column(dtype, StringData(jnp.asarray(mat), jnp.asarray(lens)), _pad_validity(validity_np, n, cap))
        return col.normalized()
    arr = np.asarray(raw)
    n = arr.shape[0]
    if validity_np is None and arr.dtype == object:
        validity_np = np.array([v is not None for v in arr], bool)
        arr = np.array([v if v is not None else 0 for v in arr])
    out = np.zeros((cap,), dtype.np_dtype())
    out[:n] = arr.astype(dtype.np_dtype())
    col = Column(dtype, jnp.asarray(out), _pad_validity(validity_np, n, cap))
    return col.normalized()


def _pad_validity(validity_np: Optional[np.ndarray], n: int, cap: int) -> Optional[Array]:
    if validity_np is None:
        return None
    v = np.zeros((cap,), bool)
    v[:n] = np.asarray(validity_np, bool)[:n]
    return jnp.asarray(v)
