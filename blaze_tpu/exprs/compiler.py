"""Expression compiler: IR -> jax column functions.

Ref analog: the physical-expression construction in from_proto.rs (lib.rs:
191-535) + CachedExprsEvaluator (datafusion-ext-plans common/
cached_exprs_evaluator.rs). Unlike the reference we do no explicit common-
subexpression elimination or short-circuiting: everything traces into one XLA
program where CSE is automatic and both branches of a select are data-flow
(no branch cost on a vector machine — "short-circuit" SC_AND/SC_OR exists in
the reference to skip expensive UDFs, which run on the host path here anyway).

A compiled expression is `fn(batch: ColumnBatch) -> Column`; null semantics
are Spark's (strict nulls for most ops, Kleene AND/OR, null-prop selects).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from blaze_tpu.columnar.batch import Column, ColumnBatch, StringData
from blaze_tpu.columnar.types import (BOOLEAN, DataType, FLOAT64, INT64,
    TypeKind)
from blaze_tpu.exprs import ir
from blaze_tpu.exprs import strings as S
from blaze_tpu.exprs.cast import cast_column, check_overflow, _const_string, _and_valid

CompiledExpr = Callable[[ColumnBatch], Column]

# ---------------------------------------------------------------------------
# common-subexpression elimination (ref cached_exprs_evaluator.rs:38-60).
# XLA CSEs identical subgraphs AFTER tracing; this memo removes the
# TRACE-TIME cost (and the eager-path re-evaluation cost for unjitted
# host-fn chains): within one cse_scope — one batch flowing through one
# fused chain — each distinct expression key evaluates once.
# ---------------------------------------------------------------------------

import contextlib
import threading

_cse_tls = threading.local()


@contextlib.contextmanager
def cse_scope():
    prev = getattr(_cse_tls, "memo", None)
    _cse_tls.memo = {}
    try:
        yield
    finally:
        _cse_tls.memo = prev


def compile_expr(expr: ir.Expr, schema) -> CompiledExpr:
    """Bind + lower an expression against an input schema (with CSE when
    evaluated inside a cse_scope)."""
    inner = _compile_expr(expr, schema)
    key = ("cse", expr.key())

    def run(b: ColumnBatch) -> Column:
        memo = getattr(_cse_tls, "memo", None)
        if memo is None:
            return inner(b)
        # the entry RETAINS the batch: keying by id() alone would let a
        # freed batch's address be recycled within the scope and serve a
        # stale Column for the new object
        bkey = (id(b),) + key
        hit = memo.get(bkey)
        if hit is None:
            hit = (b, inner(b))
            memo[bkey] = hit
        return hit[1]

    return run


def _compile_expr(expr: ir.Expr, schema) -> CompiledExpr:
    """Bind + lower an expression against an input schema."""
    if isinstance(expr, ir.Col):
        idx = schema.index_of(expr.name)
        return lambda b: b.columns[idx]
    if isinstance(expr, ir.BoundRef):
        idx = expr.index
        return lambda b: b.columns[idx]
    if isinstance(expr, ir.Literal):
        return _compile_literal(expr)
    if isinstance(expr, ir.Binary):
        return _compile_binary(expr, schema)
    if isinstance(expr, ir.Not):
        c = compile_expr(expr.child, schema)
        return lambda b: _map_col(c(b), BOOLEAN, lambda d: ~d)
    if isinstance(expr, ir.Negate):
        c = compile_expr(expr.child, schema)

        def run_neg(b):
            col = c(b)
            if col.dtype.wide_decimal:
                from blaze_tpu.exprs import wide_decimal as W

                return W.negate(col)
            return Column(col.dtype, -col.data, col.validity)

        return run_neg
    if isinstance(expr, ir.IsNull):
        c = compile_expr(expr.child, schema)
        return lambda b: Column(BOOLEAN, ~c(b).valid_mask(), None)
    if isinstance(expr, ir.IsNotNull):
        c = compile_expr(expr.child, schema)
        return lambda b: Column(BOOLEAN, c(b).valid_mask(), None)
    if isinstance(expr, ir.Cast):
        c = compile_expr(expr.child, schema)
        dt = expr.dtype
        return lambda b: cast_column(c(b), dt)
    if isinstance(expr, ir.If):
        return _compile_case(((expr.cond, expr.then),), expr.otherwise, schema)
    if isinstance(expr, ir.CaseWhen):
        return _compile_case(expr.branches, expr.otherwise, schema)
    if isinstance(expr, ir.InList):
        return _compile_inlist(expr, schema)
    if isinstance(expr, ir.StringPredicate):
        c = compile_expr(expr.child, schema)
        fn = {"starts_with": S.starts_with, "ends_with": S.ends_with,
              "contains": S.contains}[expr.op]
        pat = expr.pattern

        def run_pred(b):
            col = c(b)
            return Column(BOOLEAN, fn(col.data, pat), col.validity)

        return run_pred
    if isinstance(expr, ir.Like):
        c = compile_expr(expr.child, schema)
        pat, esc = expr.pattern, expr.escape

        def run_like(b):
            col = c(b)
            return Column(BOOLEAN, S.like_match(col.data, pat, esc), col.validity)

        return run_like
    if isinstance(expr, ir.ScalarFn):
        from blaze_tpu.exprs.functions import compile_function

        return compile_function(expr, schema)
    if isinstance(expr, ir.MakeDecimal):
        c = compile_expr(expr.child, schema)
        dt = DataType(TypeKind.DECIMAL, precision=expr.precision, scale=expr.scale)
        if expr.precision > 18:  # every long fits
            return lambda b: Column(dt, c(b).data.astype(jnp.int64), c(b).validity)
        bound = np.int64(10 ** expr.precision)

        def run_make(b):
            # Spark, ANSI off: a long past the precision makes a null
            col = c(b)
            v = col.data.astype(jnp.int64)
            fits = (v > -bound) & (v < bound)
            return Column(dt, jnp.where(fits, v, 0),
                          _and_valid(col.validity, fits))

        return run_make
    if isinstance(expr, ir.UnscaledValue):
        c = compile_expr(expr.child, schema)
        return lambda b: Column(INT64, c(b).data.astype(jnp.int64), c(b).validity)
    if isinstance(expr, ir.CheckOverflow):
        c = compile_expr(expr.child, schema)
        p, s = expr.precision, expr.scale
        return lambda b: check_overflow(c(b), p, s)
    if isinstance(expr, ir.UdfWrapper):
        return _compile_udf_wrapper(expr, schema)
    if isinstance(expr, ir.ScalarSubquery):
        return _compile_scalar_subquery(expr)
    if isinstance(expr, ir.GetStructField):
        c = compile_expr(expr.child, schema)
        i = expr.index

        def run_gsf(b):
            col = c(b)
            child = col.data.children[i]
            v = _and_valid(col.validity, child.valid_mask()) \
                if (col.validity is not None or child.validity is not None) \
                else None
            return Column(child.dtype, child.data, v)

        return run_gsf
    if isinstance(expr, ir.GetIndexedField):
        return _compile_get_indexed(expr, schema)
    if isinstance(expr, ir.GetMapValue):
        return _compile_get_map_value(expr, schema)
    if isinstance(expr, ir.NamedStruct):
        val_fns = [compile_expr(v, schema) for v in expr.values]
        rt = expr.result_type

        def run_ns(b):
            from blaze_tpu.columnar.batch import StructData

            return Column(rt, StructData([fn(b) for fn in val_fns]), None)

        return run_ns
    raise NotImplementedError(f"cannot compile {type(expr).__name__}")


def _compile_get_indexed(expr: ir.GetIndexedField, schema) -> CompiledExpr:
    """spark GetArrayItem: 0-based element gather; negative or out-of-range
    index -> null (ref get_indexed_field.rs)."""
    c = compile_expr(expr.child, schema)
    # null index: i = -1 makes every row null while keeping the element
    # dtype (returning a null column of the INDEX dtype would corrupt the
    # output schema)
    i = -1 if expr.index.value is None else int(expr.index.value)

    def run(b: ColumnBatch) -> Column:
        col = c(b)
        ld = col.data
        lens = ld.lengths()
        ok = col.valid_mask() & (i >= 0) & (lens > i)
        src = jnp.clip(ld.offsets[:-1] + i, 0, ld.elements.capacity - 1)
        elem = ld.elements.take(jnp.where(ok, src, 0))
        return Column(elem.dtype, elem.data,
                      _and_valid(elem.validity, ok))

    return run


def _compile_get_map_value(expr: ir.GetMapValue, schema) -> CompiledExpr:
    """map[key]: match the literal key against each row's entries (stored as
    list<struct<key,value>>, types.storage_element) and gather the first
    match's value; absent -> null (ref get_map_value.rs)."""
    c = compile_expr(expr.child, schema)
    key_lit = expr.map_key

    def run(b: ColumnBatch) -> Column:
        import jax

        from blaze_tpu.ops.segment import element_rows

        mcol = c(b)
        ld = mcol.data
        entries = ld.elements.data  # StructData(key, value)
        kcol, vcol = entries.children
        ecap = kcol.capacity
        cap = mcol.capacity
        if key_lit.value is None:
            # map[NULL] is NULL for every row (spark strict-null lookup)
            return Column(vcol.dtype, vcol.take(jnp.zeros((cap,), jnp.int32)).data,
                          jnp.zeros((cap,), jnp.bool_))
        slot, row, _, in_row = element_rows(ld.offsets, cap, ecap)
        in_row = in_row & (slot >= ld.offsets[row])
        lit_col = _compile_literal(
            ir.Literal(key_lit.dtype, key_lit.value))
        # build a capacity-ecap batch to evaluate the literal against
        kmatch = _equal_values(kcol, lit_col, ecap)
        hit = in_row & kmatch & kcol.valid_mask()
        # first matching entry per row
        idx = jax.ops.segment_min(
            jnp.where(hit, slot, jnp.int32(ecap)),
            jnp.where(hit, row, jnp.int32(cap)), num_segments=cap)
        ok = (idx < ecap) & mcol.valid_mask()
        val = vcol.take(jnp.clip(idx, 0, ecap - 1))
        return Column(vcol.dtype, val.data, _and_valid(val.validity, ok))

    return run


def _equal_values(col: Column, lit_fn, cap: int):
    """Row-wise equality of a column against a literal value."""
    class _FakeBatch:
        capacity = cap

        def row_mask(self):
            return jnp.ones((cap,), jnp.bool_)

    lit_col = lit_fn(_FakeBatch())
    if col.is_string:
        return S.equals(col.data, lit_col.data)
    return col.data == lit_col.data


def _compile_udf_wrapper(expr: ir.UdfWrapper, schema) -> CompiledExpr:
    """Host-callback evaluation of an engine-external expression.

    Ref: SparkUDFWrapperExpr (spark_udf_wrapper.rs) — natively-computed
    param columns cross to the embedding layer, which evaluates the
    serialized expression row-by-row and returns the result array
    (SparkUDFWrapperContext.scala:63-111). The crossing here is
    jax.pure_callback, so the surrounding pipeline stays one jit program.
    The registered resource is `fn(*param_numpy_arrays, num_rows) ->
    (values ndarray, validity ndarray|None)`.
    """
    import jax

    from blaze_tpu.runtime import resources as _res

    param_fns = [compile_expr(p, schema) for p in expr.params]
    rt = expr.return_type
    if rt.is_string_like or rt.kind in (TypeKind.LIST, TypeKind.MAP,
                                        TypeKind.STRUCT):
        raise NotImplementedError(
            f"udf wrapper return type {rt} not yet supported")
    rid = expr.resource_id

    def run(b: ColumnBatch) -> Column:
        params = [fn(b) for fn in param_fns]
        host_args = []
        for p in params:
            if p.is_string:
                host_args += [p.data.bytes, p.data.lengths]
            else:
                host_args.append(p.data)
            host_args.append(p.valid_mask())
        host_args.append(b.num_rows)

        def callback(*arrs):
            fn = _res.get(rid)
            vals, validity = fn(*[np.asarray(a) for a in arrs])
            out_v = np.zeros((b.capacity,), rt.np_dtype())
            out_ok = np.zeros((b.capacity,), bool)
            n = min(len(vals), b.capacity)
            out_v[:n] = np.asarray(vals)[:n]
            out_ok[:n] = (np.ones(n, bool) if validity is None
                          else np.asarray(validity)[:n])
            return out_v, out_ok

        from blaze_tpu.exprs.hostfns import host_apply

        out_shape = (jax.ShapeDtypeStruct((b.capacity,), rt.np_dtype()),
                     jax.ShapeDtypeStruct((b.capacity,), np.bool_))
        vals, ok = host_apply(callback, out_shape, *host_args)
        validity = ok & b.row_mask() if expr.nullable else None
        return Column(rt, vals, validity)

    return run


def _compile_scalar_subquery(expr: ir.ScalarSubquery) -> CompiledExpr:
    """Ref: SparkScalarSubqueryWrapperExpr — the provider resource returns
    the (python) scalar on first evaluation; it becomes a literal column."""
    from blaze_tpu.runtime import resources as _res

    def run(b: ColumnBatch) -> Column:
        value = _res.get(expr.resource_id)()
        return _compile_literal(ir.Literal(expr.return_type, value))(b)

    return run


def _compile_literal(expr: ir.Literal) -> CompiledExpr:
    dt, v = expr.dtype, expr.value

    def run(b: ColumnBatch) -> Column:
        cap = b.capacity
        if v is None:
            from blaze_tpu.columnar.batch import _zero_column

            z = _zero_column(dt if not dt.is_string_like else dt, cap)
            return Column(dt, z.data, jnp.zeros((cap,), jnp.bool_))
        if dt.is_string_like:
            raw = v.encode() if isinstance(v, str) else bytes(v)
            return Column(dt, _const_string(raw, cap), None)
        if dt.kind == TypeKind.BOOLEAN:
            return Column(dt, jnp.full((cap,), bool(v)), None)
        if dt.wide_decimal:
            from blaze_tpu.columnar import int128 as i128
            from blaze_tpu.exprs import wide_decimal as W

            hi, lo = i128.np_from_ints([int(v)])
            return W.build(dt, jnp.full((cap,), hi[0], jnp.int64),
                           jnp.full((cap,), lo[0], jnp.int64), None)
        return Column(dt, jnp.full((cap,), v, dt.jnp_dtype()), None)

    return run


def _map_col(col: Column, dtype: DataType, fn) -> Column:
    return Column(dtype, fn(col.data), col.validity)


_CMP = {ir.BinOp.EQ, ir.BinOp.NEQ, ir.BinOp.LT, ir.BinOp.LE, ir.BinOp.GT,
        ir.BinOp.GE, ir.BinOp.EQ_NULLSAFE}


def _compile_binary(expr: ir.Binary, schema) -> CompiledExpr:
    lf = compile_expr(expr.left, schema)
    rf = compile_expr(expr.right, schema)
    op = expr.op

    if op in (ir.BinOp.AND, ir.BinOp.OR):
        return _compile_kleene(lf, rf, op)
    if op in _CMP:
        return lambda b: _compare(lf(b), rf(b), op)

    rt = expr.result_type

    def run(b: ColumnBatch) -> Column:
        lc, rc = lf(b), rf(b)
        return _arith(lc, rc, op, rt)

    return run


def _compare(lc: Column, rc: Column, op: ir.BinOp) -> Column:
    if lc.dtype.wide_decimal or rc.dtype.wide_decimal:
        from blaze_tpu.exprs import wide_decimal as W

        lt, eq, gt = W.compare(lc, rc)
    elif lc.is_string or rc.is_string:
        lt, eq = S.compare(lc.data, rc.data)
        gt = ~lt & ~eq
    elif lc.dtype.is_decimal or rc.dtype.is_decimal:
        lt, eq, gt = _compare_decimal(lc, rc)
    else:
        ld, rd = _promote(lc, rc)
        lt, eq, gt = ld < rd, ld == rd, ld > rd
    res = {
        ir.BinOp.EQ: eq, ir.BinOp.NEQ: ~eq, ir.BinOp.LT: lt,
        ir.BinOp.LE: lt | eq, ir.BinOp.GT: gt, ir.BinOp.GE: gt | eq,
        ir.BinOp.EQ_NULLSAFE: eq,
    }[op]
    lv, rv = lc.valid_mask(), rc.valid_mask()
    if op == ir.BinOp.EQ_NULLSAFE:
        both_null = ~lv & ~rv
        return Column(BOOLEAN, both_null | (lv & rv & res), None)
    return Column(BOOLEAN, res, _strict(lc, rc))


# decimal digits an integral type can hold (Spark's DecimalType.forType)
_INT_DIGITS = {TypeKind.BOOLEAN: 1, TypeKind.INT8: 3, TypeKind.INT16: 5,
               TypeKind.INT32: 10, TypeKind.INT64: 20}


def _compare_decimal(lc: Column, rc: Column):
    """(lt, eq, gt) of a compact decimal against a decimal of another scale,
    an integer (scale 0) or a float. An unscaled value means nothing without
    its scale: cents against units is wrong by 10^2. Both sides go to the
    larger scale, in int64 while the aligned values provably fit 18 digits,
    else on 128-bit limb planes; against a float both go to double, as
    Spark casts them."""
    lt_, rt_ = lc.dtype, rc.dtype
    if lt_.is_floating or rt_.is_floating:
        def as_double(c):
            if c.dtype.is_decimal:
                return c.data.astype(jnp.float64) / (10.0 ** c.dtype.scale)
            return c.data.astype(jnp.float64)

        ld, rd = as_double(lc), as_double(rc)
        return ld < rd, ld == rd, ld > rd
    scale = max(lt_.scale, rt_.scale)

    def digits(t):
        whole = (t.precision - t.scale if t.is_decimal
                 else _INT_DIGITS.get(t.kind))
        if whole is None:
            raise TypeError(f"cannot compare a decimal with {t}")
        return whole + scale

    if max(digits(lt_), digits(rt_)) > 18:
        from blaze_tpu.exprs import wide_decimal as W

        return W.compare(lc, rc)

    def aligned(c):
        v = c.data.astype(jnp.int64)
        up = scale - c.dtype.scale
        return v * np.int64(10 ** up) if up else v

    ld, rd = aligned(lc), aligned(rc)
    return ld < rd, ld == rd, ld > rd


def _strict(*cols: Column):
    v = None
    for c in cols:
        v = c.validity if v is None else (v if c.validity is None else (v & c.validity))
    return v


def _promote(lc: Column, rc: Column):
    ld, rd = lc.data, rc.data
    if ld.dtype != rd.dtype:
        target = jnp.promote_types(ld.dtype, rd.dtype)
        ld, rd = ld.astype(target), rd.astype(target)
    return ld, rd


def _compile_kleene(lf, rf, op) -> CompiledExpr:
    def run(b: ColumnBatch) -> Column:
        lc, rc = lf(b), rf(b)
        lv, rv = lc.valid_mask(), rc.valid_mask()
        ld = lc.data & lv if lc.validity is not None else lc.data
        rd = rc.data & rv if rc.validity is not None else rc.data
        lt, rt_ = ld.astype(jnp.bool_), rd.astype(jnp.bool_)
        if op == ir.BinOp.AND:
            val = lt & rt_
            # false & anything = false (valid); else null if either null
            valid = (lv & rv) | (lv & ~lt) | (rv & ~rt_)
        else:
            val = lt | rt_
            valid = (lv & rv) | (lv & lt) | (rv & rt_)
        if lc.validity is None and rc.validity is None:
            return Column(BOOLEAN, val, None)
        return Column(BOOLEAN, val & valid, valid)

    return run


def _arith(lc: Column, rc: Column, op: ir.BinOp, result_type: Optional[DataType]) -> Column:
    validity = _strict(lc, rc)
    if lc.dtype.is_decimal or rc.dtype.is_decimal:
        return _decimal_arith(lc, rc, op, result_type, validity)

    ld, rd = _promote(lc, rc)
    out_dt = result_type or (lc.dtype if lc.dtype.is_numeric else rc.dtype)
    if op == ir.BinOp.ADD:
        return Column(out_dt, ld + rd, validity)
    if op == ir.BinOp.SUB:
        return Column(out_dt, ld - rd, validity)
    if op == ir.BinOp.MUL:
        return Column(out_dt, ld * rd, validity)
    if op == ir.BinOp.DIV:
        if lc.dtype.is_integral and rc.dtype.is_integral:
            ld = ld.astype(jnp.float64)
            rd = rd.astype(jnp.float64)
            out_dt = result_type or FLOAT64
        zero = rd == 0
        res = ld / jnp.where(zero, 1, rd)
        return Column(out_dt, jnp.where(zero, 0, res), _and_valid(validity, ~zero))
    if op == ir.BinOp.MOD:
        zero = rd == 0
        safe = jnp.where(zero, 1, rd)
        # spark/java remainder: sign follows dividend
        res = ld - jnp.trunc(ld / safe) * safe if lc.dtype.is_floating else (
            jnp.sign(ld) * (jnp.abs(ld) % jnp.abs(safe)))
        return Column(out_dt, jnp.where(zero, 0, res), _and_valid(validity, ~zero))
    if op == ir.BinOp.BIT_AND:
        return Column(out_dt, ld & rd, validity)
    if op == ir.BinOp.BIT_OR:
        return Column(out_dt, ld | rd, validity)
    if op == ir.BinOp.BIT_XOR:
        return Column(out_dt, ld ^ rd, validity)
    if op == ir.BinOp.SHIFT_LEFT:
        return Column(out_dt, ld << rd, validity)
    if op == ir.BinOp.SHIFT_RIGHT:
        return Column(out_dt, ld >> rd, validity)
    raise NotImplementedError(f"arith op {op}")


def _decimal_arith(lc: Column, rc: Column, op: ir.BinOp,
                   result_type: Optional[DataType], validity) -> Column:
    """Unscaled int64 decimal arithmetic (ref NativeConverters.scala:599-676
    decimal special cases; plan supplies the result precision/scale)."""
    if (lc.dtype.wide_decimal or rc.dtype.wide_decimal
            or (result_type is not None and result_type.wide_decimal)):
        from blaze_tpu.exprs import wide_decimal as W

        if result_type is None or not result_type.is_decimal:
            raise NotImplementedError(
                "wide decimal arithmetic needs a planned result type")
        return W.arith(lc, rc, op, result_type, validity)
    ls = lc.dtype.scale if lc.dtype.is_decimal else 0
    rs = rc.dtype.scale if rc.dtype.is_decimal else 0
    ld = lc.data.astype(jnp.int64)
    rd = rc.data.astype(jnp.int64)
    if result_type is None or not result_type.is_decimal:
        # fall back to a plausible result type
        if op in (ir.BinOp.ADD, ir.BinOp.SUB):
            scale = max(ls, rs)
        elif op == ir.BinOp.MUL:
            scale = ls + rs
        else:
            scale = max(6, ls + rs + 1)
        prec = 18
        result_type = DataType(TypeKind.DECIMAL, precision=prec, scale=scale)
    out_s = result_type.scale
    if op in (ir.BinOp.ADD, ir.BinOp.SUB):
        lu = ld * (10 ** max(out_s - ls, 0))
        ru = rd * (10 ** max(out_s - rs, 0))
        res = lu + ru if op == ir.BinOp.ADD else lu - ru
        return Column(result_type, res, validity)
    if op == ir.BinOp.MUL:
        prod = ld * rd  # scale ls+rs
        ds = out_s - (ls + rs)
        if ds >= 0:
            return Column(result_type, prod * (10 ** ds), validity)
        div = 10 ** (-ds)
        q = jnp.abs(prod) // div
        r = jnp.abs(prod) % div
        q = q + (2 * r >= div)
        return Column(result_type, jnp.sign(prod) * q, validity)
    if op == ir.BinOp.DIV:
        zero = rd == 0
        safe = jnp.where(zero, 1, rd)
        # q = l / r scaled to out_s: (ld * 10^(out_s + rs - ls)) / rd, HALF_UP
        shift = out_s + rs - ls
        num = ld * (10 ** max(shift, 0))
        den = safe * (10 ** max(-shift, 0))
        q = jnp.abs(num) // jnp.abs(den)
        r = jnp.abs(num) % jnp.abs(den)
        q = q + (2 * r >= jnp.abs(den))
        res = jnp.sign(num) * jnp.sign(den) * q
        return Column(result_type, jnp.where(zero, 0, res), _and_valid(validity, ~zero))
    raise NotImplementedError(f"decimal op {op}")


def _compile_case(branches, otherwise, schema) -> CompiledExpr:
    conds = [compile_expr(c, schema) for c, _ in branches]
    vals = [compile_expr(v, schema) for _, v in branches]
    other = compile_expr(otherwise, schema) if otherwise is not None else None

    def run(b: ColumnBatch) -> Column:
        vcols = [f(b) for f in vals]
        ocol = other(b) if other is not None else None
        all_vals = vcols + ([ocol] if ocol is not None else [])
        out_dtype = all_vals[0].dtype

        is_str = all_vals[0].is_string
        if is_str:
            w = max(v.data.width for v in all_vals)
            all_vals = [Column(v.dtype, S.ensure_width(v.data, w), v.validity)
                        for v in all_vals]
            vcols = all_vals[: len(vcols)]
            ocol = all_vals[-1] if ocol is not None else None

        # start from else branch (or null), then apply branches so that
        # earlier (higher-priority) branches win via the `taken` mask
        if ocol is not None:
            acc_data, acc_valid = ocol.data, ocol.valid_mask()
        else:
            proto = all_vals[0]
            if is_str:
                acc_data = StringData(jnp.zeros_like(proto.data.bytes),
                                      jnp.zeros_like(proto.data.lengths))
            else:
                acc_data = jnp.zeros_like(proto.data)
            acc_valid = jnp.zeros((b.capacity,), jnp.bool_)
        taken = jnp.zeros((b.capacity,), jnp.bool_)
        for cf, vcol in zip(conds, vcols):
            ccol = cf(b)
            fire = ccol.data.astype(jnp.bool_) & ccol.valid_mask() & ~taken
            if is_str:
                acc_data = StringData(
                    jnp.where(fire[:, None], vcol.data.bytes, acc_data.bytes),
                    jnp.where(fire, vcol.data.lengths, acc_data.lengths))
            else:
                acc_data = jnp.where(fire, vcol.data, acc_data)
            acc_valid = jnp.where(fire, vcol.valid_mask(), acc_valid)
            taken = taken | fire
        return Column(out_dtype, acc_data, acc_valid)

    return run


def _compile_inlist(expr: ir.InList, schema) -> CompiledExpr:
    cf = compile_expr(expr.child, schema)
    lits = [compile_expr(v, schema) for v in expr.values]
    negated = expr.negated

    # Spark 3VL: `x IN (a, b, NULL)` is TRUE on a match, NULL when x is null
    # or the (unmatched) list contains a null, FALSE otherwise; NOT IN flips
    # the value and keeps nullness.
    has_null_lit = any(isinstance(v, ir.Literal) and v.value is None
                       for v in expr.values)

    def run(b: ColumnBatch) -> Column:
        ccol = cf(b)
        hit = jnp.zeros((b.capacity,), jnp.bool_)
        for lf in lits:
            lcol = lf(b)
            if ccol.is_string:
                eq = S.equals(ccol.data, lcol.data)
            else:
                ld, rd = _promote(ccol, lcol)
                eq = ld == rd
            hit = hit | (eq & lcol.valid_mask())
        res = ~hit if negated else hit
        if ccol.validity is None and not has_null_lit:
            return Column(BOOLEAN, res, None)
        valid = ccol.valid_mask()
        if has_null_lit:
            valid = valid & hit
        return Column(BOOLEAN, res, valid)

    return run
