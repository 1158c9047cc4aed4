"""Whole-stage single-dispatch execution.

The streaming executor dispatches several jit calls per batch and reads
`num_rows` back per step. Every dependent dispatch-and-pull is a host
round trip during which the device idles, so a stage that does little
device work per batch spends its wall clock in dispatch. This module
compiles an ENTIRE stage — scan→filter→project→partial agg→final agg —
into ONE jit program that `lax.scan`s over the stage's batches stacked on
device, so a stage costs one dispatch + one result pull regardless of
batch count.

Applicability (checked by `_match`): a map-like chain over a uniform-shape
batch source, terminated by a partial(+final) AggExec whose grouping key is
a single integral column with a bounded value range and whose aggregates
are sum/count/avg. Grouped accumulation then rides the MXU as one-hot
matmuls (ops/mxu_agg.py) with a dense per-group state carry — no sort, no
scatter, no hash table. Range/null violations flip an in-program flag and
the caller falls back to the general streaming path (fallback-by-
construction, the same contract as the planner's tryConvert).

No reference analog: the reference's engine is host-resident (dispatch is
free); this is TPU-first design.
"""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from blaze_tpu.columnar import types as T
from blaze_tpu.columnar.batch import (
    Column, ColumnBatch, bucket_capacity, pull_array, pull_rows,
)
from blaze_tpu.columnar.types import TypeKind
from blaze_tpu.config import conf
from blaze_tpu.ops import mxu_agg
from blaze_tpu.ops.agg import (
    AggExec, AggMode, finalize_avg, finalize_sum, state_fields,
)
from blaze_tpu.ops.base import ExecContext, MapLikeOp, Operator
from blaze_tpu.runtime import compile_service, jit_cache, trace

_GROUP_KINDS = (TypeKind.INT8, TypeKind.INT16, TypeKind.INT32,
                TypeKind.INT64, TypeKind.DATE)
# plane fns ride MXU digit planes; mm/first fns ride dense segment
# scatter carriers (segment_min/max compile in <1s and run sub-ms at
# 2^21 rows x 2^16 groups — measured on v5e)
_PLANE_FNS = ("sum", "count", "avg")
_MM_FNS = ("min", "max")
_FIRST_FNS = ("first", "first_ignores_null")
_AGG_FNS = _PLANE_FNS + _MM_FNS + _FIRST_FNS
# scalar value kinds a dense min/max/first carrier can hold
_MM_VALUE_KINDS = (TypeKind.INT8, TypeKind.INT16, TypeKind.INT32,
                   TypeKind.INT64, TypeKind.DATE, TypeKind.TIMESTAMP,
                   TypeKind.DECIMAL, TypeKind.FLOAT32, TypeKind.FLOAT64)

# plan-shape -> last working dense range bucket (see try_run_stage)
_R_MEMO: dict = {}
_STATICS_MEMO: dict = {}
_stats_warned = False


def _warn_stats_once() -> None:
    """Per-batch stat metrics hook into the STREAMING path's
    count_stream; a whole-stage program has no per-batch stream by
    design. Called only when a stage ACTUALLY compiled (a warning on
    mere flag co-existence would be a false alarm for plans that never
    match the whole-stage pattern)."""
    global _stats_warned
    if conf.enable_input_batch_statistics and not _stats_warned:
        _stats_warned = True
        import logging

        logging.getLogger(__name__).warning(
            "enable_input_batch_statistics records nothing for "
            "whole-stage-compiled stages (single dispatch, no batch "
            "stream); disable the stage compiler to collect stats")


def _walk_chain(node: Operator):
    """Longest row-aligned map chain below `node` (filters fold as masks —
    only mask-producing/row-aligned ops may ride a compiled stage).
    Returns (chain top-down, source below it); chain may be empty."""
    from blaze_tpu.ops.basic import FilterExec, ProjectExec, RenameColumnsExec

    chain: List[MapLikeOp] = []
    n = node
    while isinstance(n, MapLikeOp):
        if not n.jit_safe() or not isinstance(
                n, (FilterExec, ProjectExec, RenameColumnsExec)):
            return None
        chain.append(n)
        n = n.child
    return list(reversed(chain)), n


def _build_steps(chain: List[MapLikeOp]):
    """("mask", predicate fns, label) | ("map", batch fn, label) per chain
    op; the label is the operator's name, for the program's named scopes."""
    from blaze_tpu.ops.basic import FilterExec

    steps = []
    for op in chain:
        if isinstance(op, FilterExec):
            steps.append(("mask", list(op._fns), op.label()))
        else:
            steps.append(("map", op.make_batch_fn(), op.label()))
    return steps


def _apply_steps(steps, b: ColumnBatch):
    """-> (batch, mask): run the chain with filters folded as a row mask
    over the (uncompacted) rows; one CSE scope per step."""
    from blaze_tpu.exprs.compiler import cse_scope

    mask = b.row_mask()
    for kind, fn, label in steps:
        with cse_scope(), jax.named_scope(label):
            if kind == "map":
                b = fn(b)
            else:
                for pf in fn:
                    c = pf(b)
                    mask = mask & c.data.astype(jnp.bool_) & c.valid_mask()
    return b, mask


def _match_chain(root: Operator):
    """Agg-less stage: a pure row-aligned map chain over a uniform source.
    Returns (chain top-down, source) or None."""
    m = _walk_chain(root)
    if m is None or not m[0]:
        return None
    return m


def _match(root: Operator):
    """(final, partial, chain(list, top-down), source) or None."""
    final = None
    node = root
    if isinstance(node, AggExec) and node.mode == AggMode.FINAL:
        final = node
        node = node.children[0]
    if not (isinstance(node, AggExec) and node.mode == AggMode.PARTIAL):
        return None
    partial = node
    # final=None is the shuffle-map-side shape: the stage emits the
    # partial's typed STATE columns (sum/nonempty, sum/count, count)
    # instead of finalized values
    if final is not None and (
            len(final.group_exprs) != len(partial.group_exprs)
            or [c.fn for c in final.aggs] != [c.fn for c in partial.aggs]):
        return None
    if not (1 <= len(partial.group_exprs) <= 4):
        return None  # composite keys pack into one dense range (below)
    for call in partial.aggs:
        if call.fn not in _AGG_FNS or len(call.inputs) != 1:
            return None
        if call.dtype.wide_decimal or (
                call.fn == "avg" and
                state_fields(call, 0)[0].dtype.wide_decimal):
            return None  # int128 limb planes keep the streaming path
        if call.fn in _MM_FNS + _FIRST_FNS:
            if call.dtype.kind not in _MM_VALUE_KINDS:
                return None  # strings keep the streaming path
    if not getattr(partial, "_work_jit", True):
        return None
    m = _walk_chain(partial.children[0])
    if m is None:
        return None
    chain, n = m
    return final, partial, chain, n


def try_run_stage(root: Operator, ctx: ExecContext, deferred: bool = False,
                  chain_ok: bool = True) -> Optional[ColumnBatch]:
    """Run the stage in one dispatch, or None if the pattern/shape/range
    doesn't apply (caller then uses the streaming executor).

    deferred=True (executor.collect_fetch): skip the in-function host pull
    of the oob/num_rows flags and return (batch, flags, retry,
    commit_metrics) instead — the flags ride the CALLER's single
    device→host fetch (optimistic execution; every dependent pull is a
    host round trip). `retry()` recomputes the
    stage through the full probe/fallback loop with the already-captured
    batches; callers MUST discard the batch and use retry()'s result when
    flags[0] != 0, and MUST call commit_metrics() only when the flags
    came back clean (a discarded stage never ran to completion)."""
    if not conf.enable_stage_compiler:
        return None
    if conf.fault_injection_spec:
        # whole-stage dispatch bypasses the streaming executor's per-op
        # boundaries — give chaos specs the same "op" point here
        from blaze_tpu.runtime import faults

        faults.inject("op." + type(root).__name__)
    compile_service.note_stage_attempt()
    trace.event("whole_stage_attempt", op_kind=type(root).__name__,
                fingerprint=_stage_fp(root))
    m = _match(root)
    if m is None:
        # chain_ok=False (the shuffle drivers): an agg-less chain stage
        # flatten-compacts the WHOLE stage into one batch — fine for a
        # collect (the result materializes anyway), but it would defeat
        # the writers' per-batch bounded staging/spill and the mesh
        # exchange's one-batch quota. Agg stages are safe either way
        # (output is bounded by the group count).
        if not chain_ok:
            return None
        mc = _match_chain(root)
        if mc is None:
            return None
        out = _run_chain_stage(root, mc[0], mc[1], ctx)
        if out is not None and deferred:
            return out, None, None, None
        return out
    final, partial, chain, source = m

    gdtypes = [f.dtype for f in partial._group_fields]
    if any(dt.kind not in _GROUP_KINDS for dt in gdtypes):
        return None

    batches = list(source.execute(ctx))
    # kill/heartbeat point: the whole-stage path has no per-batch drive
    # loop after capture, so check at the source-drain boundary
    ctx.check_running()
    if not batches:
        return None
    shape0 = batches[0].shape_key()
    if any(b.shape_key() != shape0 for b in batches[1:]):
        # source already drained: fall back WITH the captured batches
        return _fallback(root, batches, source, ctx)

    # canonical batch-count rung: pad the tuple with zero-row copies so
    # len(batches) — a static axis of every stage program key below —
    # collapses onto few rungs instead of one program per scan length
    batches = compile_service.pad_batch_list(tuple(batches), "stage_agg")
    max_R = int(conf.dense_agg_range)

    nkeys = len(partial.group_exprs)

    # trace-time statics shared by the probe and the main program —
    # memoized per (plan, shape): eval_shape re-traces the whole chain
    # per aggregate, which would otherwise run on EVERY stage dispatch
    # including the fully-cached steady state
    _input_fns0 = [fns[0] for fns in partial._input_fns]
    statics_key = ("stage_statics", root.plan_key(), shape0)
    statics = _STATICS_MEMO.get(statics_key)
    if statics is None:
        sum_is_float = []
        has_validity = []
        val_dtypes = []
        for i, call in enumerate(partial.aggs):
            shp = jax.eval_shape(
                lambda bb, i=i: _input_fns0[i](
                    _apply_steps(_build_steps(chain), bb)[0]), batches[0])
            has_validity.append(shp.validity is not None)
            sum_is_float.append(
                call.fn in ("sum", "avg")
                and jnp.issubdtype(shp.data.dtype, jnp.floating))
            val_dtypes.append(shp.data.dtype)
        statics = (tuple(sum_is_float), tuple(has_validity),
                   tuple(val_dtypes))
        _STATICS_MEMO[statics_key] = statics
    sum_is_float, has_validity, val_dtypes = statics
    float_calls = [i for i, f in enumerate(sum_is_float) if f]

    def make_probe():
        """Pass 1: per-key min/max + null check + per-float-agg abs-max
        (cheap, no matmuls). Its own dispatch so the accumulation
        program can be compiled for the SMALLEST dense range that fits
        the observed keys (composite keys pack into one index:
        k = sum_i (k_i - min_i) * stride_i) and for a FIXED float scale
        (so the scan carry stays integer — mxu_agg accumulate_raw)."""
        steps = _build_steps(chain)
        group_fns = list(partial._group_fns)
        input_fns = _input_fns0

        def run(*batches):
            # stacking INSIDE the program: eager jnp.stack per tree leaf
            # costs a dispatch each
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *batches)

            def min_step(carry, b):
                kmins, kmaxs, vmaxs, bad = carry
                b, mask = _apply_steps(steps, b)
                nmins, nmaxs = [], []
                for i, gfn in enumerate(group_fns):
                    g = gfn(b)
                    bad = bad | jnp.any(mask & ~g.valid_mask())
                    k = g.data.astype(jnp.int64)
                    ok = mask & g.valid_mask()
                    klo = jnp.where(ok, k, jnp.int64(2 ** 62))
                    khi = jnp.where(ok, k, jnp.int64(-2 ** 62))
                    nmins.append(jnp.minimum(kmins[i], jnp.min(klo)))
                    nmaxs.append(jnp.maximum(kmaxs[i], jnp.max(khi)))
                nvmaxs = []
                for j, ci in enumerate(float_calls):
                    vcol = input_fns[ci](b)
                    v = vcol.data.astype(jnp.float64)
                    ok = mask & vcol.valid_mask() & jnp.isfinite(v)
                    av = jnp.max(jnp.where(ok, jnp.abs(v), 0.0))
                    nvmaxs.append(jnp.maximum(vmaxs[j], av))
                return (nmins, nmaxs, nvmaxs, bad), None

            init = ([jnp.int64(2 ** 62)] * nkeys,
                    [jnp.int64(-2 ** 62)] * nkeys,
                    [jnp.float64(0.0)] * len(float_calls),
                    jnp.array(False))
            (kmins, kmaxs, vmaxs, bad), _ = jax.lax.scan(
                min_step, init, stacked)
            kmins = [jnp.where(m == 2 ** 62, 0, m) for m in kmins]
            kmaxs = [jnp.where(m == -2 ** 62, 0, m) for m in kmaxs]
            vm = (jnp.stack(vmaxs) if float_calls
                  else jnp.zeros((1,), jnp.float64))
            return jnp.stack(kmins), jnp.stack(kmaxs), vm, bad

        return run

    # (spans, kmins) are the data-dependent STATICS of the accumulation
    # program. Probe them once per plan shape and memoize; the steady
    # state is then a single dispatch with no in-program min pass — the
    # in-program oob flag catches data drifting outside the memoized
    # ranges (or going null), triggering a re-probe + recompile.
    memo_key = ("stage_R", root.plan_key(), shape0)

    def probe_spans():
        import math

        probe = jit_cache.get_or_compile(
            ("stage_probe", root.plan_key(), shape0, len(batches)),
            make_probe)
        kmins_v, kmaxs_v, vmaxs_v, bad_v = probe(*batches)
        if bool(pull_array(bad_v, "stage.probe")):
            return None  # null grouping keys: dense slots can't hold them
        # fixed float scales: 2 spare bits of headroom under the digit
        # capacity (8*planes-2) over the probed max, so values drifting
        # up to 4x on later data still digitize; beyond that the
        # in-program overflow flag re-probes
        cap_bits = 8.0 * mxu_agg.f64_chunks() - 4.0
        scales = []
        vmaxs_np = pull_array(vmaxs_v, "stage.probe")
        for j, ci in enumerate(float_calls):
            vmax = float(vmaxs_np[j])
            exp = (math.floor(math.log2(vmax)) + 1.0
                   if vmax > 0.0 else -996.0)
            scales.append((ci, min(cap_bits - exp, 1000.0)))
        spans, kmins = [], []
        for lo, hi in zip(pull_array(kmins_v, "stage.probe"),
                          pull_array(kmaxs_v, "stage.probe")):
            # power-of-two headroom per key: exact spans would invalidate
            # the memo on ANY later dataset with one new key value (the
            # padding only wastes dense slots; packing and unpacking use
            # the same spans so correctness is unaffected)
            span, bucket = max(int(hi) - int(lo) + 1, 1), 8
            while bucket < span:
                bucket <<= 1
            spans.append(bucket)
            kmins.append(int(lo))
        total = 1
        for sp in spans:
            total *= sp
        # keep the TOTAL dense range at >= 512 by widening the last span:
        # tiny observed ranges would otherwise memoize tiny buckets and pay
        # a wasted dispatch + re-probe + recompile every time later data
        # crosses a bucket (the old single-key floor)
        while total < 512:
            spans[-1] <<= 1
            total <<= 1
        if total > max_R:
            return None
        return tuple(spans), tuple(kmins), tuple(scales)

    def make():
        # filters fold into a row mask instead of compacting (see _match)
        steps = _build_steps(chain)
        group_fns = list(partial._group_fns)
        input_fns = [fns[0] for fns in partial._input_fns]
        calls = partial.aggs
        out_mode_final = final is not None

        def apply_chain(b: ColumnBatch):
            return _apply_steps(steps, b)

        # plane count of the scan's digit-space carrier (must be static
        # before the scan): presence + per-call validity-count planes +
        # per-PLANE-call sum digit planes (min/max/first carry dense
        # value arrays instead of digit planes). sum_is_float/
        # has_validity are the hoisted statics computed next to the probe.
        n_planes = 1
        for i, call in enumerate(calls):
            if has_validity[i]:
                n_planes += 1
            if call.fn in ("sum", "avg"):
                n_planes += (mxu_agg.f64_chunks() if sum_is_float[i]
                             else mxu_agg.I64_CHUNKS)

        # map the probed per-CALL fixed scales onto SPEC indices (the
        # spec list below is: presence, then per call [count?][sum?])
        call_scale = dict(scales)
        spec_fixed_scales = {}
        spec_idx = 1
        for i, call in enumerate(calls):
            if has_validity[i]:
                spec_idx += 1
            if call.fn in ("sum", "avg"):
                if sum_is_float[i] and i in call_scale:
                    spec_fixed_scales[spec_idx] = call_scale[i]
                spec_idx += 1

        # kmins are STATIC ints from the memoized probe: no in-program min
        # pass. int32 twins for the packed-index arithmetic (wrapping is
        # benign — see the packing comment in step()).
        kmins32 = [np.int64(m).astype(np.int32) for m in kmins]

        def run(*batches):
            with jax.named_scope("scan.stack"):
                stacked = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *batches)
            # single pass: dense MXU accumulation (oob set when the
            # memoized kmins/spans no longer cover the data, or keys go
            # null — either triggers re-probe + recompile in the caller).
            # The carry stays in digit-plane space — recombination and
            # per-aggregate updates run once per STAGE, not per batch
            # (mxu_agg module docstring, streaming use).
            # INTEGER carry: with the probed fixed float scales every
            # plane's weight is 1, so the per-batch update is an exact
            # i64 add (2x-i32) instead of an emulated-f64 FMA over the
            # whole carrier (~2-3 ms/batch measured at 2M rows); the
            # single f64 recombination happens in finalize. Plane sums
            # stay < 2^38 across any scan length the driver uses.
            gh = (R + mxu_agg._GL - 1) // mxu_agg._GL
            init = {
                "acc": jnp.zeros((gh, n_planes, mxu_agg._GL), jnp.int64),
                "oob": jnp.array(False),
            }
            # dense carriers for min/max/first (identity-initialized; the
            # count/presence planes decide which slots are real groups)
            for i, call in enumerate(calls):
                dt = val_dtypes[i]
                if call.fn in _MM_FNS:
                    if jnp.issubdtype(dt, jnp.floating):
                        sent = jnp.asarray(
                            jnp.inf if call.fn == "min" else -jnp.inf, dt)
                        init[f"nanflag{i}"] = jnp.zeros((R,), jnp.bool_)
                    else:
                        info = jnp.iinfo(dt)
                        sent = jnp.asarray(
                            info.max if call.fn == "min" else info.min, dt)
                    init[f"mm{i}"] = jnp.full((R,), sent, dt)
                elif call.fn in _FIRST_FNS:
                    init[f"fv{i}"] = jnp.zeros((R,), dt)
                    init[f"fok{i}"] = jnp.zeros((R,), jnp.bool_)
                    if call.fn == "first":
                        init[f"fvalid{i}"] = jnp.zeros((R,), jnp.bool_)
            # digitize()'s spec layout and the per-call slot map are
            # trace-time constants; capture them from the (single) trace
            # of step for use after the scan
            trace_info = {}

            def step(carry, b):
                b, live = apply_chain(b)
                # named scopes from here: agg.keys, agg.inputs,
                # agg.digitize, agg.accumulate (the chain's operators
                # name their own, _apply_steps)
                # composite keys pack into one dense index. Bounds are
                # checked exactly in int64, but the packed index itself is
                # computed in int32: in-range offsets (< span <= R <= 2^16)
                # are int32-exact, out-of-range rows are masked out of the
                # one-hot by `inb` so their wrapped value is irrelevant —
                # and an int64 producer chain feeding the pallas kernel's
                # key input materializes through a lane-padded layout that
                # costs ~30ms/batch (measured; see mxu_agg pallas notes)
                with jax.named_scope("agg.keys"):
                    packed = jnp.zeros((b.capacity,), jnp.int32)
                    inb = live
                    keys_valid = live
                    null_key = jnp.array(False)
                    for i, gfn in enumerate(group_fns):
                        g = gfn(b)
                        keys_valid = keys_valid & g.valid_mask()
                        null_key = null_key | jnp.any(live & ~g.valid_mask())
                        off64 = g.data.astype(jnp.int64) - kmins[i]
                        inb = inb & g.valid_mask() & (off64 >= 0) & \
                            (off64 < spans[i])
                        off32 = g.data.astype(jnp.int32) - kmins32[i]
                        packed = packed + jnp.clip(
                            off32, 0, spans[i] - 1) * jnp.int32(strides[i])
                    carry["oob"] = carry["oob"] | null_key | \
                        jnp.any(keys_valid & ~inb)
                    k = jnp.clip(packed, 0, R - 1)
                with jax.named_scope("agg.inputs"):
                    # every aggregate plane rides ONE matmul (mxu_agg
                    # .grouped_multi); non-nullable inputs reuse the presence
                    # plane for their counts (validity is a trace-time
                    # property, so this specializes per program)
                    specs = [("count", jnp.ones_like(inb))]
                    # per call: (sum_spec_idx|None, cnt_spec_idx|None)
                    slots = []
                    for i, call in enumerate(calls):
                        vcol = input_fns[i](b)
                        if vcol.validity is None:
                            ci = None  # reuse presence
                        else:
                            specs.append(("count", vcol.validity))
                            ci = len(specs) - 1
                        si = None
                        if call.fn in ("sum", "avg"):
                            data = vcol.data
                            if sum_is_float[i]:
                                data = data.astype(jnp.float64)
                            else:
                                data = data.astype(jnp.int64)
                            vv = (jnp.ones_like(inb) if vcol.validity is None
                                  else vcol.validity)
                            specs.append(("sum", data, vv))
                            si = len(specs) - 1
                        elif call.fn in _MM_FNS:
                            vv = inb & vcol.valid_mask()
                            v = vcol.data
                            red = (jax.ops.segment_min if call.fn == "min"
                                   else jax.ops.segment_max)
                            comb = (jnp.minimum if call.fn == "min"
                                    else jnp.maximum)
                            if jnp.issubdtype(v.dtype, jnp.floating):
                                # Spark NaN order: NaN is the GREATEST value
                                # (segment.seg_min/seg_max semantics)
                                nn = vv & ~jnp.isnan(v)
                                if call.fn == "min":
                                    sent = jnp.asarray(jnp.inf, v.dtype)
                                    vm = jnp.where(nn, v, sent)
                                    flag = nn  # any_nonnan
                                else:
                                    sent = jnp.asarray(-jnp.inf, v.dtype)
                                    vm = jnp.where(vv & ~jnp.isnan(v), v, sent)
                                    flag = vv & jnp.isnan(v)  # has_nan
                                carry[f"nanflag{i}"] = carry[f"nanflag{i}"] | (
                                    jax.ops.segment_max(
                                        flag.astype(jnp.int32), k,
                                        num_segments=R) > 0)
                            else:
                                info = jnp.iinfo(v.dtype)
                                sent = jnp.asarray(
                                    info.max if call.fn == "min" else info.min,
                                    v.dtype)
                                vm = jnp.where(vv, v, sent)
                            carry[f"mm{i}"] = comb(
                                carry[f"mm{i}"], red(vm, k, num_segments=R))
                        elif call.fn in _FIRST_FNS:
                            pres = (inb if call.fn == "first"
                                    else inb & vcol.valid_mask())
                            iota = jnp.arange(b.capacity, dtype=jnp.int32)
                            idx = jax.ops.segment_min(
                                jnp.where(pres, iota, jnp.int32(b.capacity)),
                                k, num_segments=R)
                            bhas = idx < b.capacity
                            gi = jnp.clip(idx, 0, b.capacity - 1)
                            bval = vcol.data[gi]
                            prev = carry[f"fok{i}"]
                            carry[f"fv{i}"] = jnp.where(
                                prev, carry[f"fv{i}"],
                                jnp.where(bhas, bval,
                                          jnp.zeros((), bval.dtype)))
                            if call.fn == "first":
                                bvalid = vcol.valid_mask()[gi] & bhas
                                carry[f"fvalid{i}"] = jnp.where(
                                    prev, carry[f"fvalid{i}"], bvalid)
                            carry[f"fok{i}"] = prev | bhas
                        slots.append((si, ci))
                with jax.named_scope("agg.digitize"):
                    words, recipe, layout, weights, bad_vals = \
                        mxu_agg.digitize(inb, specs,
                                         fixed_scales=spec_fixed_scales)
                # non-finite float inputs (or fixed-scale overflow when
                # data drifted past the probed magnitude) can't ride
                # digit planes — treat like out-of-range keys: flag and
                # let the caller re-probe / fall back
                carry["oob"] = carry["oob"] | bad_vals
                with jax.named_scope("agg.accumulate"):
                    acc_b = mxu_agg.accumulate_raw(k, inb, words, recipe,
                                                   R)
                    carry["acc"] = carry["acc"] + acc_b.astype(jnp.int64)
                trace_info["layout"] = layout
                trace_info["slots"] = slots
                return carry, None

            carry, _ = jax.lax.scan(step, init, stacked)

            # recombine ONCE per stage (2^-s applied here, not per
            # batch), then assemble output rows (dense slots ->
            # compacted groups)
            with jax.named_scope("agg.finalize"):
                outs = mxu_agg.finalize(carry["acc"], trace_info["layout"],
                                        R, scales=spec_fixed_scales)
            pres = outs[0]
            slots = trace_info["slots"]
            cap = bucket_capacity(R)
            present = pres > 0
            schema = (final or partial)._schema
            slot = jnp.arange(R, dtype=jnp.int64)
            cols = []
            for i, gdtype in enumerate(gdtypes):
                ki = (slot // strides[i]) % spans[i] + kmins[i]
                cols.append(Column(gdtype,
                                   _pad(ki.astype(gdtype.jnp_dtype()), cap),
                                   None))
            for i, call in enumerate(calls):
                si, ci = slots[i]
                cnt = pres if ci is None else outs[ci]
                if call.fn == "count":
                    # count's state IS its result (state_fields: [count])
                    cols.append(Column(T.INT64, _pad(cnt, cap), None))
                    continue
                if call.fn in _MM_FNS:
                    has = cnt > 0
                    val = carry[f"mm{i}"]
                    if jnp.issubdtype(val.dtype, jnp.floating):
                        nan = jnp.asarray(jnp.nan, val.dtype)
                        if call.fn == "min":
                            # NaN only when the group is valid-but-all-NaN
                            val = jnp.where(carry[f"nanflag{i}"], val,
                                            jnp.where(has, nan,
                                                      jnp.zeros((),
                                                                val.dtype)))
                        else:
                            val = jnp.where(carry[f"nanflag{i}"], nan, val)
                    val = jnp.where(has, val, jnp.zeros((), val.dtype))
                    if out_mode_final:
                        cols.append(Column(call.dtype, _pad(val, cap),
                                           _pad(has, cap)))
                    else:  # state: [val, has]
                        cols.append(Column(call.dtype, _pad(val, cap),
                                           None))
                        cols.append(Column(T.BOOLEAN, _pad(has, cap),
                                           None))
                    continue
                if call.fn in _FIRST_FNS:
                    fok = carry[f"fok{i}"]
                    val = jnp.where(fok, carry[f"fv{i}"],
                                    jnp.zeros((), carry[f"fv{i}"].dtype))
                    if call.fn == "first":
                        fvalid = carry[f"fvalid{i}"]
                        if out_mode_final:
                            cols.append(Column(call.dtype, _pad(val, cap),
                                               _pad(fvalid & fok, cap)))
                        else:  # state: [val, valid, has]
                            cols.append(Column(call.dtype, _pad(val, cap),
                                               None))
                            cols.append(Column(T.BOOLEAN,
                                               _pad(fvalid, cap), None))
                            cols.append(Column(T.BOOLEAN, _pad(fok, cap),
                                               None))
                    else:
                        if out_mode_final:
                            cols.append(Column(call.dtype, _pad(val, cap),
                                               _pad(fok, cap)))
                        else:  # state: [val, has]
                            cols.append(Column(call.dtype, _pad(val, cap),
                                               None))
                            cols.append(Column(T.BOOLEAN, _pad(fok, cap),
                                               None))
                    continue
                if out_mode_final:
                    # the streaming finalize's own functions: one
                    # semantics (decimal avg HALF_UP at the result scale,
                    # a sum past its precision null) on both paths
                    sd = state_fields(call, i)[0].dtype
                    state = Column(sd, outs[si].astype(sd.jnp_dtype()),
                                   None)
                    done = (finalize_avg(call, state, cnt)
                            if call.fn == "avg"
                            else finalize_sum(call, state, cnt > 0))
                    cols.append(Column(done.dtype, _pad(done.data, cap),
                                       _pad(done.validity, cap)))
                    continue
                # partial (shuffle map side): typed STATE columns in the
                # agg-buf layout the FINAL merge consumes by position
                # (state_fields: sum -> [sum, nonempty]; avg -> [sum,
                # count])
                sfields = state_fields(call, i)
                if call.fn == "avg":
                    sd = sfields[0].dtype
                    cols.append(Column(
                        sd, _pad(outs[si].astype(sd.jnp_dtype()), cap),
                        None))
                    cols.append(Column(T.INT64, _pad(cnt, cap), None))
                else:  # sum
                    sd = sfields[0].dtype
                    cols.append(Column(
                        sd, _pad(outs[si].astype(sd.jnp_dtype()), cap),
                        None))
                    cols.append(Column(T.BOOLEAN, _pad(cnt > 0, cap),
                                       None))
            with jax.named_scope("agg.compact_groups"):
                out = ColumnBatch(schema, cols, jnp.asarray(R, jnp.int32),
                                  cap)
                out = out.compact(_pad(present, cap))
            # oob + num_rows in ONE tiny array: each host pull is a round
            # trip
            flags = jnp.stack([carry["oob"].astype(jnp.int32),
                               out.num_rows.astype(jnp.int32)])
            return out, flags

        return run

    out = None
    nrows = 0
    for attempt in (0, 1):
        memo = _R_MEMO.get(memo_key)
        if memo is None:
            memo = probe_spans()
            if memo is None:  # null keys or range beyond max_R
                return _fallback(root, batches, source, ctx)
            _R_MEMO[memo_key] = memo
        spans, kmins, scales = memo
        R = 1
        for sp in spans:
            R *= sp
        strides = []
        acc = 1
        for sp in reversed(spans):
            strides.append(acc)
            acc *= sp
        strides = list(reversed(strides))
        # float_sum_digit_planes is a trace-time static of the program
        key = ("stage", root.plan_key(), shape0, len(batches),
               spans, kmins, scales, mxu_agg.f64_chunks())
        fn = jit_cache.get_or_compile(key, make)
        out, flags = fn(*batches)
        if deferred:
            def retry() -> ColumnBatch:
                # flags tripped at the caller: rebuild on the captured
                # batches and run the full (non-deferred) loop, which
                # re-probes the range memo and falls back as needed
                from blaze_tpu.ops.basic import MemorySourceExec

                _R_MEMO.pop(memo_key, None)
                src = MemorySourceExec(list(batches), source.schema)
                root2 = _rebuild(root, source, src)
                res = try_run_stage(root2, ctx)
                return res if res is not None else _collect_streaming(
                    root2, ctx)

            _warn_stats_once()

            def commit_metrics() -> None:
                # only once the caller saw clean flags — a discarded
                # stage must not report stage_compiled (and its retry
                # shares these MetricNode objects via _rebuild's copy)
                for op in filter(None, (final, partial, *chain)):
                    op.metrics.add("output_batches", 1)
                root.metrics.add("stage_compiled", 1)
                compile_service.note_stage_compiled()

            return out, flags, retry, commit_metrics
        flags_np = pull_array(flags, "stage.flags")
        nrows = int(flags_np[1])
        if not bool(flags_np[0]):
            break
        # data drifted past the memoized range: re-probe once with the
        # captured batches, then (attempt 2 failing means a race or null
        # keys) take the general path
        _R_MEMO.pop(memo_key, None)
        out = None
    if out is None:
        return _fallback(root, batches, source, ctx)
    _warn_stats_once()
    for op in filter(None, (final, partial, *chain)):
        op.metrics.add("output_batches", 1)
    root.metrics.add("output_rows", nrows)
    root.metrics.add("stage_compiled", 1)
    compile_service.note_stage_compiled()
    # observed groupby cardinality: the dense one-hot path knows the
    # exact group count in one number — the statistic the history feed
    # aggregates per fingerprint (dense vs fallback)
    _note_stage_stats(root, nrows, dense=True)
    return out


def _pad(a: jax.Array, cap: int) -> jax.Array:
    if a.shape[0] == cap:
        return a
    return jnp.concatenate(
        [a, jnp.zeros((cap - a.shape[0],), a.dtype)])


def _run_chain_stage(root: Operator, chain: List[MapLikeOp],
                     source: Operator, ctx: ExecContext
                     ) -> Optional[ColumnBatch]:
    """Agg-less scan→filter→project stage in one dispatch: the chain runs
    over the stacked batches with filters as masks, all surviving rows
    flatten-compact into ONE output batch. Output size is the stage's
    result size, which a collect materializes anyway."""
    if any(f.dtype.is_nested for f in root.schema.fields):
        return None  # flatten-compact over stacked list storage: not yet
        # (checked BEFORE draining the source — a post-drain None would
        # make the caller re-execute the whole scan)

    batches = tuple(source.execute(ctx))
    ctx.check_running()  # kill/heartbeat point (see try_run_stage)
    if not batches:
        return None
    shape0 = batches[0].shape_key()
    if any(b.shape_key() != shape0 for b in batches[1:]):
        return _fallback(root, list(batches), source, ctx)

    batches = compile_service.pad_batch_list(batches, "stage_chain")
    key = ("stage_chain", root.plan_key(), shape0, len(batches))

    def make():
        steps = _build_steps(chain)

        def run(*batches):
            with jax.named_scope("scan.stack"):
                stacked = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *batches)

            def step(_, b):
                b, mask = _apply_steps(steps, b)
                return None, (b, mask)

            _, (outs, masks) = jax.lax.scan(step, None, stacked)
            # flatten (NB, cap) -> (NB*cap) and compact the survivors
            flat_cols = jax.tree_util.tree_map(
                lambda x: x.reshape((-1,) + x.shape[2:]), outs.columns)
            nb, cap = masks.shape
            flat = ColumnBatch(root.schema, flat_cols,
                               jnp.asarray(nb * cap, jnp.int32), nb * cap)
            return flat.compact(masks.reshape(-1))

        return run

    fn = jit_cache.get_or_compile(key, make)
    out = fn(*batches)
    _warn_stats_once()
    for op in chain:
        op.metrics.add("output_batches", 1)
    rows = pull_rows(out, "stage.output_rows")
    root.metrics.add("output_rows", rows)
    root.metrics.add("stage_compiled", 1)
    compile_service.note_stage_compiled()
    # chain stages have no group key — record output cardinality only
    _note_stage_stats(root, None, dense=True, rows=rows)
    return out


def _stage_fp(root: Operator):
    """Operator fingerprint for whole-stage events/history taps; None
    when neither tracing nor the history store would record it."""
    if not (conf.trace_enabled or conf.history_dir):
        return None
    from blaze_tpu.runtime import history

    return history.op_fingerprint(root)


def _note_stage_stats(root: Operator, groups, dense: bool,
                      rows=None) -> None:
    """Feed the history taps for a whole-stage dispatch: the compiled
    path bypasses count_stream's per-batch row tap, so output rows and
    the dense-vs-fallback group cardinality are recorded here."""
    fp = _stage_fp(root)
    if fp is None:
        return
    trace.event("whole_stage_groups", op_kind=type(root).__name__,
                fingerprint=fp, groups=groups, dense=dense)
    if conf.history_dir:
        from blaze_tpu.runtime import history

        history.observe_groups(fp, type(root).__name__, groups, dense)
        n = groups if rows is None else rows
        if n is not None:
            history.observe_rows(root, int(n))


def _fallback(root, batches, source, ctx) -> ColumnBatch:
    from blaze_tpu.ops.basic import MemorySourceExec

    trace.event("whole_stage_fallback", op_kind=type(root).__name__,
                fingerprint=_stage_fp(root))
    if conf.history_dir:
        from blaze_tpu.runtime import history

        fp = _stage_fp(root)
        if fp is not None:
            history.observe_groups(fp, type(root).__name__, None,
                                   dense=False)
    src = MemorySourceExec(batches, source.schema)
    return _collect_streaming(_rebuild(root, source, src), ctx)


def _rebuild(root: Operator, source: Operator,
             new_source: Operator) -> Operator:
    """Clone the operator chain with THE stage-source node (identity
    match) swapped for a replayable source (oob fallback).

    Replacing every LEAF instead corrupts any stage whose source subtree
    has several leaves: an agg over a broadcast join would get its scan
    AND both broadcast readers replaced by the captured JOIN OUTPUT and
    re-join garbage (silently wrong counts — caught by the q5 validator
    cell when partial-only stages started exercising this path)."""
    import copy

    def clone(op: Operator) -> Operator:
        if op is source:
            return new_source
        c = copy.copy(op)
        c.children = [clone(ch) for ch in op.children]
        return c

    return clone(root)


def _collect_streaming(root: Operator, ctx: ExecContext) -> ColumnBatch:
    from blaze_tpu.ops.common import concat_batches

    batches = list(root.execute(ctx))
    if not batches:
        return ColumnBatch.empty(root.schema)
    if len(batches) == 1:
        return batches[0]
    return concat_batches(batches, root.schema)
