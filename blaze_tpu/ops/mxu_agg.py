"""Dense-key grouped aggregation on the MXU (one-hot matmul accumulate).

The sort-based agg path (ops/agg.py) is general but leans on `lax.sort` and
scatters — both weak primitives on TPU (observed before this round: a
2M-row sort ~100ms, a 2M-row scatter ~250ms). When the grouping key is integral with a bounded range —
the common TPC-DS shape: surrogate keys like ss_item_sk — grouped sums and
counts become ONE-HOT MATMULS: decompose key k into (hi, lo) parts, then

    S[hi, lo] = sum_r v_r * onehot_hi(r) (x) onehot_lo(r)
              = A^T B  with  A = onehot_lo * v  (n x GL),  B = onehot_hi

which runs on the systolic array instead of the VPU's sort/scatter paths.

int8 engine (v2): values decompose into BALANCED base-256 digits
d_c in [-128, 127] (digits of v + bias, bias = 0x80 per byte, minus 128 —
signs fold into the digits, no separate sign plane), the one-hot sides are
int8, and the MXU runs s8 x s8 -> s32 at TWICE the bf16 rate (v5e: 394
TOPS vs 197 TFLOPS). int32 accumulation of 8-bit digits is EXACT for up to
2^23 rows per block (127 * 2^23 < 2^31), so a whole 2M-row batch
accumulates in ONE block — no (nblk, ...) partial carrier in HBM and no
32-way f64 recombination per batch (both were measured costs of the bf16
formulation). Digits recombine in f64: exact for int64 sums within 2^53
(descending-power partial coefficients stay < 2^53 when the total does),
and to 46 bits of the batch max for f64 sums — the same class as this
backend's emulated-f64 mantissa.

GL is 128: the digit-scaled side is the (n, P*GL) matrix, and keeping GL
at one lane-tile halves that carrier vs 256 while total matmul FLOPs
(2*n*R*P) are GL-invariant.

Non-finite float values cannot ride digit planes (digits of NaN/Inf are
garbage that would corrupt EVERY group's slot, not just their own): the
builders detect them per batch and report a `bad` flag so the caller falls
back to the general streaming path — same contract as the stage compiler's
out-of-range key flag.

Streaming use (the stage compiler's lax.scan over a stage's batches) rides
the split API — digitize() / accumulate() / finalize(): the scan carry
stays in RAW DIGIT-PLANE SPACE ((gh, P, GL) f64, one fused
multiply-accumulate per batch) and the 6-8-term digit recombination plus
per-aggregate carry updates run ONCE per stage instead of once per batch.
Float planes fold their per-batch scale 2^-s into the carry weight
(recombination is linear in the planes, so scaling commutes); int and
count planes carry weight 1 and stay exact (digit sums across 64 batches
of 2^23 rows stay under 2^38 << 2^53).

No reference analog: this is the TPU-first replacement for the hash-table
accumulate of agg_tables.rs:360-430 (SURVEY.md §7b).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from blaze_tpu.runtime import compile_service

Array = jax.Array

CHUNK_BITS = 8
I64_CHUNKS = 8          # full int64 (|v| < 2^62; sums exact within 2^53)
MAX_RANGE = 1 << 16
_GL = 128


def f64_chunks() -> int:
    """Float-sum digit plane count (conf.float_sum_digit_planes): 5 =
    38-bit digitization of the per-stage max (default), 6 = 46-bit (the
    emulated-f64 mantissa class). Clamped to 7 — the signed-int64 bias
    arithmetic of _float_words caps at 2^56-scale magnitudes (int sums
    use the exact uint64 8-chunk path separately). Callers must key
    compiled programs on this value — it is a trace-time static."""
    from blaze_tpu.config import conf

    return max(4, min(int(conf.float_sum_digit_planes), 7))


def _bias_f(nch: int) -> np.int64:
    """Balanced-digit bias for an nch-chunk float path: digits of
    (v + bias) are the balanced digits + 128."""
    return np.int64(128 * ((1 << (CHUNK_BITS * nch)) - 1) // 255)


_BIAS8 = np.uint64(128 * ((1 << 64) - 1) // 255)    # 8-chunk (i64 path)

# pallas fused path (TPU only): the XLA formulation materializes the
# (n, P*GL) digit-carrier and (n, gh) one-hot operands in HBM; the kernel
# builds both tiles in VMEM and leaves only the (gh, P*GL) s32 result.
_I32_EXACT_ROWS = 1 << 23   # 127 * 2^23 < 2^31: s32 block-exactness bound


_SCOPED_VMEM = 16 << 20     # Mosaic's default scoped-vmem stack, bytes
_MAX_PLANES = 64            # beyond this nothing was ever compiled


def _pick_tile(n: int, gh: int, pgl: int):
    """Largest T whose kernel fits the scoped-vmem stack, or None.

    Two resident terms: the s32 accumulator + output block
    (2*gh*pgl*4 bytes, independent of T) and the per-tile operands.
    Calibrated on a v5e with jax 0.9.0 / libtpu 0.0.34 (PR 21 chip runs)
    from the allocation sizes Mosaic reports when it refuses a kernel,
    n = 2^21: at gh = 512 the per-tile term measured 1290-1450 bytes per
    tile row (1536 bounds it: P <= 20 @ T=4096, 26 @ 2048, 29 @ 1024
    compile; 24 @ 4096, 27 @ 2048, 30 @ 1024 are refused), at gh = 256 it
    measured 860-975 (P = 52 @ 4096, 58 @ 2048, 61 @ 1024 refused; 40 @
    4096 compiles), at gh <= 64 everything up to P = 64 @ T=8192
    compiles. 2*gh + 512 bytes per tile row is the line through those
    two bounds (1536 at gh = 512, 1024 at gh = 256). Everything this
    admits must compile —
    chip_smoke.py compiles and checks the edges of this envelope and
    there is no fallback behind it, so re-calibrate here when a compiler
    update moves them. T = 4096 was the fastest tile where it fits, and
    T floors at 1024 (observed before this round).

    A double-buffered producer/consumer split (build tile i+1's operands
    while tile i's dot runs) was built and measured slower before this
    round: the extra scratch pushed T=4096 past the scoped-vmem limit,
    and at T=2048 the pipelined kernel ran 7.5ms vs the serial kernel's
    5.4ms per 2^21-row batch (P=7), which is ~91% of the s8 matmul
    roofline (4.9ms = 2*n*R*P / 394 TOPS)."""
    if pgl > _MAX_PLANES * _GL:
        return None
    acc2 = 2 * gh * pgl * 4
    for T in (4096, 2048, 1024):
        if n % T == 0 and acc2 + T * (2 * gh + 512) <= _SCOPED_VMEM:
            return T
    return None


def _use_pallas(n: int, gh: int, pgl: int) -> bool:
    import os

    if os.environ.get("BLAZE_TPU_NO_PALLAS"):
        return False
    if jax.default_backend() != "tpu":
        return False
    if n < 1024 or n > _I32_EXACT_ROWS:
        return False
    return _pick_tile(n, gh, pgl) is not None


def _pallas_accumulate(keys: Array, ok: Array, words, recipe,
                       gh: int) -> Array:
    """sum_r onehot_hi(r) (x) [onehot_lo(r) * digit_p(r)] over the whole
    batch, digits extracted IN VMEM from compact i32 word columns.

    A materialized (n, P) s8 digit matrix gets lane-padded to (n, 128) in
    HBM by XLA's layout rules (~19x the bytes; measured ~5ms/batch extra
    at 2M rows), so the kernel instead takes the (n,) i32 words the
    digits come from — the scaled 64-bit sum value as two halves, raw 0/1
    count columns — plus a STATIC recipe of (kind, word_idx, shift) per
    plane, and runs the shift/mask extraction on the VPU next to the MXU.

    keys (n,) int32 (pre-clipped to [0, rng)); ok (n,) int32 0/1 — rows
    with 0 contribute nothing; words: list of (n,) int32. Returns
    (gh, P*GL) int32 — exact (digit block sums < 2^31 for n <= 2^23,
    enforced by _use_pallas).

    Data layout: ALL row-wise inputs ride ONE (2+W, n) i32 matrix whose
    minor dim is n — fully lane-packed. Feeding (n, 1) columns instead
    makes XLA materialize each through a 128-lane-padded layout when the
    producer chain is nontrivial (~1 GB of HBM traffic per 2M-row word;
    measured 47ms/batch vs <5ms). The kernel math is correspondingly
    TRANSPOSED: one-hots build as (gh, T)/(GL, T) row-vector broadcasts
    and the dot contracts the trailing T dim."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = keys.shape[0]
    P = len(recipe)
    pgl = P * _GL
    T = _pick_tile(n, gh, pgl)
    W2 = 2 + len(words)

    m = jnp.stack([keys.astype(jnp.int32), ok.astype(jnp.int32)]
                  + [w.astype(jnp.int32) for w in words], axis=0)

    def kernel(m_ref, out_ref, acc_ref):
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            acc_ref[:] = jnp.zeros_like(acc_ref)

        # constants pinned to int32: under jax_enable_x64 a bare Python
        # int would promote to int64, which Mosaic cannot lower. Mosaic
        # also rejects i8 vector multiply (arith.muli on i8), so the
        # digit carrier is built by SELECT in i32 and cast to s8.
        one = jnp.int32(1)
        zero = jnp.int32(0)
        gl = jnp.int32(_GL)
        k = m_ref[0:1, :]                                      # (1, T)
        okc = m_ref[1:2, :] != zero                            # (1, T)
        oh_h = jnp.where(
            (k // gl == jax.lax.broadcasted_iota(jnp.int32, (gh, T), 0))
            & okc, one, zero).astype(jnp.int8)                 # (gh, T)
        kl = k % gl
        lo_hot = (kl == jax.lax.broadcasted_iota(jnp.int32, (_GL, T), 0)
                  ) & okc                                      # (GL, T)
        parts = []
        for kind, wi, sh in recipe:
            w = m_ref[2 + wi:3 + wi, :]                        # (1, T)
            if kind == "digit":
                # ((w >> sh) & 0xFF) - 128: bits sh..sh+7 regardless of
                # arithmetic-vs-logical shift (the mask keeps only them)
                d = ((w >> jnp.int32(sh)) & jnp.int32(0xFF)) - jnp.int32(128)
            else:  # "raw": already a small int (count 0/1)
                d = w
            # cast each plane to s8 immediately: holding all P i32
            # selects live until one concat+cast blows the 16M
            # scoped-vmem stack (measured 16.8M at P=7/T=2048)
            parts.append(jnp.where(lo_hot, d, zero).astype(jnp.int8))
        a = parts[0] if P == 1 else jnp.concatenate(parts, axis=0)
        # contract the row dim (trailing T on both sides)
        acc_ref[:] += jax.lax.dot_general(
            oh_h, a, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)

        @pl.when(i == n // T - 1)
        def _():
            out_ref[:] = acc_ref[:]

    # index maps stay int32 via numpy scalar constants (x64 mode would
    # promote Python-int arithmetic to an int64 Mosaic cannot return)
    return pl.pallas_call(
        kernel,
        grid=(n // T,),
        in_specs=[pl.BlockSpec((W2, T), lambda i: (np.int32(0), i),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((gh, pgl),
                               lambda i: (np.int32(0), np.int32(0)),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((gh, pgl), jnp.int32),
        scratch_shapes=[pltpu.VMEM((gh, pgl), jnp.int32)],
        name="mxu_agg_accumulate",
    )(m)


def _expand_words(words, recipe) -> Array:
    """Materialize the (n, P) s8 digit matrix from word columns (the
    portable path; the pallas kernel does this in VMEM instead)."""
    planes = []
    for kind, wi, sh in recipe:
        w = words[wi]
        if kind == "digit":
            d = ((w >> np.int32(sh)) & jnp.int32(0xFF)) - jnp.int32(128)
        else:
            d = w
        planes.append(d.astype(jnp.int8))
    return jnp.stack(planes, axis=1)


def _xla_accumulate(keys: Array, valid: Array, D: Array, gh: int) -> Array:
    """Portable s8 x s8 -> s32 formulation (CPU tests, odd shapes): the
    (n, P*GL) carrier materializes in HBM, XLA's tuned matmul does the
    rest. Returns (gh, P*GL) int32."""
    n, P = D.shape
    kh = (keys // _GL).astype(jnp.int32)
    kl = (keys % _GL).astype(jnp.int32)
    oh_l = kl[:, None] == jnp.arange(_GL, dtype=jnp.int32)[None, :]
    A = jnp.where(oh_l[:, None, :], D[:, :, None].astype(jnp.int32), 0
                  ).astype(jnp.int8).reshape(n, P * _GL)
    oh_h = ((kh[:, None] == jnp.arange(gh, dtype=jnp.int32)[None, :])
            & valid[:, None]).astype(jnp.int8)
    blk = min(n, _I32_EXACT_ROWS)
    nb = (n + blk - 1) // blk
    if n % blk:
        pad = nb * blk - n
        A = jnp.concatenate([A, jnp.zeros((pad, P * _GL), jnp.int8)])
        oh_h = jnp.concatenate([oh_h, jnp.zeros((pad, gh), jnp.int8)])
    part = jax.lax.dot_general(
        oh_h.reshape(nb, blk, gh), A.reshape(nb, blk, P * _GL),
        (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)       # (nb, gh, P*GL)
    return jnp.sum(part, axis=0) if nb > 1 else part[0]


def _accumulate_planes(keys: Array, valid: Array, words, recipe, gh: int,
                       rng: int) -> Array:
    """Shared dispatch: rows outside [0, rng) or invalid contribute
    nothing (both backends mask them out of the one-hots). Returns
    (gh, P, GL) int32 — exact per-batch plane sums."""
    n = keys.shape[0]
    P = len(recipe)
    ok = valid & (keys >= 0) & (keys < rng)
    kc = jnp.clip(keys, 0, rng - 1).astype(jnp.int32)
    pallas = _use_pallas(n, gh, P * _GL)
    # trace-time tally of which formulation this program got: a chip run
    # must be able to tell the kernel from its portable stand-in
    compile_service.note_agg_trace(pallas)
    if pallas:
        acc = _pallas_accumulate(kc, ok.astype(jnp.int32), words, recipe,
                                 gh)
    else:
        D = _expand_words(words, recipe)
        Dm = jnp.where(ok[:, None], D, jnp.int8(0))
        acc = _xla_accumulate(kc, ok, Dm, gh)
    return acc.reshape(gh, P, _GL)


def _float_words(v: Array, ok: Array, fixed_s=None):
    """Balanced base-256 digitization of round(v * 2^s), as i32 word
    columns + recipe entries (f64_chunks() planes — 5 by default, the
    conf.float_sum_digit_planes precision policy).

    s scales the batch max to 8*nch-2 bits: |scaled| stays inside the
    asymmetric balanced-digit range (-128*(2^(8nch)-1)/255 ..
    127*(2^(8nch)-1)/255). Returns (words, entries, s, bad) — bad is
    True when any contributing value is non-finite (digits would be
    garbage; caller must fall back).

    fixed_s: a STATIC scale chosen by the caller (the stage compiler
    probes a per-stage scale the way it probes key ranges, so every
    batch shares one scale and the scan carry stays in integer space —
    no per-batch emulated-f64 multiply-accumulate). bad then also trips
    when a value overflows the fixed scale's headroom, driving the
    caller's re-probe/fallback loop."""
    nch = f64_chunks()
    cap_bits = float(CHUNK_BITS * nch - 2)
    finite = jnp.isfinite(v)
    bad = jnp.any(ok & ~finite)
    v = jnp.where(ok & finite, v, 0.0).astype(jnp.float64)
    absv = jnp.abs(v)
    if fixed_s is None:
        maxv = jnp.max(absv)
        exp = jnp.floor(jnp.log2(jnp.maximum(maxv, 1e-300))) + 1.0
        # clamp so exp2(s) stays finite when the batch max is 0/denormal
        s = jnp.minimum(cap_bits - exp, 1000.0)
    else:
        s = jnp.asarray(fixed_s, jnp.float64)
        # overflow must be tested in the FLOAT domain, before the cast:
        # an out-of-range f64->i64 conversion saturates/wraps (x86
        # cvttsd2si yields int64_min for BOTH signs), and
        # |int64_min| is itself negative — a post-cast abs-compare
        # would stay silent exactly when the data overflowed
        bad = bad | jnp.any(ok & (absv > jnp.exp2(cap_bits - s)))
    scaled = jnp.round(v * jnp.exp2(s)).astype(jnp.int64)
    u = scaled + _bias_f(nch)
    # i32 halves: int64 shifts lower to 2x-i32 emulation on TPU, and the
    # pallas kernel wants lane-compact i32 columns anyway
    lo = (u & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32).view(jnp.int32)
    hi = (u >> 32).astype(jnp.int32)   # non-negative
    words = [lo, hi]
    entries = ([("digit", 0, sh) for sh in (0, 8, 16, 24)[:min(nch, 4)]]
               + [("digit", 1, sh) for sh in (0, 8, 16, 24)[:nch - 4]])
    return words, entries, s, bad


def _int_words(v: Array):
    """Balanced base-256 digitization of an int64, as i32 word columns +
    recipe entries (8 planes).

    Exact for |v| < 2^62 (the +bias add must not wrap uint64); grouped
    sums recombine exactly in f64 while they stay within 2^53 — the same
    contract as Spark's long sum overflow behavior being undefined."""
    u = v.astype(jnp.int64).astype(jnp.uint64) + _BIAS8
    lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32).view(jnp.int32)
    hi = (u >> np.uint64(32)).astype(jnp.uint32).view(jnp.int32)
    words = [lo, hi]
    entries = [("digit", 0, 0), ("digit", 0, 8), ("digit", 0, 16),
               ("digit", 0, 24), ("digit", 1, 0), ("digit", 1, 8),
               ("digit", 1, 16), ("digit", 1, 24)]
    return words, entries


def _recombine(acc_gpl: Array, start: int, nch: int) -> Array:
    """f64 digit recombination, descending power first (keeps partial
    coefficients < 2^53 whenever the total is — see module docstring)."""
    gh = acc_gpl.shape[0]
    total = jnp.zeros((gh, _GL), jnp.float64)
    for c in range(nch - 1, -1, -1):
        total = total + acc_gpl[:, start + c, :] * float(
            2 ** (CHUNK_BITS * c))
    return total


def grouped_sum(keys: Array, values: Array, valid: Array, rng: int) -> Array:
    """Per-key sums over keys in [0, rng). Returns values.dtype (rng,).

    f64: exact to 46 bits of the batch max magnitude (non-finite inputs
    are treated as 0 here — use grouped_multi's bad flag to detect them).
    int64: exact while the true sums stay within 2^53."""
    outs, _ = grouped_multi(keys, valid,
                            [("sum", values, jnp.ones_like(valid))], rng)
    return outs[0]


def grouped_count(keys: Array, valid: Array, rng: int) -> Array:
    """Per-key counts of valid rows (exact). int64 (rng,)."""
    outs, _ = grouped_multi(keys, jnp.ones_like(valid),
                            [("count", valid)], rng)
    return outs[0]


def digitize(valid: Array, specs, fixed_scales=None):
    """Digitize a batch's aggregate inputs into compact i32 word columns
    plus a static per-plane extraction recipe.

    Each spec is ("sum", values, value_valid) or ("count", count_valid).
    Returns (words, recipe, layout, weights, bad):
      * words — list of (n,) i32 columns (lane-compact; a materialized
        (n, P) s8 matrix would pad to 128 lanes in HBM)
      * recipe — per plane: ("digit", word_idx, shift) | ("raw", wi, 0)
      * layout — per spec: ("sumf"|"sumi"|"count", start_plane)
      * weights — per-plane carry weight: 2^-s for float-sum planes (the
        batch scale folds into the linear recombination), 1.0 otherwise.
        With fixed_scales the weights are all exactly 1.0 — callers may
        then carry raw integer plane sums and defer the 2^-s scaling to
        finalize (pass the scales there instead).
      * bad — True when any contributing float value was non-finite or
        overflowed a fixed scale (the caller must discard and fall back)

    fixed_scales: optional dict {spec_index: static scale} for float
    sums (see _float_words).
    """
    words = []
    recipe = []
    layout = []      # per spec: (kind, start)
    weights = []     # per plane
    bad = jnp.array(False)
    one = jnp.asarray(1.0, jnp.float64)
    for si, spec in enumerate(specs):
        if spec[0] == "count":
            _, cvalid = spec
            words.append(jnp.where(valid & cvalid, 1, 0).astype(jnp.int32))
            recipe.append(("raw", len(words) - 1, 0))
            weights.append(one)
            layout.append(("count", len(recipe) - 1))
            continue
        _, values, vvalid = spec
        ok = valid & vvalid
        start = len(recipe)
        if jnp.issubdtype(values.dtype, jnp.floating):
            fs = None if fixed_scales is None else fixed_scales.get(si)
            ws, entries, s, b = _float_words(values, ok, fixed_s=fs)
            bad = bad | b
            weights.extend([one if fs is not None else jnp.exp2(-s)]
                           * len(entries))
            layout.append(("sumf", start))
        else:
            # masked rows digitize as v=0, whose balanced digits are all
            # zero (the bias byte is exactly 0x80), so no re-mask needed
            v = jnp.where(ok, values, 0).astype(jnp.int64)
            ws, entries = _int_words(v)
            weights.extend([one] * len(entries))
            layout.append(("sumi", start))
        base = len(words)
        words.extend(ws)
        recipe.extend([(kind, base + wi, sh) for kind, wi, sh in entries])
    return words, tuple(recipe), layout, jnp.stack(weights), bad


def accumulate(keys: Array, valid: Array, words, recipe,
               rng: int) -> Array:
    """One batch's digit-plane accumulation: (gh, P, GL) f64."""
    gh = (rng + _GL - 1) // _GL
    return _accumulate_planes(keys, valid, words, recipe, gh,
                              rng).astype(jnp.float64)


def accumulate_raw(keys: Array, valid: Array, words, recipe,
                   rng: int) -> Array:
    """One batch's digit-plane accumulation as RAW (gh, P, GL) int32 —
    for callers carrying integer plane sums across batches (the stage
    compiler's fixed-scale scan: i64 carry adds are 2x-i32 and exact,
    vs the emulated-f64 multiply-accumulate a weighted carry needs)."""
    gh = (rng + _GL - 1) // _GL
    return _accumulate_planes(keys, valid, words, recipe, gh, rng)


def finalize(acc: Array, layout, rng: int, scales=None):
    """Recombine a (weighted-summed) plane carrier into per-spec outputs:
    f64 for float sums, int64 for int sums and counts.

    scales: optional dict {spec_index: static scale s} for fixed-scale
    float sums (digitize(..., fixed_scales=...)): the 2^-s deferred from
    the per-batch weights is applied here, once per stage.

    Int sums recombine in INT64 arithmetic: the f64 carrier holds exact
    per-plane digit sums (< 2^38 even across 64 maximal batches), but an
    f64 recombination would round — the TPU backend's emulated f64 has a
    ~49-bit effective mantissa, so plain double math goes off by ulps
    beyond 2^49. Int64 shifts/adds are 2x-i32 emulated but EXACT: int
    sums come out exact modulo 2^64 (Spark long-sum overflow wraps)."""
    gh = acc.shape[0]
    outs = []
    for si, (kind, start) in enumerate(layout):
        if kind == "count":
            plane = acc[:, start, :].reshape(gh * _GL)[:rng]
            outs.append(jnp.round(plane).astype(jnp.int64))
            continue
        if kind == "sumf":
            nch = f64_chunks()
            flat = _recombine(acc.astype(jnp.float64), start, nch
                              ).reshape(gh * _GL)[:rng]
            if scales is not None and si in scales:
                flat = flat * jnp.exp2(-jnp.asarray(scales[si],
                                                    jnp.float64))
            outs.append(flat)
            continue
        total = jnp.zeros((gh, _GL), jnp.int64)
        for c in range(I64_CHUNKS - 1, -1, -1):
            plane = jnp.round(acc[:, start + c, :]).astype(jnp.int64)
            total = total + (plane << np.int64(CHUNK_BITS * c))
        outs.append(total.reshape(gh * _GL)[:rng])
    return outs


def grouped_multi(keys: Array, valid: Array, specs, rng: int):
    """Compute several grouped aggregates in ONE s8 matmul.

    All digit planes of every spec stack along the matmul's N dimension,
    so the hi-side one-hot streams through the MXU once per batch instead
    of once per plane.

    Returns (outs, bad): outs aligned with specs (f64/int64 (rng,)
    arrays); bad True when any contributing float value was non-finite —
    those rows contributed 0, so the caller MUST discard and fall back.
    """
    words, recipe, layout, weights, bad = digitize(valid, specs)
    acc = accumulate(keys, valid, words, recipe, rng)
    acc = acc * weights[None, :, None]
    return finalize(acc, layout, rng), bad
