"""Narrow (compressed) on-device value forms for the fused query path.

Reference role: the read hot path of the reference decompresses NibblePack/
delta-encoded chunks ON ACCESS (memory/.../format/NibblePack.scala:12-37,
format/vectors/DoubleVector.scala, doc/compression.md) — bytes-per-sample is
its main lever against memory bandwidth. The TPU analog here: a u16
quantized form of the f32 store (and the integer-delta forms below), built
in ONE device pass and decoded in VMEM inside the fused Pallas kernel,
halving the HBM bytes the north-star query streams.

Losslessness contract: per row, scale is the largest power of two with
(vmax - vmin) / scale < 65536; a row is marked ``ok`` only when EVERY valid
cell round-trips bit-exactly (min + q * scale == v in f32). Integer-valued
counters/gauges (the common Prometheus shape: request counts, bytes, 10ms
timings) qualify; arbitrary continuous floats do not and take the raw-f32
path — rows that fail are excluded from the narrow kernel (n forced to 0)
and folded in via the general kernels, exactly like minority grid cohorts.

The resident form is rebuilt at flush time (core/memstore.py
``_compress_resident_two_phase``): serving workloads flush every few seconds
but answer many queries per second, so one extra streaming pass per flush
buys half the bytes on every query between flushes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, donate_argnums=())
def build_narrow(val, n):
    """One streaming pass: (q i16[S,C], vmin f32[S], scale f32[S], ok bool[S]).

    scale is the SMALLEST power of two with (vmax - vmin) / scale <= 65535
    (maximal precision within the u16 range; power of two => exact f32
    multiplication); ok rows round-trip bit-exactly. Rows with < 1 valid
    sample are ok with scale 1 (all cells masked anyway)."""
    S, C = val.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (S, C), 1)
    valid = col < n[:, None]
    big = jnp.float32(3.4e38)
    v = val.astype(jnp.float32)
    vmin = jnp.min(jnp.where(valid, v, big), axis=1)
    vmax = jnp.max(jnp.where(valid, v, -big), axis=1)
    empty = ~valid[:, 0]
    vmin = jnp.where(empty, 0.0, vmin)
    vmax = jnp.where(empty, 0.0, vmax)
    span = vmax - vmin
    # smallest power-of-two scale with span/scale <= 65535:
    # scale = 2^ceil(log2(span/65535)); span 0 -> scale 1
    exp = jnp.ceil(jnp.log2(jnp.maximum(span, 1e-37) / 65535.0))
    scale = jnp.exp2(jnp.maximum(exp, -126.0)).astype(jnp.float32)
    scale = jnp.where(span > 0, scale, 1.0)
    d = v - vmin[:, None]
    q = jnp.clip(jnp.round(d / scale[:, None]), 0, 65535)
    recon = vmin[:, None] + q * scale[:, None]
    exact = jnp.where(valid, recon == v, True)
    ok = jnp.all(exact, axis=1)
    # stored biased as int16 (q - 32768): Mosaic casts i16->f32 directly and
    # fast, while u16 needs a slow i32 hop (measured 2.6x slower)
    return (q - 32768.0).astype(jnp.int16), vmin, scale, ok


# ---- histogram stores -------------------------------------------------------
#
# Device analog of the wire codec's 2D-delta (memory/hist.py, ref
# doc/compression.md "Histograms"): buckets are cumulative, so the bucket-axis
# delta d[s,c,:] is small and non-negative, and the time-axis delta of THOSE
# (dd) is near zero for quiet series. The resident form keeps dd as i8/i16
# [S, C, B] plus each row's first-frame bucket deltas f32 [S, B]; the f32
# block reconstructs as v = cumsum_b(first_d + cumsum_c dd). Every reduction
# over the time axis the grid kernels need commutes with the bucket cumsum,
# so queries can matmul the narrow dd block directly (ops/gridfns.py
# *_narrow) — the whole-store f32 temp never exists.
#
# Losslessness contract (same as the scalar form): a row is ``ok`` only when
# every valid cell round-trips bit-exactly in f32 — integer-valued bucket
# counts below 2^24 qualify; rows that don't keep raw f32 in the cohort pool.

@jax.jit
def build_narrow_hist(val, n):
    """One streaming pass over a [S, C, B] cumulative-bucket block:
    (dd i16[S, C, B], first_d f32[S, B], ok16 bool[S], ok8 bool[S],
    mono bool[S], exact bool[S]).

    ``mono``/``exact`` report the monotonicity and round-trip legs of the
    contract separately so a declining store can say WHY (counter resets
    vs non-integer data vs out-of-range deltas — the residency-fallback
    metric's reason tag). ``okN`` marks rows that BOTH round-trip
    bit-exactly, stay MONOTONE over
    time, and whose dd fits the N-bit signed range; the caller picks the
    narrowest dtype whose pool stays under the cohort gate. Monotonicity is
    part of the contract because the raw rate/increase kernels clamp negative
    per-step increments (counter-reset correction) — a nonlinear step the
    narrow kernels' telescoped matmuls cannot reproduce, so a row with a
    reset must take the cohort pool and the raw path. dd is zero at cell 0
    (the first frame lives in ``first_d``) and beyond each row's valid
    count, so decodes extend the last frame constantly — consumers mask by
    ``n`` exactly like the raw store's kernels do."""
    col = jax.lax.broadcasted_iota(jnp.int32, val.shape[:2], 1)
    valid = col < n[:, None]
    v = jnp.where(valid[:, :, None], val.astype(jnp.float32), 0.0)
    d = jnp.diff(v, axis=2, prepend=0.0)           # bucket deltas [S, C, B]
    first_d = d[:, 0, :]
    dd = jnp.diff(d, axis=1, prepend=0.0)          # 2D delta along time
    pair = (valid & (col > 0))[:, :, None]
    dd = jnp.where(pair, dd, 0.0)
    # the block is STORED as integers, so the contract is decided on those:
    # every dd and first-frame delta must be integer-valued and every value
    # within 2^23 (the bound the scalar delta form puts on its prefixes).
    # Then every partial sum of either cumsum is an exactly representable
    # integer, and the round trip below holds in ANY association order — the
    # kernels' band matmuls and another backend's cumsum included. (A round
    # trip of the unrounded f32 dd alone passes for fractional rows whenever
    # one backend's cumsum happens to undo its own diff, and the integer
    # cast then truncates them.)
    dd_q = jnp.round(dd)
    integral = (jnp.all(dd == dd_q, axis=1)
                & (first_d == jnp.round(first_d))
                & jnp.all(jnp.abs(v) <= 8388608.0, axis=1))        # [S, B]
    v_rec = jnp.cumsum(first_d[:, None, :] + jnp.cumsum(dd_q, axis=1), axis=2)
    exact = jnp.where(valid[:, :, None], v_rec == v, True)
    exact &= integral[:, None, :]
    dd = dd_q
    # counter-reset detection: any negative per-step bucket increment
    # (inc = cumsum_b dd) disqualifies the row — see contract above
    inc = jnp.cumsum(dd, axis=2)
    mono_row = jnp.all(jnp.all(jnp.where(pair, inc >= 0.0, True),
                               axis=2), axis=1)
    exact_row = jnp.all(jnp.all(exact, axis=2), axis=1)
    ok_rt = exact_row & mono_row
    fit16 = jnp.all(jnp.all((dd >= -32768.0) & (dd <= 32767.0), axis=2), axis=1)
    fit8 = jnp.all(jnp.all((dd >= -128.0) & (dd <= 127.0), axis=2), axis=1)
    return (dd.astype(jnp.int16), first_d, ok_rt & fit16, ok_rt & fit8,
            mono_row, exact_row)


@jax.jit
def cast_narrow_hist_i8(dd16):
    """i16 -> i8 narrowing for stores whose ok rows all fit 8 bits (pool rows
    may wrap — their dd is never read; decodes overlay the pool row-wise)."""
    return dd16.astype(jnp.int8)


# ---- scalar delta (counter/gauge) form --------------------------------------
#
# Device analog of the wire codec's delta-delta/NibblePack framing
# (memory/deltadelta.py, ref doc/compression.md): a monotone counter's raw
# values are huge (1e9-class) but its per-step increments are tiny, so the
# quantized form above fails its bit-exact contract — span/65535 rounds the
# low bits away. The delta form stores each row as a f32 ANCHOR (first valid
# value) plus i16/i8 per-step value deltas; the fused kernels reconstruct
# v = anchor + cumsum(dv) in VMEM per tile. Unlike the hist form there is NO
# monotonicity requirement: the decode is the full exact value sequence, so
# the rate kernels' counter-reset clamp applies to the same numbers it would
# see raw.

@functools.partial(jax.jit, donate_argnums=())
def build_narrow_delta(val, n):
    """One streaming pass: (dv i16[S,C], anchor f32[S], ok16, ok8, integral).

    anchor is each row's first valid value; dv[s,0] = 0 and dv is zero beyond
    the valid count, so ``anchor + cumsum(dv)`` extends the last frame
    constantly (consumers mask by ``n``). ``okN`` marks rows that round-trip
    bit-exactly through the f32 cumsum AND whose every prefix stays within
    2^23 of the anchor (so per-tile reassociation of the cumsum cannot change
    the result) AND whose deltas fit the N-bit signed range. ``integral``
    reports whether the row's deltas were integer-valued at all — callers use
    it to classify declines (non-integer data vs out-of-range)."""
    S, C = val.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (S, C), 1)
    valid = col < n[:, None]
    v = val.astype(jnp.float32)
    anchor = jnp.where(valid[:, 0], v[:, 0], 0.0)
    d = jnp.diff(v, axis=1, prepend=0.0)
    pair = valid & (col > 0)
    dvq = jnp.where(pair, jnp.round(d), 0.0)
    integral = jnp.all(jnp.where(pair, d == dvq, True), axis=1)
    # bit-exact round trip through the SAME reduction the kernels run
    prefix = jnp.cumsum(dvq, axis=1)
    recon = anchor[:, None] + prefix
    exact = jnp.where(valid, recon == v, True)
    # reassociation safety: tiles decode cumsum locally then offset by the
    # previous tile's total; every partial sum must be integer-exact in f32,
    # which |prefix| <= 2^23 guarantees for integer deltas
    bound = jnp.all(jnp.where(valid, jnp.abs(prefix) <= 8388608.0, True), axis=1)
    ok_rt = integral & jnp.all(exact, axis=1) & bound
    fit16 = jnp.all((dvq >= -32768.0) & (dvq <= 32767.0), axis=1)
    fit8 = jnp.all((dvq >= -128.0) & (dvq <= 127.0), axis=1)
    return dvq.astype(jnp.int16), anchor, ok_rt & fit16, ok_rt & fit8, integral


@functools.lru_cache(1)
def _cast_delta_i8_call():
    # donation declared only where XLA honors it (the CPU backend warns and
    # ignores it)
    donate = () if jax.default_backend() == "cpu" else (0,)
    return jax.jit(lambda dv16: dv16.astype(jnp.int8), donate_argnums=donate)


def cast_narrow_delta_i8(dv16):
    """i16 -> i8 narrowing when every ok row fits 8 bits; donates (frees) the
    i16 intermediate — flush-path encode never holds both widths."""
    return _cast_delta_i8_call()(dv16)
