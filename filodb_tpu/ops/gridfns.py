"""Grid fast path: range functions as static band matmuls on the MXU.

Why: TPU microbenchmarks showed per-row binary search and data-dependent
[S, T] gathers 20-2000x slower than streaming compares and matmuls.
Prometheus-style series are scrape-interval regular, so the store tracks
a per-shard *grid* (base_ts, interval, uniform start): when every live series has
sample k at timestamp base + k*interval, window edges are closed-form grid
indices and window reductions become [S, C] x [C, T] matmuls with STATIC 0/1
band matrices — the MXU-shaped formulation:

  - count:            closed form from per-series sample count n
  - sum/avg:          val @ band
  - rate/increase/delta: per-cell increments inc[s,c] (elementwise; counter
    correction folds in as relu — a reset cell's corrected increment is 0), then
    window delta over (lo_t, hi_t] is ONE matmul inc @ band_open; first-sample
    values ride a static one-hot matmul
  - last_over_time/last_sample: static one-hot matmul + per-row tail value

Shards that drift off the grid (irregular intervals, mid-series gaps,
heterogeneous starts) fall back to the general path (ops/rangefns.py).
Mixed start cohorts are a known TODO: bucket rows by start cell and shift bands
per cohort. Semantics match the general kernels exactly on aligned data
(reference behavior: query/.../exec/rangefn/ + RateFunctions.scala).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GRID_FNS = {"rate", "increase", "delta", "sum_over_time", "count_over_time",
            "avg_over_time", "last_sample", "last_over_time"}


def grid_edges(out_ts: np.ndarray, window_ms: int, base_ts: int,
               interval_ms: int, spread: tuple[int, int] = (0, 0)):
    """Where a window lies in a row — THE one place that says so. Cell c of
    a row holds a stamp ``base_ts + c * interval_ms + d``; on a grid ``d``
    is 0 for every row, on a line store it is the row's start plus the
    cell's residual, somewhere in ``spread = (dmin, dmax)``. Returned are
    the cells that lie in [t - window, t] for EVERY such d: [lo_t, hi_t]
    inclusive (empty when hi < lo). With the default spread that is the
    exact closed form of the grid; with a wider one the cells just outside
    ([lo - k, lo) and (hi, hi + k], k = ceil of the spread in intervals)
    are in the window for some rows and not for others, and the caller
    decides them row by row from the true stamps."""
    dmin, dmax = spread
    num_lo = np.asarray(out_ts, np.int64) - window_ms - base_ts - dmin
    num_hi = np.asarray(out_ts, np.int64) - base_ts - dmax
    lo = -((-num_lo) // interval_ms)            # ceil, in integers
    hi = num_hi // interval_ms                  # floor
    return lo.astype(np.int64), hi.astype(np.int64)


def band_matrix(C: int, lo: np.ndarray, hi: np.ndarray, open_left: bool,
                dtype=np.float32) -> np.ndarray:
    """Static [C, T] 0/1 band: cell c contributes to step t iff
    lo_t < c <= hi_t (open_left) or lo_t <= c <= hi_t."""
    c = np.arange(C)[:, None]
    lo_ = lo[None, :] + (1 if open_left else 0)
    return ((c >= lo_) & (c <= hi[None, :])).astype(dtype)


def onehot_matrix(C: int, pos: np.ndarray, dtype=np.float32) -> np.ndarray:
    """[C, T] one-hot of clipped positions per step."""
    m = np.zeros((C, len(pos)), dtype)
    m[np.clip(pos, 0, C - 1), np.arange(len(pos))] = 1
    return m


def _plan(kernel: str, key: tuple, build):
    """Compiled program via the explicit plan cache (query/plancache.py):
    every grid entry point below keys on (fn, padded shape, dtype) — the
    variant kernels (hist / narrow) ARE the residency axis of the key."""
    from ..query.plancache import plan_cache
    return plan_cache.program(kernel, key, build)


def _grid_kernel(fn, val, n, band, band_open, onehot_lo, onehot_hi, lo, hi,
                 rel_out, window_ms, interval_ms, stale_ms, born=None):
    """val [S, C]: sample k of each series at column k == grid cell k.
    ``born [S]`` i32: the rows of a store in time-aligned cells that holds
    a row born late (core/chunkstore.py) — a row's samples are its cells
    ``born <= c < n``; None is the kernel as it ever was.

    All device-side time arithmetic is int32 *grid-relative* milliseconds
    (rel_out = out_ts - base_ts): no int64 emulation on TPU. The wrapper
    guarantees the relative range fits i32 (falls back to the general path
    otherwise).
    """
    S, C = val.shape
    acc = val.dtype
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < n[:, None]
    if born is not None:
        valid &= jnp.arange(C, dtype=jnp.int32)[None, :] >= born[:, None]
    v = jnp.where(valid, val, 0).astype(acc)

    last_cell = n[:, None] - 1                                    # [S, 1] i32
    f_idx = jnp.maximum(lo, 0)[None, :]                           # [1, T] i32
    if born is not None:
        f_idx = jnp.maximum(f_idx, born[:, None])                 # [S, T]
    l_idx = jnp.minimum(hi[None, :], last_cell)
    cnt = jnp.maximum(l_idx - f_idx + 1, 0)
    cnt_f = cnt.astype(acc)

    if fn == "count_over_time":
        return jnp.where(cnt >= 1, cnt_f, jnp.nan)

    if fn in ("sum_over_time", "avg_over_time"):
        s = v @ band                                              # MXU
        if fn == "avg_over_time":
            s = s / cnt_f
        return jnp.where(cnt >= 1, s, jnp.nan)

    if fn in ("last_sample", "last_over_time"):
        static_v = v @ onehot_hi                                  # value at cell hi_t
        row_last = jnp.take_along_axis(
            v, jnp.clip(last_cell, 0, C - 1), axis=1)             # [S, 1]
        l_v = jnp.where(hi[None, :] <= last_cell, static_v, row_last)
        ok = cnt >= 1
        if fn == "last_sample":
            l_rel = l_idx * interval_ms                           # i32 [S, T]
            ok = ok & ((rel_out[None, :] - l_rel) <= stale_ms)
        return jnp.where(ok, l_v, jnp.nan)

    if fn in ("rate", "increase", "delta"):
        is_counter = fn != "delta"
        prev = jnp.concatenate([v[:, :1], v[:, :-1]], axis=1)
        pair = valid & jnp.concatenate([jnp.zeros_like(valid[:, :1]), valid[:, :-1]], 1)
        raw_inc = jnp.where(pair, v - prev, 0.0)
        # counter: corrected increment = relu(diff); a reset cell contributes 0
        inc = jnp.maximum(raw_inc, 0.0) if is_counter else raw_inc
        delta = inc @ band_open                                   # MXU, (lo_t, hi_t]
        f_v = v @ onehot_lo                                       # raw first value
        if born is not None:        # born inside the window: its own first
            own = jnp.take_along_axis(
                v, jnp.clip(born[:, None], 0, C - 1), axis=1)
            f_v = jnp.where(born[:, None] > lo[None, :], own, f_v)
        f_rel = f_idx * interval_ms                               # [1, T] i32
        l_rel = l_idx * interval_ms                               # [S, T] i32
        win_start = rel_out[None, :] - window_ms
        win_end = rel_out[None, :]
        dur_start = (f_rel - win_start).astype(acc) / 1000.0
        dur_end = (win_end - l_rel).astype(acc) / 1000.0
        sampled = (l_rel - f_rel).astype(acc) / 1000.0
        avg_dur = sampled / (cnt_f - 1.0)
        if is_counter:
            dur_zero = jnp.where(delta > 0, sampled * (f_v / delta), jnp.inf)
            dur_start = jnp.where((delta > 0) & (f_v >= 0) & (dur_zero < dur_start),
                                  dur_zero, dur_start)
        thresh = avg_dur * 1.1
        extrap = sampled
        extrap = extrap + jnp.where(dur_start < thresh, dur_start, avg_dur / 2)
        extrap = extrap + jnp.where(dur_end < thresh, dur_end, avg_dur / 2)
        scaled = delta * (extrap / sampled)
        if fn == "rate":
            scaled = scaled * (1000.0 / window_ms.astype(acc))
        return jnp.where(cnt >= 2, scaled, jnp.nan)

    raise ValueError(fn)  # pragma: no cover


def grid_operands(C: int, out_ts: np.ndarray, window_ms: int, fn: str,
                  base_ts: int, interval_ms: int, dtype=np.float32):
    """Device-resident static operands for _grid_kernel (bands, one-hots,
    edges), cached per query shape: rebuilding AND re-uploading four [C, T]
    matrices per query is host work and host->device transfers that can
    exceed the device work itself (sub-millisecond for a histogram query).
    Same rationale as fusedgrid._device_operands."""
    key = np.ascontiguousarray(np.asarray(out_ts, np.int64)).tobytes()
    dtype = np.dtype(dtype)
    # bound retained HBM: four [C, T] matrices per entry x 32 entries; large
    # shapes (long dashboards on f64 stores) stay transient as before the
    # cache existed (fusedgrid's cache is bounded the same way by its shape
    # gates)
    if 4 * C * len(out_ts) * dtype.itemsize > 16 << 20:
        return _grid_operands_build(C, key, int(window_ms), int(base_ts),
                                    int(interval_ms), dtype.str)
    return _grid_operands_cached(C, key, int(window_ms), int(base_ts),
                                 int(interval_ms), dtype.str)


@functools.lru_cache(maxsize=32)
def _grid_operands_cached(C: int, out_ts_key: bytes, window_ms: int,
                          base_ts: int, interval_ms: int, dtype_str: str):
    return _grid_operands_build(C, out_ts_key, window_ms, base_ts,
                                interval_ms, dtype_str)


def _grid_operands_build(C: int, out_ts_key: bytes, window_ms: int,
                         base_ts: int, interval_ms: int, dtype_str: str):
    out_ts = np.frombuffer(out_ts_key, np.int64)
    dtype = np.dtype(dtype_str)
    lo, hi = grid_edges(out_ts, window_ms, base_ts, interval_ms)
    rel = out_ts - base_ts
    assert abs(rel).max() < 2**31 and window_ms < 2**31, "grid range exceeds i32"
    return dict(
        band=jnp.asarray(band_matrix(C, lo, hi, False, dtype)),
        band_open=jnp.asarray(band_matrix(C, lo, hi, True, dtype)),
        onehot_lo=jnp.asarray(onehot_matrix(C, np.maximum(lo, 0), dtype)),
        onehot_hi=jnp.asarray(onehot_matrix(C, hi, dtype)),
        lo=jnp.asarray(lo.astype(np.int32)), hi=jnp.asarray(hi.astype(np.int32)),
        rel_out=jnp.asarray(rel.astype(np.int32)),
        window_ms=jnp.int32(window_ms), interval_ms=jnp.int32(interval_ms),
    )


# ---- histograms -------------------------------------------------------------

HIST_GRID_FNS = {"rate", "increase", "delta", "sum_over_time", "last_sample",
                 "last_over_time"}


def _grid_hist_kernel(fn, val, n, band, band_open, onehot_lo, onehot_hi, lo, hi,
                      rel_out, window_ms, interval_ms, stale_ms):
    """Histogram variant: val [S, C, B] cumulative bucket counts; outputs
    [S, T, B]. Buckets share the series' sample times, so window edges and the
    extrapolation factor are computed once and broadcast over B; the per-bucket
    delta rides one einsum (ref: ChunkedRateFunction on HistogramVector —
    rate/increase apply per bucket)."""
    S, C, B = val.shape
    acc = val.dtype
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < n[:, None]
    v = jnp.where(valid[:, :, None], val, 0).astype(acc)

    last_cell = n[:, None] - 1
    f_idx = jnp.maximum(lo, 0)[None, :]
    l_idx = jnp.minimum(hi[None, :], last_cell)
    cnt = jnp.maximum(l_idx - f_idx + 1, 0)                       # [S, T]
    cnt_f = cnt.astype(acc)

    if fn == "sum_over_time":
        s = jnp.einsum("scb,ct->stb", v, band)
        return jnp.where((cnt >= 1)[:, :, None], s, jnp.nan)

    if fn in ("last_sample", "last_over_time"):
        static_v = jnp.einsum("scb,ct->stb", v, onehot_hi)
        row_last = jnp.take_along_axis(
            v, jnp.clip(last_cell, 0, C - 1)[:, :, None], axis=1)  # [S, 1, B]
        l_v = jnp.where((hi[None, :] <= last_cell)[:, :, None], static_v, row_last)
        ok = cnt >= 1
        if fn == "last_sample":
            l_rel = l_idx * interval_ms
            ok = ok & ((rel_out[None, :] - l_rel) <= stale_ms)
        return jnp.where(ok[:, :, None], l_v, jnp.nan)

    if fn in ("rate", "increase", "delta"):
        is_counter = fn != "delta"
        prev = jnp.concatenate([v[:, :1], v[:, :-1]], axis=1)
        pair = valid & jnp.concatenate([jnp.zeros_like(valid[:, :1]), valid[:, :-1]], 1)
        raw_inc = jnp.where(pair[:, :, None], v - prev, 0.0)
        inc = jnp.maximum(raw_inc, 0.0) if is_counter else raw_inc
        delta = jnp.einsum("scb,ct->stb", inc, band_open)          # [S, T, B]
        f_v = jnp.einsum("scb,ct->stb", v, onehot_lo)
        f_rel = f_idx * interval_ms
        l_rel = l_idx * interval_ms
        win_end = rel_out[None, :]
        dur_start = (f_rel - (win_end - window_ms)).astype(acc) / 1000.0   # [.., T]
        dur_end = (win_end - l_rel).astype(acc) / 1000.0
        sampled = (l_rel - f_rel).astype(acc) / 1000.0
        avg_dur = sampled / (cnt_f - 1.0)
        thresh = avg_dur * 1.1
        extrap = sampled
        extrap = extrap + jnp.where(dur_start < thresh, dur_start, avg_dur / 2)
        extrap = extrap + jnp.where(dur_end < thresh, dur_end, avg_dur / 2)
        factor = (extrap / sampled)[:, :, None]                    # [S, T, 1]
        if is_counter:
            dur_zero = jnp.where(delta > 0, sampled[:, :, None] * (f_v / delta), jnp.inf)
            # per-bucket zero clamp (matches per-bucket extrapolatedRate)
            ds = jnp.broadcast_to(dur_start[:, :, None], delta.shape)
            ds = jnp.where((delta > 0) & (f_v >= 0) & (dur_zero < ds), dur_zero, ds)
            extrap_b = sampled[:, :, None] + \
                jnp.where(ds < thresh[:, :, None], ds, avg_dur[:, :, None] / 2) + \
                jnp.where(dur_end[:, :, None] < thresh[:, :, None],
                          dur_end[:, :, None], avg_dur[:, :, None] / 2)
            factor = extrap_b / sampled[:, :, None]
        scaled = delta * factor
        if fn == "rate":
            scaled = scaled * (1000.0 / window_ms.astype(acc))
        return jnp.where((cnt >= 2)[:, :, None], scaled, jnp.nan)

    raise ValueError(fn)  # pragma: no cover


# ---- narrow (2D-delta resident) histograms ----------------------------------
#
# The hist-resident store keeps dd[s,c,b] = (bucket-delta of frame c) minus
# (bucket-delta of frame c-1) as i8/i16 plus first_d[s,b] f32 (ops/narrow.py
# build_narrow_hist). Every time-axis reduction the grid kernels need is
# LINEAR in the frames, so it commutes with the bucket cumsum:
#
#   inc[s,c,:]   = v[s,c,:] - v[s,c-1,:]        = cumsum_b dd[s,c,:]
#   window delta = einsum(inc, band)            = cumsum_b einsum(dd, band)
#   v_ext[s,c,:] = F[s,:] + sum_{c'<=c} inc     (F = cumsum_b first_d,
#                                                constant past the last frame)
#
# so the kernels below matmul the NARROW dd block and run one [S, T, B]
# bucket cumsum on the output — the whole-store f32 temp never exists, and
# results are bit-identical to the raw kernel on rows the encoder verified
# (integer components stay exact in f32 through both summation orders).

def grid_operands_hist_narrow(C: int, out_ts: np.ndarray, window_ms: int,
                              base_ts: int, interval_ms: int):
    """Static operands for the narrow hist kernel, cached per query shape
    (same rationale as :func:`grid_operands`): the open band for window
    deltas, prefix bands selecting v_ext at the lo/hi cells, the weighted
    band W[c, t] = #{window-t cells >= c} for sum_over_time, and the static
    (unmasked) per-step cell count."""
    key = np.ascontiguousarray(np.asarray(out_ts, np.int64)).tobytes()
    if 4 * C * len(out_ts) * 4 > 16 << 20:
        return _hist_narrow_operands_build(C, key, int(window_ms),
                                           int(base_ts), int(interval_ms))
    return _hist_narrow_operands_cached(C, key, int(window_ms), int(base_ts),
                                        int(interval_ms))


@functools.lru_cache(maxsize=32)
def _hist_narrow_operands_cached(C, out_ts_key, window_ms, base_ts, interval_ms):
    return _hist_narrow_operands_build(C, out_ts_key, window_ms, base_ts,
                                       interval_ms)


def _hist_narrow_operands_build(C, out_ts_key, window_ms, base_ts, interval_ms):
    out_ts = np.frombuffer(out_ts_key, np.int64)
    lo, hi = grid_edges(out_ts, window_ms, base_ts, interval_ms)
    rel = out_ts - base_ts
    assert abs(rel).max() < 2**31 and window_ms < 2**31, "grid range exceeds i32"
    T = len(out_ts)
    zeros = np.zeros(T, np.int64)
    l0 = np.maximum(lo, 0)
    h0 = np.minimum(hi, C - 1)
    # W[c, t] = #{cells in [l0_t, h0_t] >= c}; rows past h0 (and empty
    # windows) are 0. Cell 0's weight multiplies a zero dd frame — harmless.
    c = np.arange(C)[:, None]
    wband = np.maximum(h0[None, :] - np.maximum(c, l0[None, :]) + 1, 0) \
        .astype(np.float32)
    wband[:, h0 < l0] = 0.0
    return dict(
        band_open=jnp.asarray(band_matrix(C, lo, hi, True, np.float32)),
        prefix_lo=jnp.asarray(band_matrix(C, zeros,
                                          np.minimum(l0, C - 1), True,
                                          np.float32)),
        prefix_hi=jnp.asarray(band_matrix(C, zeros, np.clip(hi, 0, C - 1),
                                          True, np.float32)),
        wband=jnp.asarray(wband),
        cnt_static=jnp.asarray(np.maximum(h0 - l0 + 1, 0).astype(np.int32)),
        lo=jnp.asarray(lo.astype(np.int32)), hi=jnp.asarray(hi.astype(np.int32)),
        rel_out=jnp.asarray(rel.astype(np.int32)),
        window_ms=jnp.int32(window_ms), interval_ms=jnp.int32(interval_ms),
    )


def _grid_hist_kernel_narrow(fn, dd, first_d, n, band_open, prefix_lo,
                             prefix_hi, wband, cnt_static, lo, hi, rel_out,
                             window_ms, interval_ms, stale_ms):
    """Narrow variant of :func:`_grid_hist_kernel`: streams the i8/i16 dd
    block through the static matmuls and finishes with one bucket cumsum on
    the [S, T, B] output — numerics match the raw kernel bit-for-bit on rows
    the encoder verified (same masks, same extrapolation algebra)."""
    f32 = jnp.float32
    ddf = dd.astype(f32)
    F = jnp.cumsum(first_d, axis=1)                               # [S, B]
    last_cell = n[:, None] - 1
    f_idx = jnp.maximum(lo, 0)[None, :]
    l_idx = jnp.minimum(hi[None, :], last_cell)
    cnt = jnp.maximum(l_idx - f_idx + 1, 0)                       # [S, T]
    cnt_f = cnt.astype(f32)

    if fn == "sum_over_time":
        ext = jnp.cumsum(jnp.einsum("scb,ct->stb", ddf, wband), axis=2) \
            + cnt_static[None, :, None].astype(f32) * F[:, None, :]
        # v_ext extends the last frame past each row's valid count: subtract
        # the overhang cells' worth of it to match the raw masked sum
        v_last = F + jnp.cumsum(jnp.sum(ddf, axis=1), axis=1)     # [S, B]
        over = (cnt_static[None, :] - cnt).astype(f32)
        s = ext - over[:, :, None] * v_last[:, None, :]
        return jnp.where((cnt >= 1)[:, :, None], s, jnp.nan)

    if fn in ("last_sample", "last_over_time"):
        l_v = F[:, None, :] + jnp.cumsum(
            jnp.einsum("scb,ct->stb", ddf, prefix_hi), axis=2)
        # v_ext at cell clip(hi): v[hi] when hi is valid, the row's last
        # frame beyond it — exactly the raw kernel's static/row_last select
        ok = cnt >= 1
        if fn == "last_sample":
            l_rel = l_idx * interval_ms
            ok = ok & ((rel_out[None, :] - l_rel) <= stale_ms)
        return jnp.where(ok[:, :, None], l_v, jnp.nan)

    if fn in ("rate", "increase", "delta"):
        is_counter = fn != "delta"
        delta = jnp.cumsum(jnp.einsum("scb,ct->stb", ddf, band_open), axis=2)
        f_v = F[:, None, :] + jnp.cumsum(
            jnp.einsum("scb,ct->stb", ddf, prefix_lo), axis=2)
        f_rel = f_idx * interval_ms
        l_rel = l_idx * interval_ms
        win_end = rel_out[None, :]
        dur_start = (f_rel - (win_end - window_ms)).astype(f32) / 1000.0
        dur_end = (win_end - l_rel).astype(f32) / 1000.0
        sampled = (l_rel - f_rel).astype(f32) / 1000.0
        avg_dur = sampled / (cnt_f - 1.0)
        thresh = avg_dur * 1.1
        extrap = sampled
        extrap = extrap + jnp.where(dur_start < thresh, dur_start, avg_dur / 2)
        extrap = extrap + jnp.where(dur_end < thresh, dur_end, avg_dur / 2)
        factor = (extrap / sampled)[:, :, None]
        if is_counter:
            dur_zero = jnp.where(delta > 0,
                                 sampled[:, :, None] * (f_v / delta), jnp.inf)
            ds = jnp.broadcast_to(dur_start[:, :, None], delta.shape)
            ds = jnp.where((delta > 0) & (f_v >= 0) & (dur_zero < ds),
                           dur_zero, ds)
            extrap_b = sampled[:, :, None] + \
                jnp.where(ds < thresh[:, :, None], ds, avg_dur[:, :, None] / 2) + \
                jnp.where(dur_end[:, :, None] < thresh[:, :, None],
                          dur_end[:, :, None], avg_dur[:, :, None] / 2)
            factor = extrap_b / sampled[:, :, None]
        scaled = delta * factor
        if fn == "rate":
            scaled = scaled * (1000.0 / window_ms.astype(f32))
        return jnp.where((cnt >= 2)[:, :, None], scaled, jnp.nan)

    raise ValueError(fn)  # pragma: no cover


def periodic_samples_grid_hist_narrow(dd, first_d, n, out_ts: np.ndarray,
                                      window_ms: int, fn: str, base_ts: int,
                                      interval_ms: int,
                                      stale_ms: int = 300_000):
    """Narrow hist grid path: [S, T, B] output streamed off the dd block."""
    C = dd.shape[1]
    ops = grid_operands_hist_narrow(C, out_ts, window_ms, base_ts, interval_ms)
    k = _plan("grid-hist-narrow",
              (fn,) + tuple(dd.shape) + (len(out_ts), str(dd.dtype)),
              lambda: functools.partial(_grid_hist_kernel_narrow, fn))
    return k(dd, first_d, jnp.asarray(n, jnp.int32), ops["band_open"],
             ops["prefix_lo"], ops["prefix_hi"], ops["wband"],
             ops["cnt_static"], ops["lo"], ops["hi"], ops["rel_out"],
             ops["window_ms"], ops["interval_ms"],
             jnp.int32(min(stale_ms, 2**31 - 1)))


def _fused_hist_quantile_narrow_kernel(q, les, dd, first_d, n, gids, fn,
                                       num_groups, has_corr, corr_sum,
                                       corr_cnt, band_open, prefix_lo,
                                       prefix_hi, wband, cnt_static, lo, hi,
                                       rel_out, window_ms, interval_ms,
                                       stale_ms):
    """Narrow twin of :func:`_fused_hist_quantile_kernel`: per-bucket range
    function off the dd block + bucket-wise group sum + quantile, one device
    program. ``corr_sum``/``corr_cnt`` carry the cohort-pool rows' partial
    state (computed row-wise by the caller; those rows' gids are excluded
    here) — zero-shaped placeholders when ``has_corr`` is False."""
    from . import aggregators
    hist = _grid_hist_kernel_narrow(fn, dd, first_d, n, band_open, prefix_lo,
                                    prefix_hi, wband, cnt_static, lo, hi,
                                    rel_out, window_ms, interval_ms, stale_ms)
    S, T, B = hist.shape
    parts = aggregators.partial_aggregate("sum", hist.reshape(S, T * B),
                                          gids, num_groups)
    psum, pcnt = parts["sum"], parts["count"]
    if has_corr:
        psum = psum + corr_sum
        pcnt = pcnt + corr_cnt
    summed = jnp.where(pcnt == 0, jnp.nan, psum)
    return histogram_quantile(q, les, summed.reshape(num_groups, T, B))


def fused_hist_quantile_grid_narrow(q: float, les, dd, first_d, n, gids,
                                    num_groups: int, out_ts: np.ndarray,
                                    window_ms: int, fn: str, base_ts: int,
                                    interval_ms: int, stale_ms: int = 300_000,
                                    corr=None):
    """Entry for the fused narrow path (hist-resident stores): builds/caches
    the narrow operands and runs the one-program kernel; returns [G, T]."""
    C = dd.shape[1]
    ops = grid_operands_hist_narrow(C, out_ts, window_ms, base_ts, interval_ms)
    T = len(out_ts)
    B = dd.shape[2]
    if corr is None:
        z = jnp.zeros((num_groups, T * B), jnp.float32)
        corr_sum = corr_cnt = z
        has_corr = False
    else:
        corr_sum, corr_cnt = corr
        has_corr = True
    def build(fn=fn, num_groups=num_groups, has_corr=has_corr):
        def run(q, les, dd, first_d, n, gids, corr_sum, corr_cnt, *ops_t):
            return _fused_hist_quantile_narrow_kernel(
                q, les, dd, first_d, n, gids, fn, num_groups, has_corr,
                corr_sum, corr_cnt, *ops_t)
        return run

    k = _plan("fused-hist-narrow",
              (fn, num_groups, has_corr) + tuple(dd.shape)
              + (T, len(les), str(dd.dtype)), build)
    return k(
        jnp.float64(q), jnp.asarray(les), dd, first_d,
        jnp.asarray(n, jnp.int32), jnp.asarray(gids, jnp.int32),
        corr_sum, corr_cnt,
        ops["band_open"], ops["prefix_lo"], ops["prefix_hi"], ops["wband"],
        ops["cnt_static"], ops["lo"], ops["hi"], ops["rel_out"],
        ops["window_ms"], ops["interval_ms"],
        jnp.int32(min(stale_ms, 2**31 - 1)))


def _fused_hist_quantile_kernel(q, les, val, n, gids, fn, num_groups,
                                band, band_open, onehot_lo, onehot_hi, lo, hi,
                                rel_out, window_ms, interval_ms, stale_ms):
    """ONE device program for histogram_quantile(q, sum by(...) (fn(m[w])))
    on a grid-aligned histogram shard: per-bucket range function + bucket-wise
    group sum + Prometheus quantile, fetched with a single sync. Each stage
    dispatched separately costs a host->device submission round trip (and
    all dispatches serialize under the shard lock) —
    fusing them is the difference between 4 round trips per query and one
    (ref: HistogramQueryBenchmark.scala is the bar; the reference streams
    bucket rates through one iterator chain for the same reason)."""
    from . import aggregators
    hist = _grid_hist_kernel(fn, val, n, band, band_open, onehot_lo,
                             onehot_hi, lo, hi, rel_out, window_ms,
                             interval_ms, stale_ms)
    S, T, B = hist.shape
    parts = aggregators.partial_aggregate("sum", hist.reshape(S, T * B),
                                          gids, num_groups)
    summed = jnp.where(parts["count"] == 0, jnp.nan, parts["sum"])
    return histogram_quantile(q, les, summed.reshape(num_groups, T, B))


def fused_hist_quantile_grid(q: float, les, val, n, gids, num_groups: int,
                             out_ts: np.ndarray, window_ms: int, fn: str,
                             base_ts: int, interval_ms: int,
                             stale_ms: int = 300_000):
    """Entry for the fused path: builds/caches the grid operands and runs
    :func:`_fused_hist_quantile_kernel`; returns the [G, T] device array."""
    C = val.shape[1]
    dtype = np.float64 if val.dtype == jnp.float64 else np.float32
    ops = grid_operands(C, out_ts, window_ms, fn, base_ts, interval_ms, dtype)

    def build(fn=fn, num_groups=num_groups):
        def run(q, les, val, n, gids, *ops_t):
            return _fused_hist_quantile_kernel(q, les, val, n, gids, fn,
                                               num_groups, *ops_t)
        return run

    k = _plan("fused-hist",
              (fn, num_groups) + tuple(val.shape)
              + (len(out_ts), str(val.dtype)), build)
    return k(
        jnp.float64(q), jnp.asarray(les), val, jnp.asarray(n, jnp.int32),
        jnp.asarray(gids, jnp.int32),
        ops["band"], ops["band_open"], ops["onehot_lo"],
        ops["onehot_hi"], ops["lo"], ops["hi"], ops["rel_out"],
        ops["window_ms"], ops["interval_ms"],
        jnp.int32(min(stale_ms, 2**31 - 1)))


def periodic_samples_grid_hist(val, n, out_ts: np.ndarray, window_ms: int, fn: str,
                               base_ts: int, interval_ms: int,
                               stale_ms: int = 300_000):
    """Histogram grid path: [S, T, B] output."""
    C = val.shape[1]
    dtype = np.float64 if val.dtype == jnp.float64 else np.float32
    ops = grid_operands(C, out_ts, window_ms, fn, base_ts, interval_ms, dtype)
    k = _plan("grid-hist",
              (fn,) + tuple(val.shape) + (len(out_ts), str(val.dtype)),
              lambda: functools.partial(_grid_hist_kernel, fn))
    return k(val, jnp.asarray(n, jnp.int32), ops["band"],
             ops["band_open"], ops["onehot_lo"], ops["onehot_hi"],
             ops["lo"], ops["hi"], ops["rel_out"], ops["window_ms"],
             ops["interval_ms"], jnp.int32(min(stale_ms, 2**31 - 1)))


def _hist_quantile(q, les, counts, xp):
    """One shared body for the device (xp=jnp) and host (xp=np) entry points
    below: the classic-le and native-histogram paths answer identically by
    construction, not by keeping two copies in sync."""
    import contextlib
    guard = (np.errstate(invalid="ignore", divide="ignore")
             if xp is np else contextlib.nullcontext())
    B = les.shape[0]
    total = counts[..., -1]
    rank = q * total
    # first bucket with cumulative >= rank
    b = (counts < rank[..., None]).sum(axis=-1)
    b = xp.clip(b, 0, B - 1)
    lo_le = xp.where(b > 0, les[xp.maximum(b - 1, 0)], 0.0)
    hi_le = les[b]
    lo_cnt = xp.where(b > 0, xp.take_along_axis(
        counts, xp.maximum(b - 1, 0)[..., None], axis=-1)[..., 0], 0.0)
    hi_cnt = xp.take_along_axis(counts, b[..., None], axis=-1)[..., 0]
    with guard:
        frac = xp.where(hi_cnt > lo_cnt, (rank - lo_cnt) / (hi_cnt - lo_cnt), 1.0)
        res = lo_le + (hi_le - lo_le) * frac
    # +Inf top bucket: clamp to the highest finite bound
    res = xp.where(xp.isinf(hi_le),
                   xp.where(b > 0, les[xp.maximum(b - 1, 0)], xp.nan), res)
    res = xp.where((total > 0) & ~xp.isnan(total), res, xp.nan)
    res = xp.where(q < 0, -xp.inf, res)
    res = xp.where(q > 1, xp.inf, res)
    return res


@jax.jit
def histogram_quantile(q, les, counts):
    """Prometheus histogram_quantile, vectorized: les [B], counts [..., B]
    cumulative -> [...] (ref: Histogram.scala quantile :288; device mirror of
    memory/hist.py host reference)."""
    return _hist_quantile(q, les, counts, jnp)


def histogram_quantile_np(q, les, counts):
    """Host-numpy evaluation of the identical algebra — the classic
    le-labeled path (query/exec.py _classic_le_quantile) finishes tiny
    ragged per-group matrices here without a device round trip."""
    return _hist_quantile(q, les, counts, np)


def periodic_samples_grid(val, n, out_ts: np.ndarray, window_ms: int, fn: str,
                          base_ts: int, interval_ms: int, stale_ms: int = 300_000,
                          born=None):
    """Grid-path periodic samples over a uniform-start shard: [S, T] output.
    ``born``: the rows' birth cells where the store holds a row born late
    (``_grid_kernel``'s births mode, a program of its own)."""
    k = _plan("grid",
              (fn,) + tuple(val.shape) + (len(out_ts), str(val.dtype))
              + (() if born is None else ("births",)),
              lambda: functools.partial(_grid_kernel, fn))
    ops = grid_kernel_operands(val.shape[1], val.dtype, out_ts, window_ms,
                               fn, base_ts, interval_ms, stale_ms)
    if born is not None:
        ops += (jnp.asarray(born, jnp.int32),)
    return k(val, jnp.asarray(n, jnp.int32), *ops)


def grid_kernel_operands(C: int, val_dtype, out_ts: np.ndarray,
                         window_ms: int, fn: str, base_ts: int,
                         interval_ms: int, stale_ms: int) -> tuple:
    """``_grid_kernel``'s operands after ``(fn, val, n)``, in its order:
    the cached device arrays of ``grid_operands`` and the staleness bound
    as a HOST s32 (an argument of the call, no upload of its own)."""
    dtype = np.float64 if val_dtype == jnp.float64 else np.float32
    ops = grid_operands(C, out_ts, window_ms, fn, base_ts, interval_ms, dtype)
    return (ops["band"], ops["band_open"], ops["onehot_lo"],
            ops["onehot_hi"], ops["lo"], ops["hi"], ops["rel_out"],
            ops["window_ms"], ops["interval_ms"],
            np.int32(min(stale_ms, 2**31 - 1)))
