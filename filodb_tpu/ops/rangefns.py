"""Range functions (PeriodicSamplesMapper kernels): all series x all output steps
in one compiled program.

Reference semantics: query/.../exec/rangefn/RateFunctions.scala (Prometheus
extrapolatedRate, kept numerically consistent), AggrOverTimeFunctions.scala
(*_over_time incl. accurate stddev/stdvar), RangeFunction.scala:38-226 (chunked vs
sliding selection — here everything is one data-parallel path).

A window for output step t covers sample timestamps in (t - window, t] (left-open,
Prometheus range-vector semantics). Output is [P, T] float64 with NaN where the
function is undefined (missing samples); presenters drop NaN rows/steps.

Kernels are cached per (function, accum dtype); shapes recompile per (P, C, T)
bucket which the exec layer pads to stabilize.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import windows as W

NAN = jnp.nan

# functions needing counter-reset correction (ref: needsCounterCorrection)
COUNTER_FNS = {"rate", "increase", "irate"}

RANGE_FNS = [
    "rate", "increase", "delta", "irate", "idelta",
    "sum_over_time", "count_over_time", "avg_over_time", "min_over_time",
    "max_over_time", "stddev_over_time", "stdvar_over_time", "last_over_time",
    "changes", "resets", "deriv", "predict_linear", "quantile_over_time",
    "holt_winters", "last_sample",
]


def _extrapolated(out_ts, window_ms, first_t, first_v, last_t, last_v, cnt,
                  is_counter: bool, is_rate: bool, acc=jnp.float64):
    """Prometheus extrapolatedRate (ref RateFunctions.scala:37-80), vectorized.

    ``first_t``/``last_t`` are int64 epoch ms: all time arithmetic stays integer
    and only the (small) differences are cast to ``acc`` — mandatory for f32
    accumulation, where epoch-ms magnitudes lose whole-second precision.
    """
    win_start = out_ts[None, :] - window_ms
    win_end = out_ts[None, :]
    dur_start = (first_t - win_start).astype(acc) / 1000.0
    dur_end = (win_end - last_t).astype(acc) / 1000.0
    sampled = (last_t - first_t).astype(acc) / 1000.0
    avg_dur = sampled / (cnt - 1.0)
    delta = last_v - first_v
    if is_counter:
        dur_zero = jnp.where(delta > 0, sampled * (first_v / delta), jnp.inf)
        dur_start = jnp.where((delta > 0) & (first_v >= 0) & (dur_zero < dur_start),
                              dur_zero, dur_start)
    thresh = avg_dur * 1.1
    extrap = sampled
    extrap = extrap + jnp.where(dur_start < thresh, dur_start, avg_dur / 2)
    extrap = extrap + jnp.where(dur_end < thresh, dur_end, avg_dur / 2)
    scaled = delta * (extrap / sampled)
    if is_rate:
        scaled = scaled / ((win_end - win_start).astype(acc) / 1000.0)
    return jnp.where(cnt >= 2, scaled, NAN)


def _linreg_sums(ctx):
    """Window sums for linear regression over (t_rel_seconds, value)."""
    ts, valid, left, right = ctx["ts"], ctx["valid"], ctx["left"], ctx["right"]
    v = ctx["fval"]
    t_rel = jnp.where(valid, (ts - ctx["t0"]).astype(jnp.float64) / 1000.0, 0.0)
    p_t = W.prefix_sum(t_rel, valid)
    p_t2 = W.prefix_sum(t_rel * t_rel, valid)
    p_v = W.prefix_sum(v, valid)
    p_tv = W.prefix_sum(t_rel * v, valid)
    cnt = (right - left).astype(jnp.float64)
    s_t = W.window_sum(p_t, left, right)
    s_t2 = W.window_sum(p_t2, left, right)
    s_v = W.window_sum(p_v, left, right)
    s_tv = W.window_sum(p_tv, left, right)
    # slope/intercept of least squares fit v = a + b * t_rel
    denom = cnt * s_t2 - s_t * s_t
    slope = jnp.where(denom != 0, (cnt * s_tv - s_t * s_v) / denom, NAN)
    intercept = (s_v - slope * s_t) / cnt
    return cnt, slope, intercept


def _open_holes(fn, ts, val, n):
    """Rows of a line store that has HOLES (core/chunkstore.py, the text at
    ``RES_DTYPE``: a used cell without a sample, its stamp past TS_PAD) ->
    rows the kernels below can read. A hole is no sample, so a range
    function reads the rows with the holes taken out (``close_holes``).
    The instant selector reads them IN PLACE. A staleness marker is a
    sample without a value at the marker's OWN stamp (its value cell holds
    that stamp less the one in ``ts``): the newest row at or before a step
    being a marker, the step has no sample. A cell the row skipped is
    nothing: it repeats the cell before it, so the sample (or the marker)
    held there is served on, as far as the lookback says."""
    from ..core.chunkstore import HOLE_RUN_MAX, HOLE_SKIPPED, close_holes
    if fn not in ("last_sample", "last_sample_age"):
        return close_holes(ts, val, n)
    hole = ts > W.TS_PAD
    skipped = hole & (val == HOLE_SKIPPED)
    marker = hole & ~skipped
    ts = jnp.where(marker, ts - W.TS_PAD + val.astype(ts.dtype), ts)
    val = jnp.where(marker, jnp.nan, val)
    for _ in range(HOLE_RUN_MAX):       # a row never starts in a hole
        ts = jnp.where(skipped, jnp.roll(ts, 1, axis=1), ts)
        val = jnp.where(skipped, jnp.roll(val, 1, axis=1), val)
    return ts, val, n


def _periodic(fn, ts, val, n, out_ts, window_ms, arg0, arg1, w_cap, acc,
              holes=False):
    """Core dispatch; ``fn``, ``w_cap`` and ``holes`` are static."""
    if holes:
        ts, val, n = _open_holes(fn, ts, val, n)
    valid = W.valid_mask(ts, n)
    left, right = W.window_edges(ts, out_ts, window_ms)
    cnt_i = right - left
    cnt = cnt_i.astype(acc)
    fval = jnp.where(valid, val, 0).astype(acc)
    ctx = dict(ts=ts, val=val, fval=fval, valid=valid, left=left, right=right,
               t0=out_ts[0] - window_ms)

    def first_last(values):
        f_v = W.take(values, left)
        l_v = W.take(values, right - 1)
        f_t = W.take(ts, left)          # int64: cast only differences downstream
        l_t = W.take(ts, right - 1)
        return f_t, f_v, l_t, l_v

    if fn in ("rate", "increase", "delta"):
        is_counter = fn != "delta"
        if is_counter:
            # window-relative correction: first sample stays raw; the last sample
            # carries only the resets *inside* the window (corr[last] - corr[first])
            corrected = W.counter_correct(val, valid, dtype=acc)
            corr = corrected - fval
            f_v = W.take(fval, left)
            l_v = W.take(fval, right - 1) + (W.take(corr, right - 1) - W.take(corr, left))
            f_t = W.take(ts, left)
            l_t = W.take(ts, right - 1)
        else:
            f_t, f_v, l_t, l_v = first_last(fval)
        return _extrapolated(out_ts, window_ms, f_t, f_v, l_t, l_v, cnt,
                             is_counter, fn == "rate", acc)

    if fn in ("irate", "idelta"):
        i2 = right - 1
        i1 = right - 2
        v2 = W.take(fval, i2)
        v1 = W.take(fval, i1)
        dt = (W.take(ts, i2) - W.take(ts, i1)).astype(acc)
        if fn == "irate":
            dv = jnp.where(v2 >= v1, v2 - v1, v2)  # reset => counter restarted
            res = dv / (dt / 1000.0)
        else:
            res = v2 - v1
        return jnp.where(cnt_i >= 2, res, NAN)

    if fn == "sum_over_time":
        s = W.window_sum(W.prefix_sum(fval, valid, dtype=acc), left, right)
        return jnp.where(cnt_i >= 1, s, NAN)

    if fn == "count_over_time":
        return jnp.where(cnt_i >= 1, cnt, NAN)

    if fn == "avg_over_time":
        s = W.window_sum(W.prefix_sum(fval, valid, dtype=acc), left, right)
        return jnp.where(cnt_i >= 1, s / cnt, NAN)

    if fn in ("min_over_time", "max_over_time"):
        op = "min" if fn == "min_over_time" else "max"
        r = W.window_minmax(fval, valid, left, right, op)
        return jnp.where(cnt_i >= 1, r, NAN)

    if fn in ("stddev_over_time", "stdvar_over_time"):
        # center per series first: variance is shift-invariant and centering kills
        # the E[x^2]-E[x]^2 cancellation (near-constant windows come out exactly 0)
        nvalid = jnp.maximum(valid.sum(axis=1), 1)
        row_mean = (jnp.where(valid, fval, 0).sum(axis=1) / nvalid)[:, None]
        cv = jnp.where(valid, fval - row_mean, 0.0)
        s = W.window_sum(W.prefix_sum(cv, valid, dtype=acc), left, right)
        s2 = W.window_sum(W.prefix_sum(cv * cv, valid, dtype=acc), left, right)
        mean = s / cnt
        var = jnp.maximum(s2 / cnt - mean * mean, 0.0)
        var = jnp.where(cnt_i <= 1, 0.0, var)  # one sample: exactly zero spread
        r = var if fn == "stdvar_over_time" else jnp.sqrt(var)
        return jnp.where(cnt_i >= 1, r, NAN)

    if fn in ("last_over_time", "last_sample", "last_sample_age"):
        l_v = W.take(fval, right - 1)
        l_t = W.take(ts, right - 1)
        # last_sample additionally enforces staleness: arg0 = stale_ms
        if fn != "last_over_time":
            age = out_ts[None, :] - l_t
            if fn == "last_sample_age":
                # how long before the step the instant selector's sample
                # was stamped (ms; PromQL timestamp() is the step less
                # this): a difference of stamps, small, never a stamp
                l_v = jnp.where(jnp.isnan(l_v), NAN, age.astype(acc))
            return jnp.where((cnt_i >= 1) & (age <= arg0), l_v, NAN)
        return jnp.where(cnt_i >= 1, l_v, NAN)

    if fn in ("changes", "resets"):
        prev = jnp.concatenate([fval[:, :1], fval[:, :-1]], axis=1)
        pair_ok = valid & jnp.concatenate(
            [jnp.zeros_like(valid[:, :1]), valid[:, :-1]], axis=1)
        if fn == "changes":
            ind = pair_ok & (fval != prev)
        else:
            ind = pair_ok & (fval < prev)
        pfx = W.prefix_sum(ind.astype(acc), jnp.ones_like(valid), dtype=acc)
        c = W.take(pfx, right) - W.take(pfx, jnp.minimum(left + 1, right))
        return jnp.where(cnt_i >= 1, c, NAN)

    if fn == "deriv":
        cnt_r, slope, _ = _linreg_sums(ctx)
        return jnp.where(cnt_r >= 2, slope, NAN)

    if fn == "predict_linear":
        cnt_r, slope, intercept = _linreg_sums(ctx)
        # intercept is at t_rel = 0 (t0); predict at out_ts + arg0 seconds
        t_pred = (out_ts[None, :] - ctx["t0"]).astype(jnp.float64) / 1000.0 + arg0
        return jnp.where(cnt_r >= 2, intercept + slope * t_pred, NAN)

    if fn == "quantile_over_time":
        vals, mask = W.gather_windows(ts, fval, valid, left, right, w_cap)
        # NaN-fill then sort: NaNs sort to the end
        svals = jnp.sort(vals, axis=2)
        k = mask.sum(axis=2).astype(jnp.float64)
        rank = arg0 * (k - 1.0)
        lo = jnp.clip(jnp.floor(rank).astype(jnp.int32), 0, w_cap - 1)
        hi = jnp.clip(lo + 1, 0, w_cap - 1)
        frac = rank - lo
        v_lo = jnp.take_along_axis(svals, lo[:, :, None], axis=2)[:, :, 0]
        v_hi = jnp.take_along_axis(svals, hi[:, :, None], axis=2)[:, :, 0]
        v_hi = jnp.where(hi[:, :].astype(jnp.float64) > (k - 1), v_lo, v_hi)
        r = v_lo + (v_hi - v_lo) * frac
        return jnp.where(cnt_i >= 1, r, NAN)

    if fn == "holt_winters":
        # double exponential smoothing (ref HoltWinters in RangeFunction.scala;
        # Prometheus holt_winters): level/trend scan over the window samples
        vals, mask = W.gather_windows(ts, fval, valid, left, right, w_cap, fill=0.0)
        sf, tf = arg0, arg1
        v0 = vals[:, :, 0]
        v1 = jnp.where(mask[:, :, 1], vals[:, :, 1], v0)

        def body(carry, xm):
            s, b = carry
            x, m = xm
            s_new = sf * x + (1 - sf) * (s + b)
            b_new = tf * (s_new - s) + (1 - tf) * b
            s2 = jnp.where(m, s_new, s)
            b2 = jnp.where(m, b_new, b)
            return (s2, b2), None

        # Prometheus: s = x0, b = x1 - x0, then smooth over samples 1..n-1
        init = (v0, v1 - v0)
        xs = (jnp.moveaxis(vals[:, :, 1:], 2, 0), jnp.moveaxis(mask[:, :, 1:], 2, 0))
        (s_fin, _), _ = jax.lax.scan(body, init, xs)
        return jnp.where(cnt_i >= 2, s_fin, NAN)

    raise ValueError(f"unknown range function {fn}")  # pragma: no cover


def _kernel(fn: str, w_cap: int, acc_name: str, shape_key: tuple,
            holes: bool = False):
    """The per-shape compiled program via the explicit plan cache (query/
    plancache.py): the key carries the padded row/step buckets the exec
    layer already stabilizes, so repeated dashboard shapes hit a cached
    executable and the cache's capacity bound actually bounds retained
    programs (functools.cache + jax's internal cache bounded neither)."""
    from ..query.plancache import plan_cache
    return plan_cache.program(
        "periodic",
        (fn, w_cap, acc_name) + shape_key + (("holes",) if holes else ()),
        lambda: periodic_body(fn, w_cap, acc_name, holes))


def periodic_body(fn: str, w_cap: int = 256, accum: str = "float64",
                  holes: bool = False):
    """``_periodic`` with its statics bound: ``(ts, val, n, *operands)`` ->
    ``[P, T]``, the traceable body of the ``periodic`` program and of a
    gathered leaf's one program (query/exec.py ``_leaf_body``), which
    composes it after its gather."""
    return functools.partial(_periodic, fn, w_cap=w_cap,
                             acc=jnp.dtype(accum), holes=holes)


def periodic_operands(out_ts, window_ms, arg0: float = 0.0,
                      arg1: float = 0.0) -> tuple:
    """What the host knows of a ``periodic_body`` call, as HOST values of
    the types the program was traced with (s64 steps, s64 window, two
    f64): arguments of the one call, not eager uploads before it — each of
    those is a dispatch, and a leaf dispatches under its shard's lock."""
    return (np.asarray(out_ts, np.int64), np.int64(window_ms),
            np.float64(arg0), np.float64(arg1))


HIST_FNS = {"rate", "increase", "delta", "sum_over_time", "last_sample",
            "last_over_time"}


def periodic_samples_hist(ts, val, n, out_ts, window_ms, fn: str,
                          arg0: float = 0.0, w_cap: int = 256,
                          accum: str = "float64"):
    """General (off-grid) histogram range functions: val [S, C, B] cumulative
    bucket counts -> [S, T, B], any timestamp layout.

    Buckets share their series' timestamps, so the scalar kernel is vmapped
    over the bucket axis — the searchsorted window edges depend only on the
    (unbatched) timestamps and are computed once, while per-bucket counter
    correction and extrapolation batch across B (ref: HistogramVector read
    through chunked range functions, RateFunctions.scala applied per bucket).
    """
    assert fn in HIST_FNS, f"{fn} not supported on histograms"
    from ..query.plancache import plan_cache
    S, C, B = val.shape
    acc = jnp.dtype(accum)

    def build():
        body = functools.partial(_periodic, fn, w_cap=w_cap, acc=acc)

        def hist(ts, val, n, out_ts, window_ms, arg0, arg1):
            def one_bucket(vb):
                return body(ts, vb, n, out_ts, window_ms, arg0, arg1)
            return jnp.moveaxis(jax.vmap(one_bucket, in_axes=2)(val), 0, 2)
        return hist

    k = plan_cache.program(
        "periodic-hist",
        (fn, w_cap, accum, S, C, B, len(out_ts), str(val.dtype)), build)
    return k(ts, val, n, jnp.asarray(out_ts), jnp.int64(window_ms),
             jnp.float64(arg0), jnp.float64(0.0))


def periodic_samples(ts, val, n, out_ts, window_ms, fn: str,
                     arg0: float = 0.0, arg1: float = 0.0, w_cap: int = 256,
                     accum: str = "float64", holes: bool = False):
    """Evaluate range function ``fn`` for every series row at every output step.

    ts/val/n: store arrays (already gathered to the selected rows) — see windows.py.
    out_ts: int64 [T] output step timestamps. window_ms: range window (for
    ``last_sample`` pass the staleness lookback as both window and arg0).
    Returns float64 [P, T] with NaN for undefined points. ``holes``: the
    rows come from a line store with holes in place (:func:`_open_holes`).
    """
    S, C = val.shape
    k = _kernel(fn, w_cap, accum, (S, C, len(out_ts), str(val.dtype)), holes)
    return k(ts, val, n, *periodic_operands(out_ts, window_ms, arg0, arg1))
