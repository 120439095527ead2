"""The plain reference: PromQL window functions and aggregates in numpy f64.

Evaluated from the generator's closed form (benchmark/datagen.py), never
from the store: for a query ``agg [by (g)] (fn(m[w]))`` over steps
``out_ts`` it returns what Prometheus would, one f64 row per output series,
NaN where a step has no answer. It imports nothing of the program. The
window algebra is Prometheus' (closed window [t - w, t], extrapolatedRate
spelled out); ``benchmark/rehearse.py`` ties it series by series to the
repo's golden model ``tests/prom_reference.py``.

Every series of a deployment has samples at columns 0..head_col of the
10 s grid, so window cell ranges are per step, not per series; the work is
done in row blocks on a few threads (numpy releases the GIL) and needs only
the columns the query's windows touch.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import datagen

BLOCK = 1 << 13        # rows at a time per thread: ~50 MB an f64 block of 750 columns


def window_cells(out_ts, window_ms: int, iv_ms: int, head_col: int):
    """(lo[T], hi[T]): sample columns k with t - w <= BASE + k*iv <= t,
    clipped to 0..head_col; a window with no sample has hi < lo."""
    t = np.asarray(out_ts, np.int64) - datagen.BASE_TS
    lo = -((-(t - window_ms)) // iv_ms)
    hi = t // iv_ms
    return np.maximum(lo, 0), np.minimum(hi, head_col)


def per_series(fn: str, vals, cols, out_ts, window_ms, iv_ms, head_col):
    """fn(m[w]) for the series rows of ``vals`` ([B, len(cols)] f64, the
    columns ``cols`` ascending and covering every window cell): [B, T],
    NaN where undefined."""
    lo, hi = window_cells(out_ts, window_ms, iv_ms, head_col)
    cnt = hi - lo + 1
    pos = {int(c): i for i, c in enumerate(cols)}
    B, T = vals.shape[0], len(out_ts)
    out = np.full((B, T), np.nan)
    if fn in ("sum_over_time", "avg_over_time", "count_over_time"):
        P = np.cumsum(vals, axis=1)           # P[:, i] = sum of columns <= i
        for j in range(T):
            if cnt[j] < 1:
                continue
            if fn == "count_over_time":
                out[:, j] = cnt[j]
                continue
            a = pos[int(lo[j])]
            s = P[:, pos[int(hi[j])]] - (P[:, a - 1] if a else 0.0)
            out[:, j] = s / cnt[j] if fn == "avg_over_time" else s
        return out
    if fn not in ("rate", "increase"):
        raise ValueError(f"reference has no {fn!r}")
    w_s = window_ms / 1000.0
    for j in range(T):
        if cnt[j] < 2:
            continue
        v0, v1 = vals[:, pos[int(lo[j])]], vals[:, pos[int(hi[j])]]
        delta = v1 - v0                                # monotone: no resets
        t = int(out_ts[j]) - datagen.BASE_TS
        t0, t1 = int(lo[j]) * iv_ms, int(hi[j]) * iv_ms
        sampled = (t1 - t0) / 1000.0
        avg = sampled / (cnt[j] - 1)
        dur_start = np.full(B, (t0 - (t - window_ms)) / 1000.0)
        dur_end = (t - t1) / 1000.0
        with np.errstate(divide="ignore", invalid="ignore"):
            dur_zero = sampled * (v0 / delta)
        clamp = (delta > 0) & (v0 >= 0) & (dur_zero < dur_start)
        dur_start = np.where(clamp, dur_zero, dur_start)
        thresh = avg * 1.1
        extrap = sampled + np.where(dur_start < thresh, dur_start, avg / 2) \
            + (dur_end if dur_end < thresh else avg / 2)
        inc = delta * (extrap / sampled)
        out[:, j] = inc / w_s if fn == "rate" else inc
    return out


def needed_columns(fn: str, out_ts, window_ms, iv_ms, head_col) -> np.ndarray:
    lo, hi = window_cells(out_ts, window_ms, iv_ms, head_col)
    ok = hi >= lo
    if not ok.any():
        return np.zeros(0, np.int64)
    if fn in ("rate", "increase"):
        return np.unique(np.concatenate([lo[ok], hi[ok]]))
    return np.arange(lo[ok].min(), hi[ok].max() + 1)


def evaluate(seed: int, sids, spec: dict, out_ts, iv_ms: int, head_col: int,
             groups: int, threads: int = 6, values=None) -> dict:
    """``spec`` = {"agg", "fn", "window_s", "by"}: the answer as
    {label-tuple: f64[T]}. ``by`` is () or ("g",), with g = series % groups
    (the deployment's labelling). ``values(sids, cols) -> [B, n] f64``
    replaces the generator (the control computes it in a lower precision)."""
    sids = np.asarray(sids, np.int64)
    out_ts = np.asarray(out_ts, np.int64)
    fn, agg = spec["fn"], spec["agg"]
    window_ms = int(spec["window_s"]) * 1000
    by = tuple(spec.get("by", ()))
    if by not in ((), ("g",)):
        raise ValueError(f"reference groups by () or (g), not {by}")
    G = groups if by else 1
    cols = needed_columns(fn, out_ts, window_ms, iv_ms, head_col)
    T = len(out_ts)
    if values is None:
        def values(s, c):
            return datagen.counter_np(seed, s, c, np.float64)
    if len(cols) == 0 or len(sids) == 0:
        return {}
    # a per-step shift near the mean keeps the second moment well inside f64
    shift = np.nan_to_num(per_series(fn, values(sids[:1], cols), cols, out_ts,
                                     window_ms, iv_ms, head_col)[0])

    def block(lo):
        s = sids[lo:lo + BLOCK]
        x = per_series(fn, values(s, cols), cols, out_ts, window_ms, iv_ms,
                       head_col) - shift
        ok = np.isfinite(x)
        x = np.where(ok, x, 0.0)
        g = (s % groups) if by else np.zeros(len(s), np.int64)
        n = np.zeros((G, T)); s1 = np.zeros((G, T)); s2 = np.zeros((G, T))
        for k in range(G):
            m = g == k
            n[k] = ok[m].sum(axis=0)
            s1[k] = x[m].sum(axis=0)
            s2[k] = (x[m] * x[m]).sum(axis=0)
        return n, s1, s2

    starts = range(0, len(sids), BLOCK)
    with ThreadPoolExecutor(max(1, threads)) as ex:
        parts = list(ex.map(block, starts))
    n = sum(p[0] for p in parts)
    s1 = sum(p[1] for p in parts)
    s2 = sum(p[2] for p in parts)
    with np.errstate(divide="ignore", invalid="ignore"):
        mean = s1 / n
        if agg == "sum":
            res = s1 + n * shift
        elif agg == "avg":
            res = mean + shift
        elif agg == "count":
            res = n.copy()
        elif agg in ("stddev", "stdvar"):
            var = np.maximum(s2 / n - mean * mean, 0.0)
            res = np.sqrt(var) if agg == "stddev" else var
        else:
            raise ValueError(f"reference has no aggregate {agg!r}")
    res = np.where(n > 0, res, np.nan)
    out = {}
    for k in range(G):
        if not (n[k] > 0).any():
            continue                       # a group with no sample: no series
        key = (("g", f"g{k}"),) if by else ()
        out[key] = res[k]
    return out


def raw_values(seed: int, sids, cols) -> np.ndarray:
    """What a raw selector returns at on-grid stamps: the samples."""
    return datagen.counter_np(seed, sids, cols, np.float64)
