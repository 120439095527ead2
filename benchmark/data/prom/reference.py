"""The plain reference: PromQL window functions and aggregates in numpy f64,
from each sample's TRUE stamp.

Evaluated from the generator's closed forms (``datagen.py``), never from
the store; it imports nothing of the program. A window is Prometheus's
closed [t - w, t] over the stamps the samples came with: every series has
its own phase and its late scrapes, so which cells a window holds, and the
three durations of ``extrapolatedRate``, are per series. Stamps rise along
a series, so a window's samples are one run of scrapes [first, last]; the
run is found by arithmetic on the nominal schedule, corrected by one cell
where a late scrape crosses an edge, and CHECKED against the stamps
themselves (``window_run`` raises if a bracket is wrong). The work is done
in row blocks on a few threads, over the scrapes the query's windows can
touch; 2^20 series fit.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import datagen

BLOCK = 1 << 13


def scrape_range(out_ts, window_ms: int, iv_ms: int, head_col: int):
    """(k0, k1): every scrape some window of ``out_ts`` can hold lies in
    k0..k1 (a stamp lies at or after its nominal one, less than iv +
    LATE_MAX after it)."""
    t = np.asarray(out_ts, np.int64) - datagen.BASE_TS
    k0 = (int(t.min()) - window_ms - iv_ms - datagen.LATE_MAX) // iv_ms
    k1 = int(t.max()) // iv_ms
    return max(k0, 0), min(k1, head_col)


def window_run(stamps, k0: int, out_ts, window_ms: int, iv_ms: int,
               offset0):
    """(first, last) int64 [B, T]: indices into the block's columns of the
    first and last sample with t - w <= stamp <= t (last < first: none).
    ``stamps`` [B, K] rise along a row; ``offset0`` [B] is each row's
    smallest possible offset from the nominal schedule (its phase)."""
    B, K = stamps.shape
    t = np.asarray(out_ts, np.int64)[None, :]
    rel = t - datagen.BASE_TS - offset0[:, None]

    def at(idx):
        return np.take_along_axis(stamps, np.clip(idx, 0, K - 1), axis=1)

    # last: the last scrape scheduled at or before t, unless it came late
    last = np.clip(rel // iv_ms - k0, -1, K - 1)
    last = last - ((last >= 0) & (at(last) > t))
    # first: the first scrape scheduled at or after t - w, or the one
    # before it if that one came late enough
    first = np.clip(-((-(rel - window_ms)) // iv_ms) - k0, 0, K)
    first = first - ((first >= 1) & (at(first - 1) >= t - window_ms))
    bad = (((last >= 0) & (at(last) > t))
           | ((last < K - 1) & (at(last + 1) <= t))
           | ((first < K) & (at(first) < t - window_ms))
           | ((first >= 1) & (at(first - 1) >= t - window_ms)))
    if bad.any():
        raise AssertionError("reference: a window's run of samples is not "
                             "bracketed by the stamps")
    return first, last


def per_series(fn: str, stamps, vals, k0: int, out_ts, window_ms: int,
               iv_ms: int, offset0):
    """fn(m[w]) for the rows of ``vals``/``stamps`` ([B, K] f64 / i64, the
    scrapes k0..k0+K-1): [B, T], NaN where undefined."""
    first, last = window_run(stamps, k0, out_ts, window_ms, iv_ms, offset0)
    cnt = (last - first + 1).astype(np.float64)
    B, K = vals.shape
    t = np.asarray(out_ts, np.int64)[None, :]

    def at(a, idx):
        return np.take_along_axis(a, np.clip(idx, 0, K - 1), axis=1)

    with np.errstate(divide="ignore", invalid="ignore"):
        if fn in ("sum_over_time", "avg_over_time", "count_over_time"):
            if fn == "count_over_time":
                res = cnt
            else:
                P = np.concatenate([np.zeros((B, 1)), np.cumsum(vals, axis=1)],
                                   axis=1)                # P[:, i] = sum of < i
                s = (np.take_along_axis(P, np.clip(last + 1, 0, K), axis=1)
                     - np.take_along_axis(P, np.clip(first, 0, K), axis=1))
                res = s / cnt if fn == "avg_over_time" else s
            return np.where(cnt >= 1, res, np.nan)
        if fn not in ("rate", "increase"):
            raise ValueError(f"reference has no {fn!r}")
        v0, v1 = at(vals, first), at(vals, last)
        t0, t1 = at(stamps, first), at(stamps, last)
        delta = v1 - v0                                # monotone: no resets
        sampled = (t1 - t0) / 1000.0
        avg = sampled / (cnt - 1)
        dur_start = (t0 - (t - window_ms)) / 1000.0
        dur_end = (t - t1) / 1000.0
        dur_zero = sampled * (v0 / delta)
        clamp = (delta > 0) & (v0 >= 0) & (dur_zero < dur_start)
        dur_start = np.where(clamp, dur_zero, dur_start)
        thresh = avg * 1.1
        extrap = (sampled + np.where(dur_start < thresh, dur_start, avg / 2)
                  + np.where(dur_end < thresh, dur_end, avg / 2))
        inc = delta * (extrap / sampled)
        res = inc / (window_ms / 1000.0) if fn == "rate" else inc
        return np.where(cnt >= 2, res, np.nan)


def evaluate(seed: int, sids, spec: dict, out_ts, iv_ms: int, head_col: int,
             groups: int, threads: int = 6, values=None) -> dict:
    """``spec`` = {"agg", "fn", "window_s", "by"}: the answer as
    {label-tuple: f64[T]}. ``by`` is () or ("g",), with g = series % groups.
    ``values(sids, cols) -> [B, n] f64`` replaces the value generator (the
    control computes it in a lower precision); the stamps stay the law's."""
    sids = np.asarray(sids, np.int64)
    out_ts = np.asarray(out_ts, np.int64)
    fn, agg = spec["fn"], spec["agg"]
    window_ms = int(spec["window_s"]) * 1000
    by = tuple(spec.get("by", ()))
    if by not in ((), ("g",)):
        raise ValueError(f"reference groups by () or (g), not {by}")
    G = groups if by else 1
    T = len(out_ts)
    k0, k1 = scrape_range(out_ts, window_ms, iv_ms, head_col)
    if k1 < k0 or len(sids) == 0:
        return {}
    cols = np.arange(k0, k1 + 1)
    if values is None:
        def values(s, c):
            return datagen.counter_np(seed, s, c, np.float64)
    word = datagen.fold_seed(seed)

    def series(s):
        off = datagen.offset_np(seed, s, cols, iv_ms)
        stamps = datagen.BASE_TS + cols[None, :] * iv_ms + off
        with np.errstate(over="ignore"):
            ph = datagen.phase(np, word, np.asarray(s, np.uint32),
                               iv_ms).astype(np.int64)
        return per_series(fn, stamps, values(s, cols), k0, out_ts, window_ms,
                          iv_ms, ph)

    # a per-step shift near the mean keeps the second moment well inside f64
    shift = np.nan_to_num(series(sids[:1])[0])

    def block(lo):
        s = sids[lo:lo + BLOCK]
        x = series(s) - shift
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

    with ThreadPoolExecutor(max(1, threads)) as ex:
        parts = list(ex.map(block, range(0, len(sids), BLOCK)))
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
    """What a raw selector returns for those scrapes: the samples."""
    return datagen.counter_np(seed, sids, cols, np.float64)


def last_scrape(seed: int, sids, steps, iv_ms: int, newest: int) -> np.ndarray:
    """[len(sids), len(steps)] int64: the newest scrape, of those up to
    ``newest``, stamped at or before each step (what an instant selector
    reads there); -1 where there is none."""
    steps = np.asarray(steps, np.int64)
    k0 = max(int((steps.min() - datagen.BASE_TS) // iv_ms) - 2, 0)
    cols = np.arange(k0, newest + 1)
    stamps = datagen.stamps_np(seed, sids, cols, iv_ms)
    held = (stamps[:, :, None] <= steps[None, None, :]).sum(axis=1)
    return np.where(held > 0, k0 + held - 1, -1)
