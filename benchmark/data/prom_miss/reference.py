"""The plain reference: PromQL window functions and aggregates in numpy f64,
from the TRUE stamps of the samples that EXIST.

Evaluated from the generator's closed forms (``datagen.py``), never from
the store; it imports nothing of the program. A missed scrape is no sample:
a window is Prometheus's closed [t - w, t] over the stamps of the scrapes
that came, found by MASKS over the cells the window can hold (no run of
cells is assumed whole), and ``extrapolatedRate`` takes its first and last
sample, its count and its durations over those alone. Step by step over
the few cells a step's window can touch, in row blocks on a few threads;
2^20 series fit.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.data.prom import reference as prom_ref

from . import datagen

BLOCK = 1 << 13
scrape_range = prom_ref.scrape_range


def per_series(fn: str, stamps, vals, exist, k0: int, out_ts, window_ms: int,
               iv_ms: int, head_col: int):
    """fn(m[w]) for the rows of ``vals``/``stamps``/``exist`` ([B, K] f64 /
    i64 / bool, the scrapes k0..k0+K-1): [B, T], NaN where undefined."""
    B, K = vals.shape
    out = np.full((B, len(out_ts)), np.nan)
    rows = np.arange(B)
    for j, t in enumerate(np.asarray(out_ts, np.int64)):
        a, b = scrape_range([t], window_ms, iv_ms, head_col)
        a, b = a - k0, b - k0
        if b < a:
            continue
        st, v = stamps[:, a:b + 1], vals[:, a:b + 1]
        inw = exist[:, a:b + 1] & (st >= t - window_ms) & (st <= t)
        cnt = inw.sum(axis=1).astype(np.float64)
        with np.errstate(divide="ignore", invalid="ignore"):
            if fn in ("sum_over_time", "avg_over_time", "count_over_time"):
                s = np.where(inw, v, 0.0).sum(axis=1)
                res = {"count_over_time": cnt, "sum_over_time": s,
                       "avg_over_time": s / cnt}[fn]
                out[:, j] = np.where(cnt >= 1, res, np.nan)
                continue
            if fn not in ("rate", "increase"):
                raise ValueError(f"reference has no {fn!r}")
            first = np.argmax(inw, axis=1)
            last = (b - a) - np.argmax(inw[:, ::-1], axis=1)
            v0, v1 = v[rows, first], v[rows, last]
            t0, t1 = st[rows, first], st[rows, last]
            delta = v1 - v0                            # monotone: no resets
            sampled = (t1 - t0) / 1000.0
            avg = sampled / (cnt - 1)
            dur_start = (t0 - (t - window_ms)) / 1000.0
            dur_end = (t - t1) / 1000.0
            dur_zero = sampled * (v0 / delta)
            clamp = (delta > 0) & (v0 >= 0) & (dur_zero < dur_start)
            dur_start = np.where(clamp, dur_zero, dur_start)
            thresh = avg * 1.1
            extrap = (sampled
                      + np.where(dur_start < thresh, dur_start, avg / 2)
                      + np.where(dur_end < thresh, dur_end, avg / 2))
            inc = delta * (extrap / sampled)
            res = inc / (window_ms / 1000.0) if fn == "rate" else inc
            out[:, j] = np.where(cnt >= 2, res, np.nan)
    return out


def evaluate(seed: int, sids, spec: dict, out_ts, iv_ms: int, head_col: int,
             groups: int, threads: int = 6, values=None,
             holes: bool = True) -> dict:
    """``spec`` = {"agg", "fn", "window_s", "by"}: the answer as
    {label-tuple: f64[T]}, as ``prom``'s. ``holes=False``: the answer of a
    store that took every missed scrape for a sample (what a probe must
    differ from)."""
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

    def series(s):
        missed = datagen.miss_np(seed, s, cols)
        exist = ~missed if holes else np.ones(missed.shape, bool)
        return per_series(fn, datagen.stamps_np(seed, s, cols, iv_ms, missed),
                          values(s, cols), exist, k0, out_ts, window_ms,
                          iv_ms, head_col)

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
    """What a raw selector returns for those scrapes: the samples, and
    nothing (NaN) where the scrape failed."""
    v = datagen.counter_np(seed, sids, cols, np.float64)
    return np.where(datagen.miss_np(seed, sids, cols), np.nan, v)


def last_scrape(seed: int, sids, steps, iv_ms: int, newest: int) -> np.ndarray:
    """[len(sids), len(steps)] int64: the newest scrape, of those up to
    ``newest``, whose row (sample or marker) is stamped at or before each
    step — what an instant selector looks at there; -1 where there is
    none."""
    steps = np.asarray(steps, np.int64)
    k0 = max(int((steps.min() - datagen.BASE_TS) // iv_ms) - 2, 0)
    cols = np.arange(k0, newest + 1)
    stamps = datagen.stamps_np(seed, sids, cols, iv_ms)
    held = (stamps[:, :, None] <= steps[None, None, :]).sum(axis=1)
    return np.where(held > 0, k0 + held - 1, -1)
