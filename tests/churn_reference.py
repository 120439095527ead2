"""The plain reference of a fleet that redeploys: series that are born and
end, PromQL over the samples that EXIST. numpy f64 and plain Python; it
imports nothing of the program (nor of the benchmark).

A series is (slot, revision) with its own birth and end scrape; its samples
are the ones that exist: scrapes ``born <= k < end`` of a 10 s grid. Window
functions and aggregates are written out per series from stamps and values,
the rule ``tests/prom_reference.py`` follows: a series contributes to a
window only what it has in it; ``rate`` / ``increase`` / ``delta`` need two
samples of the SAME series and extrapolate from that series' own first and
last sample in the window (Prometheus's ``extrapolatedRate``: a series born
or ended inside a window is not stretched to the window's edge); a new
revision is a new counter (no reset correction across revisions); ``avg``
and ``stddev`` divide by the series present at the step. ``window_brute``
is the brute-force twin: one window, plain Python floats and loops.

The value law is the benchmark's ``counter`` mixer spelled out in Python
ints (``counter_py``), so the law's integers can be checked from plain
Python too: ``v(id, age) = start(id) + 64 age + h(seed, id, age)``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

BASE_TS = 1_700_000_000_000
IV = 10_000
STALE_MS = 300_000

_M1, _M2, _M3 = 0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35
_U = 0xFFFF_FFFF
_START_COL = 0xFFFF_FFFF
EVENT_COL = 0x8000_0000


def fold_seed_py(seed: int) -> int:
    seed = int(seed)
    x = (seed ^ (seed >> 32) ^ 0xA511E9B3) & _U
    x = (x * _M2) & _U
    x ^= x >> 15
    return x


def mix_py(word: int, s: int, c: int) -> int:
    """Two multiply rounds over (word, s, c), every step mod 2^32."""
    x = (((s * _M1) & _U) ^ word) ^ (((c * _M2) & _U) + _M3 & _U)
    x = (x * _M2) & _U
    x ^= x >> 15
    return (x * _M3) & _U


def counter_py(seed: int, sid: int, age: int) -> int:
    word = fold_seed_py(seed)
    start = mix_py(word, sid, _START_COL) % 100_000
    return (start + 64 * age + (mix_py(word, sid, age) >> 26)) & _U


def score_py(seed: int, target: int, event: int) -> int:
    """A target's score at update event ``event`` (the draw takes the
    least)."""
    return mix_py(fold_seed_py(seed), target, EVENT_COL + event)


@dataclass
class Series:
    """One (slot, revision): samples at scrapes ``born <= k < end``."""
    slot: int
    rev: int
    born: int
    end: int | None         # exclusive; None: alive at the head
    values: np.ndarray      # f64, one a scrape it holds, from ``born`` on

    def samples(self, head: int, base: int = BASE_TS, iv: int = IV):
        """(stamps int64, values f64) of the scrapes up to ``head``."""
        last = head if self.end is None else min(self.end - 1, head)
        n = max(last - self.born + 1, 0)
        k = self.born + np.arange(n, dtype=np.int64)
        return base + k * iv, np.asarray(self.values[:n], np.float64)


def window_brute(fn: str, ts, vals, t: int, window_ms: int) -> float:
    """fn over ONE window [t - w, t] of one series, the slow obvious way:
    plain Python floats, one loop a sample."""
    w_ts, w_v = [], []
    for a, b in zip(ts, vals):
        if t - window_ms <= int(a) <= t:
            w_ts.append(int(a))
            w_v.append(float(b))
    n = len(w_ts)
    if fn == "count_over_time":
        return float(n) if n else math.nan
    if fn in ("sum_over_time", "avg_over_time"):
        if not n:
            return math.nan
        s = 0.0
        for x in w_v:
            s += x
        return s / n if fn == "avg_over_time" else s
    if fn not in ("rate", "increase", "delta"):
        raise ValueError(fn)
    if n < 2:
        return math.nan
    v = list(w_v)
    if fn != "delta":               # counter: resets of THIS series only
        corr = 0.0
        for i in range(1, n):
            if w_v[i] < w_v[i - 1]:
                corr += w_v[i - 1] - w_v[i]
            v[i] = w_v[i] + corr
    dur_start = (w_ts[0] - (t - window_ms)) / 1000.0
    dur_end = (t - w_ts[-1]) / 1000.0
    sampled = (w_ts[-1] - w_ts[0]) / 1000.0
    avg = sampled / (n - 1)
    delta = v[-1] - v[0]
    if fn != "delta" and delta > 0 and v[0] >= 0:
        dur_zero = sampled * (v[0] / delta)
        if dur_zero < dur_start:
            dur_start = dur_zero
    extrap = sampled
    extrap += dur_start if dur_start < avg * 1.1 else avg / 2
    extrap += dur_end if dur_end < avg * 1.1 else avg / 2
    out = delta * (extrap / sampled)
    return out / (window_ms / 1000.0) if fn == "rate" else out


def range_fn(fn: str, ts, vals, out_ts, window_ms: int) -> np.ndarray:
    """fn(m[w]) of one series at every step, numpy f64: NaN where
    undefined. Window edges by search over the series' own stamps."""
    ts = np.asarray(ts, np.int64)
    vals = np.asarray(vals, np.float64)
    out_ts = np.asarray(out_ts, np.int64)
    lo = np.searchsorted(ts, out_ts - window_ms, side="left")
    hi = np.searchsorted(ts, out_ts, side="right")         # [lo, hi)
    n = hi - lo
    out = np.full(len(out_ts), np.nan)
    if fn == "count_over_time":
        return np.where(n > 0, n.astype(np.float64), np.nan)
    if fn in ("sum_over_time", "avg_over_time"):
        P = np.concatenate([[0.0], np.cumsum(vals)])
        s = P[hi] - P[lo]
        with np.errstate(invalid="ignore", divide="ignore"):
            return np.where(n > 0, s / n if fn == "avg_over_time" else s,
                            np.nan)
    if fn not in ("rate", "increase", "delta"):
        raise ValueError(fn)
    v = vals.copy()
    if fn != "delta" and len(v) > 1:
        drop = np.maximum(vals[:-1] - vals[1:], 0.0)
        v[1:] += np.cumsum(drop)
    for j in np.flatnonzero(n >= 2):
        a, b = lo[j], hi[j] - 1
        t = int(out_ts[j])
        dur_start = (ts[a] - (t - window_ms)) / 1000.0
        dur_end = (t - ts[b]) / 1000.0
        sampled = (ts[b] - ts[a]) / 1000.0
        avg = sampled / (n[j] - 1)
        delta = v[b] - v[a]
        if fn != "delta" and delta > 0 and v[a] >= 0:
            dur_start = min(dur_start, sampled * (v[a] / delta))
        extrap = sampled \
            + (dur_start if dur_start < avg * 1.1 else avg / 2) \
            + (dur_end if dur_end < avg * 1.1 else avg / 2)
        out[j] = delta * (extrap / sampled)
        if fn == "rate":
            out[j] /= window_ms / 1000.0
    return out


def instant(ts, vals, out_ts, stale_ms: int = STALE_MS):
    """(values, stamps) an instant selector returns of one series: its
    newest sample at or before each step, within the lookback; NaN / -1."""
    ts = np.asarray(ts, np.int64)
    out_ts = np.asarray(out_ts, np.int64)
    at = np.searchsorted(ts, out_ts, side="right") - 1
    ok = (at >= 0) & (out_ts - ts[np.maximum(at, 0)] <= stale_ms) \
        if len(ts) else np.zeros(len(out_ts), bool)
    v = np.where(ok, np.asarray(vals, np.float64)[np.maximum(at, 0)], np.nan) \
        if len(ts) else np.full(len(out_ts), np.nan)
    s = np.where(ok, ts[np.maximum(at, 0)], -1) if len(ts) \
        else np.full(len(out_ts), -1)
    return v, s


def aggregate(op: str, rows, keys=None) -> dict:
    """``op`` over per-series rows (f64 [T], NaN where the series is not
    present at the step), grouped by ``keys`` (one hashable a row; None:
    one group ``()``): {key: f64[T]}, NaN where no series of the group is
    present; ``avg`` / ``stddev`` / ``stdvar`` over the series PRESENT."""
    keys = [()] * len(rows) if keys is None else list(keys)
    out = {}
    for key in sorted(set(keys), key=str):
        x = np.array([r for r, k in zip(rows, keys) if k == key], np.float64)
        ok = np.isfinite(x)
        n = ok.sum(axis=0)
        z = np.where(ok, x, 0.0)
        with np.errstate(invalid="ignore", divide="ignore"):
            if op == "sum":
                r = z.sum(axis=0)
            elif op == "count":
                r = n.astype(np.float64)
            elif op == "avg":
                r = z.sum(axis=0) / n
            elif op in ("stddev", "stdvar"):
                mean = z.sum(axis=0) / n
                var = (np.where(ok, (x - mean) ** 2, 0.0)).sum(axis=0) / n
                r = np.sqrt(var) if op == "stddev" else var
            else:
                raise ValueError(op)
        if (n > 0).any():
            out[key] = np.where(n > 0, r, np.nan)
    return out
