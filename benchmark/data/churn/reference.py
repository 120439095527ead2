"""The plain reference over series that are born and end: PromQL window
functions and aggregates in numpy f64, from the law alone.

A series is (slot, revision) with its own birth and end scrape
(``law.Schedule``); its samples are the ones that exist: scrapes ``born <=
k < end`` of the exact 10 s grid, values ``counter``'s law of the series'
own id and age. Prometheus's semantics over those samples: a series
contributes to a window only what it has in it; ``rate`` / ``increase``
need two samples of the SAME series and extrapolate from that series' own
first and last sample in the window (a series born or ended inside a
window is not stretched to the window's edge: ``extrapolatedRate``); a
new revision is a new counter; ``avg`` and ``stddev`` divide by the series
present at the step. It imports nothing of the program.

Series sharing (birth, end) share their window cells, so the work is done
a CLASS at a time with ``counter``'s own per-series arithmetic
(``counter.reference.per_series``, imported: the same function object) on
the class's own clock — time moved so that the birth is column 0 and the
head the class's last scrape: the arithmetic then clips the window to the
series' life exactly as it clips it to the store's there.
``benchmark/tests/test_churn_data.py`` ties it series by series to
``tests/churn_reference.py`` (stamps and values, brute force).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from ..counter import datagen
from ..counter import reference as base

BLOCK = base.BLOCK


def classes(sched, rows, head_col: int):
    """[(born, last scrape held, rows of the class)] of ``rows``; a series
    not born by ``head_col`` is in none."""
    rows = np.asarray(rows, np.int64)
    born = sched.born[rows].astype(np.int64)
    last = np.minimum(sched.end[rows].astype(np.int64) - 1, head_col)
    keep = born <= last
    rows, born, last = rows[keep], born[keep], last[keep]
    key = born * (head_col + 2) + last
    order = np.argsort(key, kind="stable")
    rows, key = rows[order], key[order]
    cuts = np.flatnonzero(np.diff(key)) + 1
    out = []
    for part in np.split(np.arange(len(rows)), cuts):
        if len(part):
            k = int(key[part[0]])
            out.append((k // (head_col + 2), k % (head_col + 2), rows[part]))
    return out


def per_series(sched, seed: int, fn: str, rows, out_ts, window_ms: int,
               iv_ms: int, head_col: int, values=None) -> np.ndarray:
    """fn(m[w]) of the series ``rows``: [len(rows), T], NaN where
    undefined — a class at a time, on the class's own clock."""
    rows = np.asarray(rows, np.int64)
    out_ts = np.asarray(out_ts, np.int64)
    out = np.full((len(rows), len(out_ts)), np.nan)
    at = {int(r): i for i, r in enumerate(rows)}
    for born, last, mine in classes(sched, rows, head_col):
        clock = out_ts - born * iv_ms
        cols = base.needed_columns(fn, clock, window_ms, iv_ms, last - born)
        if not len(cols):
            continue
        vals = _values(sched, seed, mine, cols + born, values)
        got = base.per_series(fn, vals, cols, clock, window_ms, iv_ms,
                              last - born)
        out[[at[int(r)] for r in mine]] = got
    return out


def _values(sched, seed, rows, cols, values):
    if values is not None:
        return values(rows, cols)
    return sched.values(seed, rows, cols)


def evaluate(sched, seed: int, slots, spec: dict, out_ts, iv_ms: int,
             head_col: int, groups: int, threads: int = 6,
             values=None) -> dict:
    """``spec`` = {"agg", "fn", "window_s", "by"} over every series of the
    slots ``slots``: the answer as {label-tuple: f64[T]}. ``by`` is () or
    ("g",), g = slot % groups. ``values(rows, cols) -> [B, n] f64``
    replaces the generator (the control's lower precision)."""
    out_ts = np.asarray(out_ts, np.int64)
    fn, agg = spec["fn"], spec["agg"]
    window_ms = int(spec["window_s"]) * 1000
    by = tuple(spec.get("by", ()))
    if by not in ((), ("g",)):
        raise ValueError(f"reference groups by () or (g), not {by}")
    G = groups if by else 1
    T = len(out_ts)
    wanted = np.zeros(sched.plan.slots, bool)
    wanted[np.asarray(slots, np.int64)] = True
    rows = np.flatnonzero(wanted[sched.slot])
    work = []
    for born, last, mine in classes(sched, rows, head_col):
        clock = out_ts - born * iv_ms
        cols = base.needed_columns(fn, clock, window_ms, iv_ms, last - born)
        if len(cols):
            work += [(born, last, clock, cols, mine[lo:lo + BLOCK])
                     for lo in range(0, len(mine), BLOCK)]
    if not work:
        return {}
    # a per-step shift near the mean keeps the second moment well inside f64
    b0, l0, c0, k0, m0 = work[0]
    shift = np.nan_to_num(base.per_series(
        fn, _values(sched, seed, m0[:1], k0 + b0, values), k0, c0, window_ms,
        iv_ms, l0 - b0)[0])

    def block(job):
        born, last, clock, cols, mine = job
        x = base.per_series(fn, _values(sched, seed, mine, cols + born,
                                        values), cols, clock, window_ms,
                            iv_ms, last - born) - shift
        ok = np.isfinite(x)
        x = np.where(ok, x, 0.0)
        g = (sched.slot[mine] % groups) if by else np.zeros(len(mine),
                                                            np.int64)
        n = np.zeros((G, T)); s1 = np.zeros((G, T)); s2 = np.zeros((G, T))
        for k in range(G):
            m = g == k
            n[k] = ok[m].sum(axis=0)
            s1[k] = x[m].sum(axis=0)
            s2[k] = (x[m] * x[m]).sum(axis=0)
        return n, s1, s2

    with ThreadPoolExecutor(max(1, threads)) as ex:
        parts = list(ex.map(block, work))
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
            continue
        key = (("g", f"g{k}"),) if by else ()
        out[key] = res[k]
    return out


def instant(sched, seed: int, rows, t_ms, iv_ms: int, head_col: int,
            stale_ms: int = 300_000):
    """What an instant selector returns of the series ``rows`` at the
    stamps ``t_ms``: (values [n, T], stamps [n, T] in ms; NaN / -1 where
    the series has no sample in the lookback) — its newest sample at or
    before the step, for as long as the lookback says."""
    rows = np.asarray(rows, np.int64)
    t = np.asarray(t_ms, np.int64) - datagen.BASE_TS
    k = t // iv_ms                                           # newest scrape
    last = np.minimum(sched.end[rows].astype(np.int64) - 1, head_col)
    at = np.minimum(k[None, :], last[:, None])
    ok = (at >= sched.born[rows, None]) \
        & (t[None, :] - at * iv_ms <= stale_ms)
    vals = np.full(at.shape, np.nan)
    for j in range(at.shape[1]):
        col = np.where(ok[:, j], at[:, j], sched.born[rows])
        with np.errstate(over="ignore"):
            v = datagen.counter(
                np, datagen.fold_seed(seed),
                sched.series_id[rows].astype(np.uint32),
                (col - sched.born[rows]).astype(np.uint32))
        vals[:, j] = np.where(ok[:, j], v.astype(np.float64), np.nan)
    stamps = np.where(ok, datagen.BASE_TS + at * iv_ms, -1)
    return vals, stamps


def window_count(sched, rows, t_ms, window_ms: int, iv_ms: int,
                 head_col: int, born=None, end=None) -> np.ndarray:
    """sum over the series ``rows`` of count_over_time(m[w]) at the stamps
    ``t_ms``: int64 [T]. ``born`` / ``end`` replace the law's (what a store
    that lost its births, or its ends, would count)."""
    rows = np.asarray(rows, np.int64)
    b = (sched.born[rows] if born is None else born).astype(np.int64)
    e = (sched.end[rows] if end is None else end).astype(np.int64)
    lo, hi = base.window_cells(t_ms, window_ms, iv_ms, head_col)
    out = np.zeros(len(lo), np.int64)
    for j in range(len(lo)):
        f = np.maximum(lo[j], b)
        l = np.minimum(hi[j], e - 1)
        out[j] = np.maximum(l - f + 1, 0).sum()
    return out
