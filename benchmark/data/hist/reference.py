"""The plain reference: ``histogram_quantile(q, sum [by (g)] (fn(h[w])))`` in
numpy f64, ``fn`` one of ``rate``, ``increase``, ``delta``.

Evaluated from the generator's closed form (``datagen.py``), never from the
store; it imports nothing of the program. Three plain steps, each the
textbook rule:

1. per series and per bucket, the range function over the closed window
   ``[t - w, t]`` with Prometheus's extrapolation spelled out
   (``extrapolatedRate``: the slope through the first and last sample is
   stretched to the window's ends, but no further than half a sample
   interval past a sample that is not near the end, and for a counter no
   further back than where it would have been zero). A counter that FALLS
   adds nothing for that cell — the upstream project's counter correction
   (``RateFunctions.scala``), which the program follows; Prometheus proper
   would count the value after the drop. The deployment's stream never
   falls, so the two agree there; the tier-1 tests put one drop in;
2. bucket by bucket, the sum over the group's series (a series with fewer
   than two samples in the window adds nothing; a group none of whose
   series has two has no value at that step);
3. the quantile of the summed buckets by linear interpolation inside the
   bucket whose cumulative count first reaches ``q`` times the total, the
   lower edge of the first bucket being 0; a rank in the +Inf bucket
   answers the highest finite bound.

A sample's cells are the columns of the deployment's grid: sample ``k`` of
every series has stamp ``BASE_TS + k * interval``; series ``i`` has
``n[i]`` samples (all ``head_col + 1`` in a deployment).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import datagen

BLOCK = 256            # series at a time a thread: 16 MB an f64 block of 122 columns
FNS = ("rate", "increase", "delta")


def window_cells(out_ts, window_ms: int, iv_ms: int):
    """(lo[T], hi[T]): sample columns k with t - w <= BASE + k*iv <= t, lo
    clipped to 0; hi is clipped per series to its last sample."""
    t = np.asarray(out_ts, np.int64) - datagen.BASE_TS
    lo = -((-(t - window_ms)) // iv_ms)
    hi = t // iv_ms
    return np.maximum(lo, 0), hi


def needed_columns(out_ts, window_ms: int, iv_ms: int,
                   head_col: int) -> np.ndarray:
    """The first and last cell of every window that holds two samples: all
    a range function needs of a counter that never falls."""
    lo, hi = window_cells(out_ts, window_ms, iv_ms)
    hi = np.minimum(hi, head_col)
    ok = hi > lo
    return np.unique(np.concatenate([lo[ok], hi[ok]]))


def bucket_rates(fn: str, vals, cols, n, out_ts, window_ms: int,
                 iv_ms: int) -> np.ndarray:
    """fn(h[w]) per series and bucket: ``vals [S, len(cols), B]`` f64, the
    cumulative buckets at the cells ``cols`` (ascending, every window's
    first and last cell among them), ``n [S]`` samples a series ->
    ``[S, T, B]``, NaN where a window holds fewer than two samples. Where
    ``cols`` holds every cell a fall is corrected cell by cell; between
    cells left out the counter must not have fallen."""
    if fn not in FNS:
        raise ValueError(f"reference has no {fn!r}")
    vals = np.asarray(vals, np.float64)
    cols = np.asarray(cols, np.int64)
    n = np.asarray(n, np.int64)
    S, _, B = vals.shape
    T = len(out_ts)
    lo, hi = window_cells(out_ts, window_ms, iv_ms)
    counter = fn != "delta"
    if counter:
        # the corrected counter: a cell that falls adds nothing
        steps = np.maximum(np.diff(vals, axis=1), 0.0)
        run = np.concatenate([vals[:, :1], vals[:, :1] + np.cumsum(steps, 1)],
                             axis=1)
    else:
        run = vals
    w_s = window_ms / 1000.0
    out = np.full((S, T, B), np.nan)
    rows = np.arange(S)
    for j in range(T):
        first = int(lo[j])
        last = np.minimum(int(hi[j]), n - 1)                  # [S]
        cnt = last - first + 1
        ok = cnt >= 2
        if not ok.any():
            continue
        pf = int(np.searchsorted(cols, first))
        pl = np.searchsorted(cols, np.where(ok, last, first))
        if cols[pf] != first or (cols[pl] != np.where(ok, last, first)).any():
            raise ValueError(f"step {j}: a window's end is not in cols")
        t = int(out_ts[j]) - datagen.BASE_TS
        t0 = first * iv_ms
        t1 = last * iv_ms                                     # [S]
        sampled = (t1 - t0) / 1000.0
        with np.errstate(divide="ignore", invalid="ignore"):
            avg = sampled / (cnt - 1)
            delta = run[rows, pl] - run[:, pf]                # [S, B]
            v0 = vals[:, pf]
            dur_start = np.full((S, B), (t0 - (t - window_ms)) / 1000.0)
            dur_end = ((t - t1) / 1000.0)[:, None]
            if counter:
                dur_zero = sampled[:, None] * (v0 / delta)
                clamp = (delta > 0) & (v0 >= 0) & (dur_zero < dur_start)
                dur_start = np.where(clamp, dur_zero, dur_start)
            thresh = (avg * 1.1)[:, None]
            half = (avg / 2)[:, None]
            extrap = sampled[:, None] \
                + np.where(dur_start < thresh, dur_start, half) \
                + np.where(dur_end < thresh, dur_end, half)
            res = delta * (extrap / sampled[:, None])
        if fn == "rate":
            res = res / w_s
        out[:, j] = np.where(ok[:, None], res, np.nan)
    return out


def quantile(q: float, les, counts) -> np.ndarray:
    """Prometheus's ``bucketQuantile`` over cumulative ``counts [..., B]``
    with upper bounds ``les [B]`` (the last +Inf): ``[...]``."""
    les = np.asarray(les, np.float64)
    counts = np.asarray(counts, np.float64)
    B = len(les)
    total = counts[..., -1]
    rank = q * total
    with np.errstate(invalid="ignore", divide="ignore"):
        b = np.minimum((counts < rank[..., None]).sum(axis=-1), B - 1)
        below = np.maximum(b - 1, 0)
        start = np.where(b > 0, les[below], 0.0)
        c_lo = np.where(b > 0, np.take_along_axis(
            counts, below[..., None], -1)[..., 0], 0.0)
        c_hi = np.take_along_axis(counts, b[..., None], -1)[..., 0]
        end = les[b]
        inside = np.where(c_hi > c_lo, (rank - c_lo) / (c_hi - c_lo), 1.0)
        res = start + (end - start) * inside
    res = np.where(b == B - 1, les[B - 2] if B > 1 else np.nan, res)
    res = np.where((total > 0) & np.isfinite(total), res, np.nan)
    if q < 0:
        res = np.full_like(res, -np.inf)
    if q > 1:
        res = np.full_like(res, np.inf)
    return res


def group_sums(rates, gid, G: int):
    """Step 2: ``rates [S, T, B]`` (NaN = no value) summed bucket by bucket
    over the series of each group ``gid [S]`` -> (``[G, T, B]`` sums,
    ``[G, T]`` how many series had a value)."""
    ok = np.isfinite(rates[:, :, 0])
    x = np.where(ok[:, :, None], rates, 0.0)
    T, B = rates.shape[1], rates.shape[2]
    tot, cnt = np.zeros((G, T, B)), np.zeros((G, T))
    for k in range(G):
        m = gid == k
        tot[k] = x[m].sum(axis=0)
        cnt[k] = ok[m].sum(axis=0)
    return tot, cnt


def group_quantile(q: float, les, rates, gid, G: int) -> np.ndarray:
    """Steps 2 and 3 -> ``[G, T]``, NaN where no series of the group has a
    value."""
    tot, cnt = group_sums(rates, gid, G)
    return np.where(cnt > 0, quantile(q, les, tot), np.nan)


def evaluate(seed: int, sids, spec: dict, out_ts, iv_ms: int, head_col: int,
             groups: int, nb: int, threads: int = 6, values=None) -> dict:
    """``spec`` = {"q", "fn", "window_s", "by"}: the answer as
    {label-tuple: f64[T]}. ``by`` is () or ("g",), with g = series % groups
    (the deployment's labelling). ``values(sids, cols) -> [n, m, nb] f64``
    replaces the generator (the control computes it in a lower
    precision)."""
    sids = np.asarray(sids, np.int64)
    out_ts = np.asarray(out_ts, np.int64)
    fn, q = spec["fn"], float(spec["q"])
    window_ms = int(spec["window_s"]) * 1000
    by = tuple(spec.get("by", ()))
    if by not in ((), ("g",)):
        raise ValueError(f"reference groups by () or (g), not {by}")
    G = groups if by else 1
    T = len(out_ts)
    cols = needed_columns(out_ts, window_ms, iv_ms, head_col)
    if len(cols) == 0 or len(sids) == 0:
        return {}
    if values is None:
        def values(s, c):
            return datagen.columns_np(seed, s, c, nb)[2]
    les = datagen.bucket_les(nb)

    def block(lo):
        s = sids[lo:lo + BLOCK]
        r = bucket_rates(fn, values(s, cols), cols,
                         np.full(len(s), head_col + 1), out_ts, window_ms,
                         iv_ms)
        g = (s % groups) if by else np.zeros(len(s), np.int64)
        return group_sums(r, g, G)

    with ThreadPoolExecutor(max(1, threads)) as ex:
        parts = list(ex.map(block, range(0, len(sids), BLOCK)))
    tot = sum(p[0] for p in parts)
    cnt = sum(p[1] for p in parts)
    res = np.where(cnt > 0, quantile(q, les, tot), np.nan)
    out = {}
    for k in range(G):
        if not (cnt[k] > 0).any():
            continue                       # a group with no sample: no series
        key = (("g", f"g{k}"),) if by else ()
        out[key] = res[k]
    return out
