"""The plain reference of ``tsbs_cpu``: numpy f64 over the series a query
selects, and over no other.

A TSBS ``single-groupby`` query names one metric and 1 or 8 hosts; the mix's
``ref`` carries them (``metric``, ``hosts``) with ``agg``, ``fn`` and
``window_s``. The reference walks those few series from scrape 0 by the law
(``datagen.walk_np``), evaluates ``fn`` over the closed window [t - w, t] of
the exact 10 s grid a step, folds the series with ``agg`` and returns one
row under the empty label set (an aggregate without ``by`` drops every
label, the metric's name too). It imports nothing of the program and never
reads the store; ``tests/tsbs_reference.py`` is its brute-force twin, tied
to it series by series in tier-1.
"""

from __future__ import annotations

import numpy as np

from ..counter.reference import window_cells  # noqa: F401 — the same grid
from . import datagen

WINDOW_FNS = {"max_over_time": np.max, "min_over_time": np.min,
              "sum_over_time": np.sum, "avg_over_time": np.mean,
              "count_over_time": None}
AGGS = {"max": np.nanmax, "min": np.nanmin, "sum": np.nansum,
        "avg": np.nanmean, "count": None}


def selected(sids, ref: dict) -> np.ndarray:
    """The series ids ``ref``'s matchers select among ``sids`` (sorted):
    field ``metric`` of each host in ``hosts``."""
    field = datagen.METRICS.index(ref["metric"])
    want = np.asarray([datagen.NF * int(h) + field for h in ref["hosts"]],
                      np.int64)
    return np.intersect1d(want, np.asarray(sids, np.int64))


def per_series(fn: str, vals: np.ndarray, c0: int, lo, hi) -> np.ndarray:
    """fn(m[w]) a series: ``vals`` [n, cols] holds scrapes c0.., (lo, hi)
    the window's cells a step -> [n, T], NaN where a window is empty."""
    out = np.full((vals.shape[0], len(lo)), np.nan)
    for j, (a, b) in enumerate(zip(lo.tolist(), hi.tolist())):
        if b < a:
            continue
        if fn == "count_over_time":
            out[:, j] = b - a + 1
        else:
            out[:, j] = WINDOW_FNS[fn](vals[:, a - c0:b - c0 + 1], axis=1)
    return out


def evaluate(seed: int, sids, ref: dict, out_ts, iv_ms: int, head_col: int,
             values=None) -> dict:
    """``agg(fn(metric{hostname=~hosts}[w]))`` at the steps ``out_ts``:
    {(): f64[T]}, NaN where no selected series has a sample in the window;
    {} where none is selected or no step has an answer."""
    fn, agg = ref["fn"], ref["agg"]
    if fn not in WINDOW_FNS or agg not in AGGS:
        raise ValueError(f"tsbs_cpu reference has no {agg}({fn})")
    sel = selected(sids, ref)
    lo, hi = window_cells(out_ts, int(ref["window_s"]) * 1000, iv_ms,
                          head_col)
    ok = hi >= lo
    if not len(sel) or not ok.any():
        return {}
    c0, c1 = int(lo[ok].min()), int(hi[ok].max())
    cols = np.arange(c0, c1 + 1)
    vals = (datagen.values_np(seed, sel, cols) if values is None
            else np.asarray(values(sel, cols), np.float64))
    x = per_series(fn, vals, c0, lo, hi)
    with np.errstate(all="ignore"), np.testing.suppress_warnings() as sup:
        sup.filter(RuntimeWarning)
        if agg == "count":
            res = np.isfinite(x).sum(axis=0).astype(np.float64)
            res[res == 0] = np.nan
        else:
            res = AGGS[agg](x, axis=0)
    res = np.where(ok, res, np.nan)
    return {(): res}


def raw_values(seed: int, sids, cols) -> np.ndarray:
    """What a raw selector returns at on-grid stamps: the samples."""
    return datagen.values_np(seed, sids, cols)
