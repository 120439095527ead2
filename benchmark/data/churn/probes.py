"""The read-back: what a raw selector, a count and the FUSED kernel must
return around a birth and an end, from the law alone.

(a) ``m{instance="t<i>"}`` and ``timestamp(..)`` of a target revised at
    the newest event at or before the landed scrape, over the scrapes
    from that event to the landed one: the old revision's series read
    their LAST sample (value and stamp of the scrape before the event: they
    got nothing past their edge), the new one's their own from their first.
    One step BEFORE the event, ``count(m{instance=..})`` reads the old
    revision alone (a birth that leaked early would double it).
(b) ``count by (revision)(m{instance="t<j>"})`` across an event of the
    HISTORY, one step on each side: the scrape before it (the old revision
    alone) and the event's own (both: the old one inside the lookback).
(c) the fused kernel's own: ``sum(count_over_time(m{g="g<j>"}[5m]))`` of
    one seeded group at four steps straddling an event — the counts read
    back EXACTLY, and the module checks that a store with every row born
    at cell 0, and one with no row ended, would each give other counts at
    one step at least.
"""

from __future__ import annotations

import numpy as np

from ..counter import datagen
from . import reference

WINDOW_MS = 300_000


def _targets_in(ids, per_target: int) -> np.ndarray:
    ids = np.asarray(ids, np.int64)
    t, c = np.unique(ids // per_target, return_counts=True)
    return t[c == per_target]           # whole targets of the container


def _rows_of(sched, target: int) -> np.ndarray:
    p = sched.plan
    lo = target * p.per_target
    return np.flatnonzero((sched.slot >= lo) & (sched.slot < lo + p.per_target))


def _labels(sched, row: int) -> dict:
    return {"host": f"h{int(sched.slot[row])}",
            "revision": str(int(sched.rev[row]))}


def probes(sched, seed: int, ids, col: int, deploy: dict, n: int,
           scrape_ms) -> list[dict]:
    p = sched.plan
    iv = int(deploy["scrape_interval_ms"])
    metric = deploy["metric"]
    groups = int(deploy["labels"]["groups"])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xC4A2])
    mine = _targets_in(ids, p.per_target)
    out = []
    e_now = sched.event_of(col)

    def instant_probe(text, rows, cols, stamps=False):
        t = np.asarray([scrape_ms(c, deploy) for c in cols], np.int64)
        vals, at = reference.instant(sched, seed, rows, t, iv, col)
        got = at / 1000.0 if stamps else vals
        keep = np.isfinite(vals).all(axis=1)
        return {"promql": text, "start_ms": int(t[0]), "end_ms": int(t[-1]),
                "step_ms": iv,
                "want": [(_labels(sched, r), got[i])
                         for i, r in enumerate(rows) if keep[i]]}

    def count_probe(text, rows, c, by_rev):
        t = np.asarray([scrape_ms(c, deploy)], np.int64)
        vals, _ = reference.instant(sched, seed, rows, t, iv, col)
        here = np.isfinite(vals[:, 0])
        if by_rev:
            want = [({"revision": str(int(v))},
                     np.array([float((here & (sched.rev[rows] == v)).sum())]))
                    for v in np.unique(sched.rev[rows][here])]
        else:
            want = [({}, np.array([float(here.sum())]))]
        return {"promql": text, "start_ms": int(t[0]), "end_ms": int(t[0]),
                "step_ms": iv, "want": want}

    # (a) a target revised at the newest event the landed scrape has seen
    drawn = np.intersect1d(sched.drawn[e_now - 1], mine) if e_now else ()
    if len(drawn):
        tgt, k = int(rng.choice(drawn)), e_now * p.every
        rows = _rows_of(sched, tgt)
        rows = rows[sched.born[rows] <= col]
        cols = np.arange(k, min(col, k + 3) + 1)
        sel = f'{metric}{{instance="t{tgt}"}}'
        out.append(instant_probe(sel, rows, cols))
        out.append(instant_probe(f"timestamp({sel})", rows, cols, True))
        out.append(count_probe(f"count({sel})", rows, k - 1, False))
    # (b) a target revised at an event of the history, one step a side
    past = [e for e in range(1, min(e_now, p.events - 1) + 1)
            if len(np.intersect1d(sched.drawn[e - 1], mine))]
    if past:
        e = int(rng.choice(past))
        tgt = int(rng.choice(np.intersect1d(sched.drawn[e - 1], mine)))
        rows = _rows_of(sched, tgt)
        rows = rows[sched.born[rows] <= col]
        sel = f'count by (revision)({metric}{{instance="t{tgt}"}})'
        out.append(count_probe(sel, rows, e * p.every - 1, True))
        out.append(count_probe(sel, rows, e * p.every, True))
    # (c) the fused kernel's count of one group around an event
    g = int(rng.integers(groups))
    rows = np.flatnonzero((sched.slot % groups == g) & (sched.born <= col))
    e = int(rng.integers(1, max(min(e_now, p.events - 1), 1) + 1))
    k = e * p.every
    step = 11 * iv
    steps = [c for c in range(k - 3, k + 31, 11) if WINDOW_MS // iv <= c <= col]
    start = scrape_ms(steps[0], deploy)
    grid = start + step * np.arange(len(steps), dtype=np.int64)
    want = reference.window_count(sched, rows, grid, WINDOW_MS, iv, col)
    if p.per_event:
        every0 = reference.window_count(
            sched, rows, grid, WINDOW_MS, iv, col,
            born=np.zeros(len(rows), np.int64))
        no_end = reference.window_count(
            sched, rows, grid, WINDOW_MS, iv, col,
            end=np.full(len(rows), col + 1, np.int64))
        if (every0 == want).all() or (no_end == want).all():
            raise RuntimeError(
                f"the count probe of g{g} at scrapes {steps} does "
                f"not tell a store that lost its births ({every0.tolist()}) "
                f"or its ends ({no_end.tolist()}) from a sound one "
                f"({want.tolist()})")
    out.append({"promql": f'sum(count_over_time({metric}{{g="g{g}"}}[5m]))',
                "start_ms": start, "end_ms": int(grid[-1]), "step_ms": step,
                "want": [({}, want.astype(np.float64))]})
    return out
