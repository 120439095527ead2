"""The update law: which targets get a new ``revision`` when, hence every
series' birth and end, as closed forms of (seed, the configuration).

A SLOT is a target's metric position, ``slot = target * per_target + i``;
a SERIES is (slot, revision). At scrapes ``k = every * e``, e = 1..events
(``every`` = update interval / scrape interval; the last event falls on
``fill_columns``, the first live scrape), ``per_event`` targets drawn by
the seed get ``revision + 1``: every series of such a target has its LAST
sample at scrape ``k - 1`` and a new series of the same slot its FIRST at
scrape ``k``. The draw: each target's score at event e is ``counter``'s
mixer over (seed word, target, a column no store has + e); of every
container's targets the one with the least score (ties: the lower target)
is drawn — so every container of a scrape carries births at every event
and a read-back always finds one — and the rest of the event's
``per_event`` are those with the least scores among all others. None is
drawn twice in an event; across events a target may be drawn again (its
revision then reads 2 or more). Where the event count's draw exceeds the
containers', or a container holds no whole target, the draw is the least
scores among all targets.

The same integers from numpy, ``jax.numpy`` (``scores``) and plain Python
(``tests/churn_reference.py`` spells the mixer out in Python ints).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

from ..counter import datagen

EVENT_COL = 0x8000_0000          # no store has this column either


@dataclass(frozen=True)
class Plan:
    """The sizes of the law, read off a configuration."""
    slots: int              # deploy["series"]: the ids the harness scrapes
    per_target: int         # series a target exposes
    targets: int
    every: int              # scrapes between two update events
    events: int             # update events up to and with fill_columns
    per_event: int          # targets an event revises
    containers: int
    fill_cols: int
    rows: int               # store rows the deployment may use

    @property
    def born_per_event(self) -> int:
        return self.per_event * self.per_target

    @property
    def registered_by_fill(self) -> int:
        """Rows registered before the first live scrape."""
        return self.slots + max(self.events - 1, 0) * self.born_per_event


def plan(deploy: dict) -> Plan:
    """``deploy["churn"]`` holds the source's own parameters. An event
    revises ``update_percent`` of the targets, rounded up — held to the
    rows the store has to spare for 2 h of ended series: a deployment cut
    to a size without room (the CPU rehearsal's ``shrink`` leaves none)
    revises fewer targets, down to none, and says so in ``per_event``."""
    c = deploy["churn"]
    slots = int(deploy["series"])
    per = int(c["series_per_target"])
    targets = slots // per
    if targets * per != slots:
        raise ValueError(f"{slots} slots are no whole number of targets of "
                         f"{per} series")
    iv = int(deploy["scrape_interval_ms"])
    every = int(c["update_interval_ms"]) // iv
    fill_cols = int(deploy["fill_columns"])
    events = fill_cols // every
    rows = int(deploy["server"]["store"]["max_series_per_shard"])
    want = math.ceil(targets * float(c["update_percent"]) / 100.0)
    room = (rows - slots) // max(events * per, 1)
    return Plan(slots, per, targets, every, events, max(min(want, room), 0),
                int(deploy["containers_per_scrape"]), fill_cols, rows)


def scores(xp, word, targets, event: int):
    """uint32 score of each target at update event ``event`` (1-based)."""
    c = xp.full(targets.shape, EVENT_COL + int(event), dtype=xp.uint32)
    return datagen._mix(xp, word, targets, c)


def draw(p: Plan, seed: int, event: int, xp=np) -> np.ndarray:
    """The targets update event ``event`` revises, ascending (host int64)."""
    if p.per_event == 0:
        return np.zeros(0, np.int64)
    t = xp.arange(p.targets, dtype=xp.uint32)
    with np.errstate(over="ignore"):
        sc = np.asarray(scores(xp, datagen.fold_seed(seed), t, event)
                        ).astype(np.int64)
    key = sc * p.targets + np.arange(p.targets)         # ties: lower target
    chunk = -(-p.slots // p.containers)                 # slots a container
    picked = np.zeros(p.targets, bool)
    if p.per_event >= p.containers and chunk % p.per_target == 0:
        per_c = chunk // p.per_target
        for j in range(p.containers):
            lo, hi = j * per_c, min((j + 1) * per_c, p.targets)
            if lo < hi:
                picked[lo + int(np.argmin(key[lo:hi]))] = True
    rest = p.per_event - int(picked.sum())
    others = np.flatnonzero(~picked)
    picked[others[np.argsort(key[others], kind="stable")[:rest]]] = True
    return np.flatnonzero(picked).astype(np.int64)


class Schedule:
    """Every series of one (configuration, seed): the draws of all events
    and what follows from them. A ROW is a series in the order the write
    path registers them — the slots at scrape 0, then each event's new
    series in slot order — so ``row`` is also the store row of a one-shard
    deployment that evicts nothing."""

    def __init__(self, deploy: dict, seed: int, xp=np):
        p = self.plan = plan(deploy)
        self.drawn = [draw(p, seed, e, xp) for e in range(1, p.events + 1)]
        # revision of each target after event e (rev_after[0]: all zeros)
        rev = np.zeros((p.events + 1, p.targets), np.int32)
        for e, t in enumerate(self.drawn, 1):
            rev[e] = rev[e - 1]
            rev[e, t] += 1
        self.rev_after = rev
        n = p.slots + p.events * p.born_per_event
        self.slot = np.empty(n, np.int64)
        self.rev = np.zeros(n, np.int32)
        self.born = np.zeros(n, np.int32)
        self.end = np.full(n, np.iinfo(np.int32).max, np.int32)   # exclusive
        self.slot[:p.slots] = np.arange(p.slots)
        cur = np.arange(p.slots, dtype=np.int64)    # slot -> its newest row
        self.current = [cur.copy()]
        at = p.slots
        i = np.arange(p.per_target)
        for e, t in enumerate(self.drawn, 1):
            slots = (t[:, None] * p.per_target + i[None, :]).ravel()
            rows = np.arange(at, at + len(slots))
            self.end[cur[slots]] = e * p.every
            self.slot[rows] = slots
            self.rev[rows] = np.repeat(rev[e, t], p.per_target)
            self.born[rows] = e * p.every
            cur[slots] = rows
            self.current.append(cur.copy())
            at += len(slots)
        assert at == n

    @property
    def series_id(self) -> np.ndarray:
        """The id the value law takes: a series' own, below 2^32."""
        return self.slot + self.rev.astype(np.int64) * self.plan.slots

    def event_of(self, k: int) -> int:
        """Update events at or before scrape ``k``."""
        return min(int(k) // self.plan.every, self.plan.events)

    def rows_at(self, slots, k: int) -> np.ndarray:
        """The row that holds each slot's series alive at scrape ``k``."""
        return self.current[self.event_of(k)][np.asarray(slots, np.int64)]

    def values(self, seed: int, rows, cols, dtype=np.float64) -> np.ndarray:
        """[len(rows), len(cols)]: ``counter``'s law of each series' own id
        and AGE (a new process's counters start over); NaN where the series
        holds no sample at that scrape."""
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        age = cols[None, :] - self.born[rows, None]
        ok = (age >= 0) & (cols[None, :] < self.end[rows, None])
        with np.errstate(over="ignore"):
            v = datagen.counter(np, datagen.fold_seed(seed),
                                self.series_id[rows].astype(np.uint32)[:, None],
                                np.maximum(age, 0).astype(np.uint32))
        return np.where(ok, v.astype(np.float64), np.nan).astype(dtype)


_KEPT: dict = {}


def schedule(deploy: dict, seed: int) -> Schedule:
    """One ``Schedule`` a (configuration's sizes, seed), kept."""
    p = plan(deploy)
    key = (p, int(seed))
    s = _KEPT.get(key)
    if s is None:
        if len(_KEPT) >= 4:
            _KEPT.pop(next(iter(_KEPT)))
        s = _KEPT[key] = Schedule(deploy, seed)
    return s
