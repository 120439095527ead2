"""``prom_miss``: ``prom``'s stream with the scrapes a real scraper misses —
one in 128, each marked stale as Prometheus marks it — in a line store that
keeps a hole as a hole.

The six points of ``benchmark/data/__init__.py`` for that kind of data:

- ``datagen.py``: ``miss(s, k)``, a closed form of (seed, s, k), the same
  booleans from numpy and ``jax.numpy``; a missed scrape's row carries
  ``STALE_NAN`` at its scheduled stamp; every other scrape is ``prom``'s
  (stamps) and ``counter``'s (values), imported.
- ``fill.py``: columns ``1..fill-1`` written into ``st.val`` and ``st.res``
  in the store's own hole form (the mark in the residual, the marker's
  stamp less its cell's line stamp in the value cell) by donated programs;
  landed = the cell is used (sample or hole), ``n_host[row] > col``.
- ``reference.py``: ``prom``'s functions and aggregates, numpy f64, over
  the samples that exist.
- the needed bytes are ``prom``'s: the marks ride in the residuals.

DEPARTURE, written down: Prometheus marks only the first failed scrape of a
run and sends nothing for the rest. The harness's containers hold a row for
every series (``served.Writer.publish`` fills a template, ``drain`` waits
for every published row), so every missed scrape carries a marker here; to
every range function the store's state is the same (a marker and a skipped
cell both leave a hole; the hole's value cell says which, and a marker's
own stamp), and absence WITHOUT a marker — after which an instant selector
serves the sample before, inside the lookback — is exercised by the tier-1
tests (``tests/test_line_holes.py``), not by the cell.
"""

from __future__ import annotations

import numpy as np

from benchmark.data import prom as _prom

from . import datagen, reference
from . import fill as _fill

_iv = _prom._iv

# 1. series and 2. the nominal stamp: prom's, as they are

schema = _prom.schema
series_labels = _prom.series_labels
scrape_ms = _prom.scrape_ms


def scrape(seed: int, ids, k: int, deploy: dict) -> dict:
    vals = datagen.counter_np(seed, ids, [k], np.float64)[:, 0]
    missed = datagen.miss_np(seed, ids, [k])[:, 0]
    return {"ts": datagen.stamps_np(seed, ids, [k], _iv(deploy))[:, 0],
            "values": np.where(missed, datagen.STALE_NAN, vals)}


# 3. the history on the device

def fill(shard, sid, seed: int, deploy: dict) -> None:
    _fill.fill_history(shard, sid, seed, int(deploy["fill_columns"]),
                       _iv(deploy))


def check_filled(shard, sid, deploy: dict) -> set:
    _fill.check_filled(shard, sid, int(deploy["fill_columns"]), _iv(deploy))
    st = shard.store
    if st.res.devices() != st.val.devices():
        raise RuntimeError(f"shard {shard.shard_num}: residuals and values "
                           f"on two devices")
    return set(st.val.devices())


landed = _prom.landed           # the cell is used: a sample or a hole


# 4. the plain reference

def evaluate(seed: int, sids, ref: dict, out_ts, deploy: dict, head_col: int,
             values=None) -> dict:
    return reference.evaluate(seed, sids, ref, out_ts, _iv(deploy), head_col,
                              int(deploy["labels"]["groups"]), values=values)


def raw_values(seed: int, sids, cols, deploy: dict) -> np.ndarray:
    return reference.raw_values(seed, sids, cols)


# 5. the read-back probes

PROBE_STEPS = _prom.PROBE_STEPS
NEAR = 8        # scrapes: how far behind a probed sample a hole may lie
COUNT_WINDOW_S = _prom.COUNT_WINDOW_S


def probes(seed: int, ids, col: int, deploy: dict, n: int) -> list[dict]:
    """Seeded probes of a container (series ``ids``) whose newest scrape is
    ``col``, racks taken from the law in a seeded order so that EVERY run's
    probes meet holes; four steps an interval apart, the last the latest a
    row of scrape ``col`` can be stamped:

    (a) ``n - 1`` racks (one at least) read as ``m{rack=..}`` and as
        ``timestamp(m{rack=..})``: at every step every probed series holds
        a SAMPLE, and one of them at least sits right after a run of
        missed scrapes (in a container too small to hold such a rack:
        within NEAR scrapes after one) — values and stamps the sample's
        own, exactly;
    (b) one rack read as ``count by (rack)(m{rack=..})``: at one step at
        least a series' newest scrape was MISSED, so the count falls
        there (4, 4, 3, 4): absence is probed as a count because the
        harness's read-back compares finite numbers only. Its steps are
        its own (:func:`_absent_probe`): one of them lies between the
        marker's stamp and the stamp the row's LINE gives the marker's
        cell, where a store that kept the hole and lost the marker's own
        stamp still serves the sample before;
    (c) ``sum(count_over_time(m{g="g<j>"}[5m]))`` of one seeded group
        inside the filled history, counts exact, which the FUSED kernel
        makes (:func:`_count_probe`)."""
    iv = _iv(deploy)
    per = int(deploy["labels"]["per_rack"])
    ids = np.asarray(ids)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x4EAD])
    end = scrape_ms(col, deploy) + iv + datagen.LATE_MAX - 1
    steps = end - iv * np.arange(PROBE_STEPS - 1, -1, -1)
    metric = deploy["metric"]
    after, near, lost_racks, seen = [], [], [], set()
    full = 2 * max(n - 1, 1)
    for sid in rng.permutation(ids):
        rack = int(sid) // per
        if rack in seen:
            continue
        seen.add(rack)
        members = np.arange(rack * per, rack * per + per)
        if not np.isin(members, ids).all():
            continue
        held = reference.last_scrape(seed, members, steps, iv, col)
        if (held < 1).any():
            continue
        lost = np.stack([datagen.miss_np(seed, [i], held[j])[0]
                         for j, i in enumerate(members)])
        sel = f'{metric}{{rack="r{rack}"}}'
        if lost.any():
            if not lost_racks and (~lost).any(axis=0).all():
                lost_racks.append(rack)
            continue
        right = any(datagen.miss_np(seed, [i], held[j] - 1).any()
                    for j, i in enumerate(members))
        close = right or any(
            datagen.miss_np(seed, [i], held[j, 0] - 1 - np.arange(NEAR)).any()
            for j, i in enumerate(members))
        into = after if right else near
        if len(into) < full and close:
            vals = np.stack([datagen.counter_np(seed, [i], held[j],
                                                np.float64)[0]
                             for j, i in enumerate(members)])
            stamps = np.stack([datagen.stamps_np(seed, [i], held[j], iv)[0]
                               for j, i in enumerate(members)])
            for promql, want in ((sel, vals),
                                 (f"timestamp({sel})", stamps / 1000.0)):
                into.append({"promql": promql, "start_ms": int(steps[0]),
                              "end_ms": int(steps[-1]), "step_ms": iv,
                              "want": [({"host": f"h{i}"}, want[j])
                                       for j, i in enumerate(members)]})
        if lost_racks and len(after) == full:
            break
    after = (after + near)[:full]
    absent = _absent_probe(seed, ids, col, deploy, rng, end, lost_racks)
    if absent is None or not after:
        raise RuntimeError("prom_miss: the container's racks meet no hole "
                           "at the probed steps")
    return after + [absent, _count_probe(seed, deploy, rng)]


def _count_by_rack(seed: int, rack: int, steps, col: int, deploy: dict):
    """The probe ``count by (rack)(m{rack="r<rack>"})`` at ``steps``, or
    None where a step holds no member's sample or none is absent."""
    iv, per = _iv(deploy), int(deploy["labels"]["per_rack"])
    members = np.arange(rack * per, rack * per + per)
    held = reference.last_scrape(seed, members, steps, iv, col)
    if (held < 1).any():
        return None
    lost = np.stack([datagen.miss_np(seed, [i], held[j])[0]
                     for j, i in enumerate(members)])
    count = (~lost).sum(axis=0).astype(np.float64)
    if not lost.any() or (count < 1).any():
        return None
    return {"promql": f'count by (rack)({deploy["metric"]}'
                      f'{{rack="r{rack}"}})',
            "start_ms": int(steps[0]), "end_ms": int(steps[-1]),
            "step_ms": iv, "want": [({"rack": f"r{rack}"}, count)]}


def _absent_probe(seed: int, ids, col: int, deploy: dict, rng, end: int,
                  lost_racks: list):
    """Probe (b). A marker comes on SCHEDULE; the line of a row whose
    first scrape was late lies ``late(s, 0)`` ms after the schedule, and
    so does the stamp the line gives the marker's cell. Taken in a seeded
    order from the series of ``ids`` with such a line of which one of the
    newest three scrapes was missed: the rack, read at four steps an
    interval apart of which one lies halfway between the two stamps.
    Prometheus has no sample there (the newest row is the marker); a
    store that stamps a hole by its line serves the scrape before. A
    container too small to hold such a series: a rack of ``lost_racks``
    (a member's newest scrape missed at one of the common steps), None
    where there is none either."""
    iv, per = _iv(deploy), int(deploy["labels"]["per_rack"])
    ids = np.asarray(ids)
    newest = np.arange(max(col - 2, 1), col + 1)
    word = datagen.fold_seed(seed)
    with np.errstate(over="ignore"):
        late0 = datagen.late(np, word, ids.astype(np.uint32),
                             np.uint32(0)).astype(np.int64)
    missed = datagen.miss_np(seed, ids, newest)
    known = set(ids.tolist()) if len(ids) < (1 << 12) else None
    for at in rng.permutation(np.flatnonzero((late0 > 0)
                                             & missed.any(axis=1))):
        sid, rack = int(ids[at]), int(ids[at]) // per
        members = np.arange(rack * per, rack * per + per)
        if not (np.isin(members, ids).all() if known is None
                else set(members.tolist()) <= known):
            continue
        k = int(newest[np.flatnonzero(missed[at])[-1]])
        x = int(datagen.stamps_np(seed, [sid], [k], iv)[0, 0]
                + late0[at] // 2)
        last = x + (end - x) // iv * iv
        steps = last - iv * np.arange(PROBE_STEPS - 1, -1, -1)
        probe = _count_by_rack(seed, rack, steps, col, deploy)
        if probe is not None:
            return probe
    steps = end - iv * np.arange(PROBE_STEPS - 1, -1, -1)
    for rack in lost_racks:
        return _count_by_rack(seed, rack, steps, col, deploy)
    return None


def _count_probe(seed: int, deploy: dict, rng) -> dict:
    """``sum(count_over_time(m{g="g<j>"}[5m]))`` of one seeded group, four
    steps an interval apart at a seeded place inside the filled history:
    integers below 2^24, so the answer has to be EXACT — and the selection
    is wide, so at the deployment's size the fused kernel makes it, from
    the validity of every cell. Checked here: a store that took its holes
    for samples would give other counts at every one of the steps."""
    iv, fill_cols = _iv(deploy), int(deploy["fill_columns"])
    groups = int(deploy["labels"]["groups"])
    g = int(rng.integers(groups))
    sids = np.arange(g, int(deploy["series"]), groups)
    k = int(rng.integers(max(fill_cols - 40, 1), fill_cols - 2))
    end = scrape_ms(k, deploy) + int(rng.integers(iv))
    steps = end - iv * np.arange(PROBE_STEPS - 1, -1, -1)
    spec = {"agg": "sum", "fn": "count_over_time",
            "window_s": COUNT_WINDOW_S, "by": ()}
    want = reference.evaluate(seed, sids, spec, steps, iv, fill_cols,
                              groups)[()]
    whole = reference.evaluate(seed, sids, spec, steps, iv, fill_cols,
                               groups, holes=False)[()]
    if not (want < whole).all():
        raise RuntimeError("prom_miss: the count probe's windows hold no "
                           "missed scrape")
    return {"promql": f'sum(count_over_time({deploy["metric"]}'
                      f'{{g="g{g}"}}[{COUNT_WINDOW_S // 60}m]))',
            "start_ms": int(steps[0]), "end_ms": int(steps[-1]),
            "step_ms": iv, "want": [({}, want)]}


# 6. the kernel's needed bytes: prom's (the marks ride in the residuals)

query_bytes = _prom.query_bytes
