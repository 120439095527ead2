"""``prom``: ``counter``'s series and values under the stamps a Prometheus
scraper makes — every series on its own scrape phase, a late scrape stamped
as it came — in a store that keeps stamps as a line plus a narrow residual.

The six points of ``benchmark/data/__init__.py`` for that kind of data:

- ``datagen.py``: ``stamp(s, k) = BASE_TS + interval * k + phase(s) +
  late(s, k)``, closed forms of (seed, s, k), the same integers from numpy
  and ``jax.numpy``; the values are ``counter``'s law, imported.
- ``fill.py``: columns ``1..fill-1`` written into ``st.val`` (values) and
  ``st.res`` (int8 residuals) by donated elementwise programs, scrape 0
  having set each row's line through the write path; landed =
  ``n_host[row] > col``.
- ``reference.py``: ``counter``'s functions and aggregates, numpy f64, with
  window membership and ``extrapolatedRate``'s durations from each sample's
  true stamp.
- ``kernelbytes.py``: a query's needed bytes at 4-byte values and 1-byte
  residuals.

``scrape_ms(k)`` is the NOMINAL stamp of scrape ``k``: every stamp of it
lies in ``[scrape_ms(k), scrape_ms(k) + interval + 63)``, so no query that
ends at or before ``scrape_ms(fill_columns)`` sees a sample of the window's
live ingest. Keys read from the configuration and of a mix's ``ref``: as
``counter``.
"""

from __future__ import annotations

import numpy as np

from benchmark.data import counter as _counter

from . import datagen, kernelbytes, reference
from . import fill as _fill


def _iv(deploy: dict) -> int:
    return int(deploy["scrape_interval_ms"])


# 1. series: counter's, as they are

schema = _counter.schema
series_labels = _counter.series_labels


# 2. a scrape

def scrape_ms(k: int, deploy: dict) -> int:
    return datagen.BASE_TS + int(k) * _iv(deploy)


def scrape(seed: int, ids, k: int, deploy: dict) -> dict:
    vals = datagen.counter_np(seed, ids, [k])[:, 0]
    return {"ts": datagen.stamps_np(seed, ids, [k], _iv(deploy))[:, 0],
            "values": np.ascontiguousarray(vals, np.float64)}


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


landed = _counter.landed


# 4. the plain reference

def evaluate(seed: int, sids, ref: dict, out_ts, deploy: dict, head_col: int,
             values=None) -> dict:
    return reference.evaluate(seed, sids, ref, out_ts, _iv(deploy), head_col,
                              int(deploy["labels"]["groups"]), values=values)


def raw_values(seed: int, sids, cols, deploy: dict) -> np.ndarray:
    return reference.raw_values(seed, sids, cols)


# 5. the read-back probe

PROBE_STEPS = 4


def probes(seed: int, ids, col: int, deploy: dict, n: int) -> list[dict]:
    """``n`` racks with a series in ``ids``, each read twice over four
    steps an interval apart: ``m{rack="r<n>"}`` must return the VALUES of
    the samples an instant selector holds at those steps, and
    ``timestamp(m{rack="r<n>"})`` their STAMPS, both exactly. The last step
    is ``scrape_ms(col) + interval + 62``, the latest a sample of scrape
    ``col`` — the newest that has landed — can be stamped, so every
    series' newest sample is read; which scrape each earlier step holds
    (a series' phase and lateness decide) is the reference's to say.
    Of a rack's series only those in ``ids`` are wanted.

    The racks are taken in a seeded order, and only those of which a
    probed step holds a sample OFF its row's line (``late(s, k)`` is not
    ``late(s, 0)``, the lateness of the scrape that set the line: the law
    says so in closed form). A store that kept the line and lost the
    residual then misses the stamps of EVERY run's probes, not of nine
    runs in ten. Last comes a probe the fused kernel answers
    (:func:`_count_probe`)."""
    iv = _iv(deploy)
    per = int(deploy["labels"]["per_rack"])
    ids = np.asarray(ids)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x4EAD])
    end = scrape_ms(col, deploy) + iv + datagen.LATE_MAX - 1
    steps = end - iv * np.arange(PROBE_STEPS - 1, -1, -1)
    word = datagen.fold_seed(seed)
    out, seen = [], set()
    for sid in rng.permutation(ids):
        rack = int(sid) // per
        if rack in seen:
            continue
        seen.add(rack)
        want_ids = np.intersect1d(np.arange(rack * per, rack * per + per),
                                  ids)
        held = reference.last_scrape(seed, want_ids, steps, iv, col)
        s = want_ids.astype(np.uint32)[:, None]
        with np.errstate(over="ignore"):
            off = (datagen.late(np, word, s, held.astype(np.uint32))
                   != datagen.late(np, word, s, np.uint32(0)))
        if not (off & (held >= 0)).any():
            continue
        want_ids = want_ids.tolist()
        vals = np.stack([reference.raw_values(seed, [i], held[j])[0]
                         for j, i in enumerate(want_ids)])
        stamps = np.stack([datagen.stamps_np(seed, [i], held[j], iv)[0]
                           for j, i in enumerate(want_ids)])
        sel = f'{deploy["metric"]}{{rack="r{rack}"}}'
        for promql, want in ((sel, vals),
                             (f"timestamp({sel})", stamps / 1000.0)):
            out.append({"promql": promql, "start_ms": int(steps[0]),
                        "end_ms": int(steps[-1]), "step_ms": iv,
                        "want": [({"host": f"h{i}"}, want[j])
                                 for j, i in enumerate(want_ids)]})
        if len(out) == 2 * n:
            break
    out.append(_count_probe(seed, deploy, rng))
    return out


COUNT_WINDOW_S = 300


def _count_probe(seed: int, deploy: dict, rng) -> dict:
    """``sum(count_over_time(m{g="g<j>"}[5m]))`` of one seeded group, four
    steps an interval apart inside the filled history: an integer below
    2^24, so the answer has to be EXACT — and the selection is wide, so at
    the deployment's size the fused line kernel makes it, deciding every
    window's edge cells from line + residual. The last step is put, by the
    law, 1 ms before the later of a late sample's true stamp and its
    line's: a kernel (or a store) that reads the line alone counts that
    sample on the wrong side of the edge. Steps are tried until the
    group's counts from the line's stamps differ from the true ones."""
    iv, fill = _iv(deploy), int(deploy["fill_columns"])
    groups = int(deploy["labels"]["groups"])
    g = int(rng.integers(groups))
    sids = np.arange(g, int(deploy["series"]), groups)
    w = COUNT_WINDOW_S * 1000
    word = datagen.fold_seed(seed)
    ks = np.arange(max(fill - 40, 1), fill - 2)
    with np.errstate(over="ignore"):
        s32 = sids.astype(np.uint32)[:, None]
        late0 = datagen.late(np, word, s32, np.uint32(0)).astype(np.int64)

        def lates(cols):
            return datagen.late(np, word, s32, np.asarray(cols, np.uint32)[
                None, :]).astype(np.int64)

    def counts(stamps, steps):
        return ((stamps[:, :, None] >= steps - w)
                & (stamps[:, :, None] <= steps)).sum(axis=(0, 1))

    shifts = lates(ks) - late0          # a residual, where the row fits
    for i in rng.permutation(len(sids)):
        shift = shifts[i]
        for k in ks[shift != 0]:
            true = int(datagen.stamps_np(seed, sids[i:i + 1], [k], iv)[0, 0])
            end = max(true, true - int(shift[k - ks[0]])) - 1
            steps = end - iv * np.arange(PROBE_STEPS - 1, -1, -1)
            k0, k1 = reference.scrape_range(steps, w, iv, fill)
            cols = np.arange(k0, k1 + 1)
            stamps = datagen.stamps_np(seed, sids, cols, iv)
            if (counts(stamps, steps)
                    == counts(stamps - lates(cols) + late0, steps)).all():
                continue
            want = reference.evaluate(
                seed, sids, {"agg": "sum", "fn": "count_over_time",
                             "window_s": COUNT_WINDOW_S, "by": ()},
                steps, iv, fill, groups)[()]
            return {"promql": f'sum(count_over_time({deploy["metric"]}'
                              f'{{g="g{g}"}}[{COUNT_WINDOW_S // 60}m]))',
                    "start_ms": int(steps[0]), "end_ms": int(steps[-1]),
                    "step_ms": iv, "want": [({}, want)]}
    raise RuntimeError("prom: no step of the filled history tells the true "
                       "stamps from the line's")


# 6. the kernel's needed bytes

def query_bytes(rows: int, ref: dict, out_ts, deploy: dict, head_col: int,
                capacity: int) -> float:
    return kernelbytes.query_bytes(rows, out_ts, int(ref["window_s"]) * 1000,
                                   _iv(deploy), head_col, capacity)
