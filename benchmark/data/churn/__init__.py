"""``churn``: ``counter``'s metric on ``counter``'s exact grid, scraped off
a fleet that redeploys — VictoriaMetrics' ``prometheus-benchmark`` law: every
``scrapeConfigUpdateInterval`` a share ``scrapeConfigUpdatePercent`` of the
targets gets a new ``revision`` label, so every series of such a target
ends and as many new ones begin.

What the data IS is ``counter``'s, imported and not copied: the value law
(``datagen.counter``: of a series' own id and AGE here — a new process's
counters start over), ``scrape_ms`` and the kernel's needed bytes
(``kernelbytes``; the kernel streams every row's cells whether or not the
row lived in them, so the needed bytes are the block's, as ``counter``'s).
What is this module's: the labels (``counter``'s ``host`` / ``g`` / ``rack``
of the SLOT, + ``instance="t<slot // per_target>"`` and ``revision``), the
update law (``law.py``: births, ends, the seeded draws), the fill through
the shard's own ingest (``fill.py``), the checks, the probes
(``probes.py``) and the plain reference over series that are born and end
(``reference.py``).

The harness's ``series`` are SLOTS here (a target's metric position): a
container keeps its row count while its label sets change. ``scrape``
returns the revised containers' key fields beside stamps and values from
the first event on (``Writer.publish`` passes them through
``dataclasses.replace``), built once a generation with
``RecordBuilder.add_series_batch`` and kept; ``landed`` follows a slot to
the row that holds its current generation.

A program whose store has no birth cells answers such a store through its
minority path, two orders of magnitude off (PERF.md §6, PR 49): the module
refuses it when it is loaded, before a server is started or a device
touched.

Keys read from the configuration: ``series``, ``metric``, ``labels.groups``,
``labels.per_rack``, ``scrape_interval_ms``, ``fill_columns``,
``containers_per_scrape``, ``server.store.max_series_per_shard``, ``churn``.
Keys of a mix's ``ref``: ``counter``'s.
"""

from __future__ import annotations

import numpy as np

from ..counter import datagen, kernelbytes, scrape_ms, schema  # noqa: F401


def _refuse_a_program_without_birth_cells() -> None:
    from filodb_tpu.core import chunkstore
    if not hasattr(chunkstore.SeriesStore, "born_dev"):
        raise SystemExit(
            "benchmark: data module 'churn' needs a store that keeps its "
            "grid in time-aligned cells with a birth cell a row "
            "(SeriesStore.born_dev); this program holds a late-born series "
            "from column 0 and answers it through the minority path "
            "(~110,000 rows a query at this deployment's rate): refused "
            "before a server is started")


_refuse_a_program_without_birth_cells()

from . import fill as _fill  # noqa: E402
from . import law, reference  # noqa: E402
from . import probes as _probes  # noqa: E402

_STATE: dict = {}       # id(shard) -> (Schedule, seed): what ``landed`` needs


def _iv(deploy: dict) -> int:
    return int(deploy["scrape_interval_ms"])


# 1. series

def labels_of(slots, revs, deploy: dict) -> dict:
    """The label sets of the series (slot, revision)."""
    g, per_rack = deploy["labels"]["groups"], deploy["labels"]["per_rack"]
    per = int(deploy["churn"]["series_per_target"])
    return {"_metric_": deploy["metric"],
            "host": [f"h{i}" for i in slots],
            "g": [f"g{i % g}" for i in slots],
            "rack": [f"r{i // per_rack}" for i in slots],
            "instance": [f"t{i // per}" for i in slots],
            "revision": [str(int(v)) for v in revs]}


def series_labels(ids, deploy: dict) -> dict:
    """Scrape 0's: every slot at revision 0."""
    ids = list(ids)
    return labels_of(ids, [0] * len(ids), deploy)


# 2. a scrape

_GENERATIONS: dict = {}


def _key_fields(sched, seed: int, ids, e: int, deploy: dict) -> dict:
    """The key fields of the container ``ids`` after update event ``e``:
    built once a generation, kept (what a producer memoizes)."""
    key = (sched.plan, int(seed), int(ids[0]), len(ids), e)
    got = _GENERATIONS.get(key)
    if got is None:
        from filodb_tpu.core.record import RecordBuilder
        tgt = np.asarray(ids, np.int64) // sched.plan.per_target
        b = RecordBuilder(schema())
        b.add_series_batch(labels_of(ids, sched.rev_after[e][tgt], deploy),
                           scrape_ms(0, deploy), 0.0)
        rc = b.build()
        got = {f: getattr(rc, f) for f in
               ("part_hash", "part_idx", "label_sets", "part_keys",
                "set_hashes", "label_columns")}
        for k in [k for k in _GENERATIONS if k[:4] == key[:4]]:
            del _GENERATIONS[k]         # one generation a container
        _GENERATIONS[key] = got
    return got


def scrape(seed: int, ids, k: int, deploy: dict) -> dict:
    sched = law.schedule(deploy, seed)
    ids = np.asarray(ids, np.int64)
    rows = sched.rows_at(ids, k)
    out = {"ts": np.full(len(ids), scrape_ms(k, deploy), np.int64),
           "values": np.ascontiguousarray(
               sched.values(seed, rows, [k])[:, 0], np.float64)}
    e = sched.event_of(k)
    if e and sched.rev_after[e][ids // sched.plan.per_target].any():
        out.update(_key_fields(sched, seed, ids, e, deploy))
    return out


# 3. the history on the device

def fill(shard, sid, seed: int, deploy: dict) -> None:
    sched = law.schedule(deploy, seed)
    live = np.flatnonzero(sid >= 0)
    if len(live) != sched.plan.slots or (sid[live] != live).any():
        raise RuntimeError("churn: one shard whose row i holds slot i")
    _STATE[id(shard)] = (sched, int(seed))
    _fill.register_events(shard, sched, seed, deploy, labels_of, scrape_ms,
                          schema())
    _fill.fill_history(shard, sched, seed, _iv(deploy))


def check_filled(shard, sid, deploy: dict) -> set:
    sched, _seed = _STATE[id(shard)]
    _fill.check_filled(shard, sched, _iv(deploy))
    st = shard.store
    if st.ts.devices() != st.val.devices():
        raise RuntimeError(f"shard {shard.shard_num}: ts/val on two devices")
    return set(st.val.devices())


def landed(shard, row, col: int):
    """``row``: slot(s). Followed to the row of the generation alive at
    scrape ``col``."""
    sched, _seed = _STATE[id(shard)]
    return shard.store.n_host[sched.rows_at(row, col)] > col


# 4. the plain reference

def evaluate(seed: int, sids, ref: dict, out_ts, deploy: dict, head_col: int,
             values=None) -> dict:
    return reference.evaluate(law.schedule(deploy, seed), seed, sids, ref,
                              out_ts, _iv(deploy), head_col,
                              int(deploy["labels"]["groups"]), values=values)


def raw_values(seed: int, sids, cols, deploy: dict) -> np.ndarray:
    """Of the slots ``sids``: the sample of the generation alive at each
    scrape."""
    sched = law.schedule(deploy, seed)
    cols = np.asarray(cols, np.int64)
    return np.stack([sched.values(seed, sched.rows_at(sids, k), [k])[:, 0]
                     for k in cols], axis=1)


# 5. the read-back probe

def probes(seed: int, ids, col: int, deploy: dict, n: int) -> list[dict]:
    return _probes.probes(law.schedule(deploy, seed), seed, ids, col, deploy,
                          n, scrape_ms)


# 6. the kernel's needed bytes

def query_bytes(rows: int, ref: dict, out_ts, deploy: dict, head_col: int,
                capacity: int) -> float:
    return kernelbytes.query_bytes(rows, out_ts, int(ref["window_s"]) * 1000,
                                   _iv(deploy), head_col, capacity)


def born_late_share(seed: int, deploy: dict, ends_back_ms):
    """What ``born_late_rows_pct`` should read for a deck whose cards end
    ``ends_back_ms`` before the head (the first live scrape): 100 x the
    late-born rows a card selects (a card's time mask selects the series
    that started at or before its end) over the store's rows, meaned over
    the cards."""
    sched = law.schedule(deploy, seed)
    p = sched.plan
    head = scrape_ms(p.fill_cols, deploy)
    born_ms = datagen.BASE_TS + sched.born.astype(np.int64) * _iv(deploy)
    late = sched.born > 0
    got = [int((late & (born_ms <= head - int(b))).sum())
           for b in ends_back_ms]
    return 100.0 * float(np.mean(got)) / p.rows
