"""``tsbs_cpu_d8``: TSBS DevOps ``cpu-only`` held as one BYTE a sample — the
store's delta8 form with stamps elided, born narrow and kept narrow under
live ingest (``store.compressed_residency: gauge``).

What the data IS is ``tsbs_cpu``'s, imported and not copied: the series and
their eleven labels, the value law (a clamped integer walk in [0, 100], so a
sample's delta lies in [-3, 3]), the exact 10 s grid, a scrape, the numpy
f64 reference over the series a ``ref`` names. What is this module's is how
the history gets into a store that holds no f32 and no s64 block
(``fill.py``: the walk's DELTAS written straight into the int8 block), what
a filled store has to look like, the bytes a narrow read needs of it, and
probes that reach over the whole depth: a wrong anchor shifts a row's every
sample, a lost delta every sample after it, and an append that missed its
column the newest one, so each probe reads the history's FIRST hour and the
scrape that has just landed.

A program without the narrow-born store cannot hold this deployment at all
(raw it is 58 GB): the module refuses it when it is loaded, before a server
is started or a device touched.

Keys read from the configuration: ``series``, ``scrape_interval_ms``,
``fill_columns``, ``containers_per_scrape``. Keys of a mix's ``ref``: as
``tsbs_cpu``.
"""

from __future__ import annotations

import inspect

import numpy as np

from .. import tsbs_cpu as base
from ..tsbs_cpu import (evaluate, raw_values, schema, scrape,  # noqa: F401
                        scrape_ms, series_labels, text_of)
from ..tsbs_cpu import datagen, reference

PROBE_STEPS = 4
FIRST_HOUR_COLS = 360


def _refuse_a_program_without_the_form() -> None:
    from filodb_tpu.core.chunkstore import SeriesStore
    if "born_narrow" not in inspect.signature(SeriesStore.__init__).parameters:
        raise SystemExit(
            "benchmark: data module 'tsbs_cpu_d8' needs a store that is "
            "born in its delta8 form and appended to in place "
            "(SeriesStore(born_narrow=...)); this program builds the raw "
            "f32 + s64 blocks first, 58 GB at 2^20 x 4,608: refused before "
            "a server is started")


_refuse_a_program_without_the_form()

from . import fill as _fill  # noqa: E402


def _iv(deploy: dict) -> int:
    return int(deploy["scrape_interval_ms"])


# 3. the history on the device

def fill(shard, sid, seed: int, deploy: dict) -> None:
    last = _fill.fill_history(shard, sid, seed, int(deploy["fill_columns"]),
                              _iv(deploy))
    # the scraper's state a container, from the fill's last column: the
    # next scrape is one step of the walk on, not fill_columns of them
    n = int(deploy["series"])
    chunk = -(-n // int(deploy["containers_per_scrape"]))
    row_of = np.full(n, -1, np.int64)
    rows = np.flatnonzero(sid >= 0)
    row_of[sid[rows]] = rows
    for lo in range(0, n, chunk):
        mine = np.arange(lo, min(lo + chunk, n))
        mine = mine[row_of[mine] >= 0]
        if len(mine):
            base._WALKS[(int(seed), int(mine[0]), len(mine))] = (
                int(deploy["fill_columns"]) - 1, last[row_of[mine]], mine)


def check_filled(shard, sid, deploy: dict) -> set:
    return _fill.check_filled(shard, sid, int(deploy["fill_columns"]),
                              _iv(deploy))


def landed(shard, row, col: int):
    return shard.store.n_host[row] > col


# 5. the read-back probes

def probe_cols(rng, col: int) -> np.ndarray:
    """Four scrapes a probe reads: one drawn in the history's first hour,
    the landed one ``col``, two evenly between."""
    c0 = int(rng.integers(1, FIRST_HOUR_COLS - 3))
    c0 += (col - c0) % (PROBE_STEPS - 1)
    return np.linspace(c0, col, PROBE_STEPS).astype(np.int64)


def probes(seed: int, ids, col: int, deploy: dict, n: int) -> list[dict]:
    """``tsbs_cpu``'s three kinds of probe, each over the store's whole
    depth — four steps of which the first lies in the history's first hour
    and the last is scrape ``col``, the one that has just landed:

    (a) ``n`` seeded hosts of the container, each read as
        ``cpu_usage_idle{hostname="host_<h>"}`` — the VALUES — and as
        ``timestamp(..)`` of the same — the STAMPS, derived from the row's
        first one — both exactly;
    (b) ``tsbs_cpu``'s matcher on two drawn tags, the counts an os;
    (c) the timed path's own leaf, ``max(max_over_time(cpu_usage_user{
        hostname=~"<8 hosts>"}[1m]))``: decoded inside the leaf's one
        program, the stored integers exactly."""
    iv = _iv(deploy)
    ids = np.asarray(ids, np.int64)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xD8D8])
    cols = probe_cols(rng, col)
    span = {"start_ms": scrape_ms(cols[0], deploy),
            "end_ms": scrape_ms(cols[-1], deploy),
            "step_ms": int(cols[1] - cols[0]) * iv}
    out = []
    idle = datagen.FIELDS.index("usage_idle")
    hosts = base._hosts_with(ids, idle)
    for h in rng.choice(hosts, min(n, len(hosts)), replace=False).tolist():
        want = reference.raw_values(seed, [datagen.NF * h + idle], cols)[0]
        stamps = (datagen.BASE_TS + cols * iv) / 1000.0
        sel = f'{datagen.METRICS[idle]}{{hostname="host_{h}"}}'
        for promql, w in ((sel, want), (f"timestamp({sel})", stamps)):
            out.append({"promql": promql, **span,
                        "want": [({"hostname": f"host_{h}"}, w)]})
    users = base._hosts_with(ids, 0)
    out.append(base._tag_probe(int(rng.choice(users)), deploy, span))
    picked = np.sort(rng.choice(users, min(base.PROBE_HOSTS, len(users)),
                                replace=False))
    ref = {"agg": "max", "fn": "max_over_time",
           "window_s": base.PROBE_WINDOW_S, "metric": datagen.METRICS[0],
           "hosts": picked.tolist()}
    steps = datagen.BASE_TS + cols * iv
    want = reference.evaluate(seed, ids, ref, steps, iv, col)[()]
    out.append({"promql": text_of(ref), **span, "want": [({}, want)]})
    return out


# 6. the gather's needed bytes

def query_bytes(rows: int, ref: dict, out_ts, deploy: dict, head_col: int,
                capacity: int) -> float:
    """Selected rows x the columns the decode reads x 1 B, + 12 B a row
    (anchor, count, pool slot): a delta row is summed from its first cell
    to the newest one a window touches."""
    lo, hi = reference.window_cells(out_ts, int(ref["window_s"]) * 1000,
                                    _iv(deploy), min(head_col, capacity - 1))
    ok = hi >= lo
    if not ok.any():
        return 0.0
    return float(len(ref["hosts"]) * (int(hi[ok].max()) + 1 + 12))
