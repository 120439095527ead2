"""``sine``: a TEST DOUBLE of a data module, never a configuration of
BENCHMARK.json. It proves the harness open by use: the rehearsal dry-adds
it (with ``configs/sine_tiny.json`` and ``traffic/wave.json`` beside it) to
a scratch copy of the benchmark's by-name files and runs one cell-shaped
pass, and no line of ``run.py``, ``served.py``, ``load.py``, ``correct.py``
or ``traffic.py`` knows its name.

It differs from ``counter`` in each of the six points: another metric and
label set (``temp{sensor, zone, floor}``); a NON-monotone gauge with its own
closed form (a triangle wave of period 24 scrapes, a seeded phase and base
per series); stamps of its own (another epoch, off every multiple of the
interval); a history written through the program's write path, scrape by
scrape, and ``landed`` read from the store's ``last_ts`` mirror; a ``ref``
of other keys (``over``, ``pick``, ``split``, ``range_s``) for
``max|min [by (zone)] (max|min_over_time(temp[w]))``; a ``floor`` probe;
and its own byte count.
"""

from __future__ import annotations

import dataclasses

import numpy as np

EPOCH_MS = 1_600_000_007_000      # not a multiple of any scrape interval
PERIOD, SLOPE = 24, 3


def _iv(deploy: dict) -> int:
    return int(deploy["scrape_interval_ms"])


def _word(seed: int) -> int:
    seed = int(seed)
    return (seed ^ (seed >> 31) ^ 0x5BD1E995) & 0x7FFF_FFFF


def wave(seed: int, sids, cols) -> np.ndarray:
    """[len(sids), len(cols)] int64: base(s) + 3 |((k + phase(s)) % 24) - 12|."""
    s = np.asarray(sids, np.int64)[:, None]
    k = np.asarray(cols, np.int64)[None, :]
    h = (s * 2654435761 + _word(seed)) % (1 << 31)
    return 100 + (h >> 7) % 1000 + SLOPE * np.abs((k + h % PERIOD) % PERIOD
                                                   - PERIOD // 2)


# 1. series

def schema():
    from filodb_tpu.core.schemas import GAUGE
    return GAUGE


def series_labels(ids, deploy: dict) -> dict:
    zones, per = int(deploy["zones"]), int(deploy["sensors_per_floor"])
    return {"_metric_": deploy["metric_name"],
            "sensor": [f"s{i}" for i in ids],
            "zone": [f"z{i % zones}" for i in ids],
            "floor": [f"f{i // per}" for i in ids]}


# 2. a scrape

def scrape_ms(k: int, deploy: dict) -> int:
    return EPOCH_MS + int(k) * _iv(deploy)


def scrape(seed: int, ids, k: int, deploy: dict) -> dict:
    return {"ts": np.full(len(ids), scrape_ms(k, deploy), np.int64),
            "values": wave(seed, ids, [k])[:, 0].astype(np.float64)}


# 3. the history on the device: through the write path, scrape by scrape

def fill(shard, sid, seed: int, deploy: dict) -> None:
    from filodb_tpu.core.record import RecordBuilder
    ids = sid[sid >= 0]
    b = RecordBuilder(schema())
    b.add_series_batch(series_labels(ids, deploy), scrape_ms(0, deploy), 0.0)
    template = b.build()
    for k in range(1, int(deploy["fill_columns"])):
        shard.ingest(dataclasses.replace(template,
                                         **scrape(seed, ids, k, deploy)))
    shard.flush()


def check_filled(shard, sid, deploy: dict) -> set:
    import jax
    st = shard.store
    jax.block_until_ready(st.n)
    live = np.flatnonzero(sid >= 0)
    last = int(deploy["fill_columns"]) - 1
    if not (landed(shard, live, last).all()
            and not landed(shard, live, last + 1).any()
            and int(np.asarray(st.n).sum()) == len(live) * (last + 1)):
        raise RuntimeError(f"shard {shard.shard_num}: the history did not "
                           f"land: last_ts {np.unique(st.last_ts[live])}")
    return set(st.val.devices())


def landed(shard, row, col: int):
    return shard.store.last_ts[row] >= EPOCH_MS + col * shard.store.grid_interval


# 4. the plain reference

def evaluate(seed: int, sids, ref: dict, out_ts, deploy: dict, head_col: int,
             values=None) -> dict:
    """``pick [by (zone)] (over_over_time(temp[range_s]))`` with ``pick``
    and ``over`` each ``max`` or ``min``, closed windows [t - w, t]."""
    sids = np.asarray(sids, np.int64)
    iv, w = _iv(deploy), 1000 * int(ref["range_s"])
    cols = np.arange(head_col + 1)
    v = (values(sids, cols) if values is not None
         else wave(seed, sids, cols).astype(np.float64))
    over = {"max": np.max, "min": np.min}[ref["over"]]
    pick = {"max": np.max, "min": np.min}[ref["pick"]]
    t = np.asarray(out_ts, np.int64) - EPOCH_MS
    lo = np.maximum(-((-(t - w)) // iv), 0)
    hi = np.minimum(t // iv, head_col)
    per = np.full((len(sids), len(t)), np.nan)
    for j in range(len(t)):
        if hi[j] >= lo[j]:
            per[:, j] = over(v[:, lo[j]:hi[j] + 1], axis=1)
    if not ref.get("split"):
        return {(): pick(per, axis=0)}
    zone = sids % int(deploy["zones"])
    return {(("zone", f"z{z}"),): pick(per[zone == z], axis=0)
            for z in np.unique(zone)}


def raw_values(seed: int, sids, cols, deploy: dict) -> np.ndarray:
    return wave(seed, sids, cols).astype(np.float64)


# 5. the read-back probe: a floor's sensors over the three newest scrapes

def probes(seed: int, ids, col: int, deploy: dict, n: int) -> list[dict]:
    per = int(deploy["sensors_per_floor"])
    ids = np.asarray(ids)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0xF100])
    floors = rng.choice(np.unique(ids // per), n, replace=False)
    cols = np.arange(col - 2, col + 1)
    out = []
    for fl in floors.tolist():
        mine = ids[ids // per == fl]
        want = raw_values(seed, mine, cols, deploy)
        out.append({"promql": f'{deploy["metric_name"]}{{floor="f{fl}"}}',
                    "start_ms": scrape_ms(cols[0], deploy),
                    "end_ms": scrape_ms(cols[-1], deploy),
                    "step_ms": _iv(deploy),
                    "want": [({"sensor": f"s{i}"}, want[j])
                             for j, i in enumerate(mine.tolist())]})
    return out


# 6. the kernel's needed bytes: every column a window touches, 4-byte values

def query_bytes(rows: int, ref: dict, out_ts, deploy: dict, head_col: int,
                capacity: int) -> float:
    t = np.asarray(out_ts, np.int64) - EPOCH_MS
    lo = max(int(-((-(t[0] - 1000 * int(ref["range_s"]))) // _iv(deploy))), 0)
    hi = min(int(t[-1] // _iv(deploy)), head_col, capacity - 1)
    return float(rows * max(hi - lo + 1, 0) * 4)
