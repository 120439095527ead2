"""``hist``: native 64-bucket histograms (``prom-histogram``: ``sum``,
``count``, ``h``) on an exact scrape grid, in a raw f32 ``[S, C, B]`` store.

The six points of ``benchmark/data/__init__.py`` for that kind of data:

- ``datagen.py``: bucket ``b`` of series ``s`` is a counter of its own,
  ``c(s, b, k)``, a closed form of (seed, series, scrape, bucket) that
  grows by 0..31 a scrape at a rate the series and the bucket set and that
  doubles up in the series' busy stretches; a scrape carries the buckets
  cumulated, ``count`` (the top bucket) and ``sum`` (a law of its own), all
  integers below 2**24, the same from numpy and ``jax.numpy``; stamps
  ``BASE_TS + k * interval`` exactly; 63 finite bounds + Inf.
- ``fill.py``: scrapes ``1..fill-1`` written into ``st.val [S, C, B]``,
  ``st.extra["sum" | "count"]``, ``st.ts``, ``st.n`` in donated row blocks;
  landed = ``n_host[row] > col``.
- ``reference.py``: ``histogram_quantile(q, sum [by (g)] (rate | increase |
  delta (h[w])))`` in numpy f64: per-bucket extrapolated rate, bucket-wise
  group sum, Prometheus's quantile; ``g = series % groups``.
- ``kernelbytes.py``: a query's needed bytes: rows x the windows' columns x
  64 buckets x 4 B (the kernel slices columns).

Keys read from the configuration: ``metric``, ``buckets``,
``labels.groups``, ``labels.per_rack``, ``scrape_interval_ms``,
``fill_columns``. Keys of a mix's ``ref``: ``q``, ``fn``, ``window_s``,
``by``.
"""

from __future__ import annotations

import numpy as np

from . import datagen, kernelbytes, reference
from . import fill as _fill


def _iv(deploy: dict) -> int:
    return int(deploy["scrape_interval_ms"])


def _nb(deploy: dict) -> int:
    return int(deploy["buckets"])


# 1. series

def schema():
    from filodb_tpu.core.schemas import PROM_HISTOGRAM
    return PROM_HISTOGRAM


def series_labels(ids, deploy: dict) -> dict:
    g, per_rack = deploy["labels"]["groups"], deploy["labels"]["per_rack"]
    return {"_metric_": deploy["metric"],
            "host": [f"h{i}" for i in ids],
            "g": [f"g{i % g}" for i in ids],
            "rack": [f"r{i // per_rack}" for i in ids]}


# 2. a scrape

def scrape_ms(k: int, deploy: dict) -> int:
    return datagen.BASE_TS + int(k) * _iv(deploy)


def scrape(seed: int, ids, k: int, deploy: dict) -> dict:
    """A container's ``values`` is ``[n, 2 + buckets]`` in the schema's
    ``col_layout`` order — ``sum``, ``count``, the cumulative buckets — and
    it carries the bounds."""
    nb = _nb(deploy)
    su, cn, h = datagen.columns_np(seed, ids, [k], nb)
    return {"ts": np.full(len(ids), scrape_ms(k, deploy), np.int64),
            "values": np.concatenate([su, cn, h[:, 0]], axis=1),
            "bucket_les": datagen.bucket_les(nb)}


# 3. the history on the device

def fill(shard, sid, seed: int, deploy: dict) -> None:
    _fill.fill_history(shard, sid, seed, int(deploy["fill_columns"]),
                       _iv(deploy))


def check_filled(shard, sid, deploy: dict) -> set:
    _fill.check_filled(shard, sid, int(deploy["fill_columns"]), _iv(deploy),
                       _nb(deploy))
    st = shard.store
    homes = {frozenset(a.devices())
             for a in (st.ts, st.val, *st.extra.values())}
    if len(homes) != 1:
        raise RuntimeError(f"shard {shard.shard_num}: its blocks sit on "
                           f"{len(homes)} sets of devices")
    return set(st.val.devices())


def landed(shard, row, col: int):
    return shard.store.n_host[row] > col


# 4. the plain reference

def evaluate(seed: int, sids, ref: dict, out_ts, deploy: dict, head_col: int,
             values=None) -> dict:
    return reference.evaluate(seed, sids, ref, out_ts, _iv(deploy), head_col,
                              int(deploy["labels"]["groups"]), _nb(deploy),
                              values=values)


def raw_values(seed: int, sids, cols, deploy: dict) -> np.ndarray:
    """``float64[n, len(cols), buckets]``: the cumulative buckets, what a
    raw selector returns of the ``h`` column (and what ``evaluate``'s
    ``values`` replaces)."""
    return datagen.columns_np(seed, sids, cols, _nb(deploy))[2]


# 5. the read-back probe

def probes(seed: int, ids, col: int, deploy: dict, n: int) -> list[dict]:
    """``n`` seeded racks with a series in ``ids``, read over the four
    newest scrapes, in turn through one finite bucket —
    ``histogram_bucket(le, h{rack="r<n>"})``, the bound seeded among those
    the rack's series have counts in — and through the ``count`` column,
    ``h{rack="r<n>", __col__="count"}``. Of a rack's series only those in
    ``ids`` are wanted (``counter``'s rule)."""
    iv, nb = _iv(deploy), _nb(deploy)
    per = int(deploy["labels"]["per_rack"])
    metric = deploy["metric"]
    ids = np.asarray(ids)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x4157])
    cols = np.arange(col - 3, col + 1)
    les = datagen.bucket_les(nb)
    out = []
    for i, sid in enumerate(rng.choice(ids, n, replace=False)):
        rack = int(sid) // per
        want_ids = np.intersect1d(np.arange(rack * per, rack * per + per),
                                  ids).tolist()
        _su, cn, h = datagen.columns_np(seed, want_ids, cols, nb)
        if i % 2 == 0:
            b = int(rng.integers(8, nb - 8))
            promql = f'histogram_bucket({float(les[b])!r}, {metric}{{rack="r{rack}"}})'
            want = h[:, :, b]
        else:
            promql = f'{metric}{{rack="r{rack}",__col__="count"}}'
            want = cn
        out.append({"promql": promql,
                    "start_ms": scrape_ms(cols[0], deploy),
                    "end_ms": scrape_ms(cols[-1], deploy), "step_ms": iv,
                    "want": [({"host": f"h{s}"}, want[j])
                             for j, s in enumerate(want_ids)]})
    return out


# 6. the kernel's needed bytes

def query_bytes(rows: int, ref: dict, out_ts, deploy: dict, head_col: int,
                capacity: int) -> float:
    return kernelbytes.query_bytes(rows, out_ts, int(ref["window_s"]) * 1000,
                                   _iv(deploy), head_col, capacity,
                                   _nb(deploy))
