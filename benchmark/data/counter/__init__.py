"""``counter``: one gauge-schema metric of monotone integer counters on an
exact scrape grid, in a raw f32 + s64 resident store.

The six points of ``benchmark/data/__init__.py`` for that kind of data;
the bodies are the harness's first generator, fill, reference and byte
count, moved here file by file:

- ``datagen.py``: ``v(s, c) = start(s) + 64 c + h(seed, s, c)``, the same
  integers from numpy and ``jax.numpy``; stamps ``BASE_TS + k * interval``
  exactly.
- ``fill.py``: columns ``1..fill-1`` written into ``st.val`` / ``st.ts`` /
  ``st.n`` by donated elementwise programs; landed = ``n_host[row] > col``.
- ``reference.py``: five window functions, five aggregates, ``by`` in
  ``()`` or ``(g)`` with ``g = series % groups``, numpy f64, no resets.
- ``kernelbytes.py``: a query's needed bytes at 4-byte values.

Keys read from the configuration: ``metric``, ``labels.groups``,
``labels.per_rack``, ``scrape_interval_ms``, ``fill_columns``. Keys of a
mix's ``ref``: ``agg``, ``fn``, ``window_s``, ``by``.
"""

from __future__ import annotations

import numpy as np

from . import datagen, kernelbytes, reference
from . import fill as _fill


def _iv(deploy: dict) -> int:
    return int(deploy["scrape_interval_ms"])


# 1. series

def schema():
    from filodb_tpu.core.schemas import GAUGE
    return GAUGE


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
    vals = datagen.counter_np(seed, ids, [k])[:, 0]
    return {"ts": np.full(len(ids), scrape_ms(k, deploy), np.int64),
            "values": np.ascontiguousarray(vals, np.float64)}


# 3. the history on the device

def fill(shard, sid, seed: int, deploy: dict) -> None:
    _fill.fill_history(shard, sid, seed, int(deploy["fill_columns"]),
                       _iv(deploy))


def check_filled(shard, sid, deploy: dict) -> set:
    _fill.check_filled(shard, sid, int(deploy["fill_columns"]), _iv(deploy))
    st = shard.store
    if st.ts.devices() != st.val.devices():
        raise RuntimeError(f"shard {shard.shard_num}: ts/val on two devices")
    return set(st.val.devices())


def landed(shard, row, col: int):
    return shard.store.n_host[row] > col


# 4. the plain reference

def evaluate(seed: int, sids, ref: dict, out_ts, deploy: dict, head_col: int,
             values=None) -> dict:
    return reference.evaluate(seed, sids, ref, out_ts, _iv(deploy), head_col,
                              int(deploy["labels"]["groups"]), values=values)


def raw_values(seed: int, sids, cols, deploy: dict) -> np.ndarray:
    return reference.raw_values(seed, sids, cols)


# 5. the read-back probe

def probes(seed: int, ids, col: int, deploy: dict, n: int) -> list[dict]:
    """``n`` seeded racks with a series in ``ids``, each read through
    ``m{rack="r<n>"}`` over the four newest scrapes. Of a rack's series
    only those in ``ids`` are wanted: on a mesh the others live on other
    shards, whose containers of this scrape may not have been sent yet."""
    iv = _iv(deploy)
    per = int(deploy["labels"]["per_rack"])
    ids = np.asarray(ids)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x4EAD])
    cols = np.arange(col - 3, col + 1)
    out = []
    for sid in rng.choice(ids, n, replace=False):
        rack = int(sid) // per
        want_ids = np.intersect1d(np.arange(rack * per, rack * per + per),
                                  ids).tolist()
        want = reference.raw_values(seed, want_ids, cols)
        out.append({"promql": f'{deploy["metric"]}{{rack="r{rack}"}}',
                    "start_ms": scrape_ms(cols[0], deploy),
                    "end_ms": scrape_ms(cols[-1], deploy), "step_ms": iv,
                    "want": [({"host": f"h{i}"}, want[j])
                             for j, i in enumerate(want_ids)]})
    return out


# 6. the kernel's needed bytes

def query_bytes(rows: int, ref: dict, out_ts, deploy: dict, head_col: int,
                capacity: int) -> float:
    return kernelbytes.query_bytes(rows, out_ts, int(ref["window_s"]) * 1000,
                                   _iv(deploy), head_col, capacity)
