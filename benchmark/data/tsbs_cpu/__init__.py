"""``tsbs_cpu``: TSBS DevOps ``cpu-only`` — ten integer cpu gauges a host
under ten tags, exact 10 s stamps — in a raw f32 + s64 resident store.

The six points of ``benchmark/data/__init__.py`` for that kind of data:

- ``datagen.py``: series ``s`` = field ``s % 10`` of host ``s // 10``, named
  ``cpu_<field>``; the ten tags a host (``hostname`` and nine drawn ones);
  the value law, a clamped integer WALK in [0, 100], the same integers from
  numpy and ``jax.numpy``; stamps ``BASE_TS + k * interval`` exactly.
- ``fill.py``: scrapes ``1..fill-1`` walked on the device in donated row
  blocks; landed = ``n_host[row] > col``.
- ``reference.py``: ``agg(fn(metric{hostname=~hosts}[w]))`` in numpy f64
  over the selected series alone; its brute-force twin is
  ``tests/tsbs_reference.py``.
- the probes (below): a host's raw samples and stamps, a matcher on two
  drawn tags, and the timed path's own leaf on eight hosts.

Keys read from the configuration: ``series``, ``scrape_interval_ms``,
``fill_columns``. Keys of a mix's ``ref``: ``agg``, ``fn``, ``window_s``,
``metric``, ``hosts``.
"""

from __future__ import annotations

import numpy as np

from . import datagen, reference
from . import fill as _fill

PROBE_STEPS = 4
PROBE_HOSTS = 8
PROBE_WINDOW_S = 60


def _iv(deploy: dict) -> int:
    return int(deploy["scrape_interval_ms"])


# 1. series

def schema():
    from filodb_tpu.core.schemas import GAUGE
    return GAUGE


def series_labels(ids, deploy: dict) -> dict:
    ids = np.asarray(list(ids), np.int64)
    labels = {"_metric_": [datagen.METRICS[f] for f in ids % datagen.NF]}
    labels.update(datagen.tag_strings(ids // datagen.NF))
    return labels


# 2. a scrape

def scrape_ms(k: int, deploy: dict) -> int:
    return datagen.BASE_TS + int(k) * _iv(deploy)


# a walk is sequential: the state of the containers last asked for is kept,
# so that the next scrape of a container is one step on and not k
_WALKS: dict = {}


def scrape(seed: int, ids, k: int, deploy: dict) -> dict:
    ids = np.asarray(ids, np.int64)
    key = (int(seed), int(ids[0]) if len(ids) else -1, len(ids))
    at = _WALKS.get(key)
    if at is not None and at[0] <= k and (at[2] == ids).all():
        x = datagen.walk_np(seed, ids, k, x=at[1], k_from=at[0],
                            last_only=True)
    else:
        x = datagen.walk_np(seed, ids, k, last_only=True)
    if len(_WALKS) > 64:
        _WALKS.clear()
    _WALKS[key] = (int(k), x, ids)
    return {"ts": np.full(len(ids), scrape_ms(k, deploy), np.int64),
            "values": np.ascontiguousarray(x, np.float64)}


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
                              values=values)


def raw_values(seed: int, sids, cols, deploy: dict) -> np.ndarray:
    return reference.raw_values(seed, sids, cols)


# 5. the read-back probes

def _hosts_with(ids: np.ndarray, field: int) -> np.ndarray:
    """Hosts whose series of ``field`` is among ``ids``."""
    return ids[ids % datagen.NF == field] // datagen.NF


def probes(seed: int, ids, col: int, deploy: dict, n: int) -> list[dict]:
    """Of a container (series ``ids``) whose newest scrape is ``col``:

    (a) ``n`` seeded hosts of it, each read as
        ``cpu_usage_idle{hostname="host_<h>"}`` — the VALUES of the four
        newest scrapes — and as ``timestamp(..)`` of the same — their
        STAMPS, on the grid — both exactly;
    (b) a matcher on two DRAWN tags, ``count by (os)(cpu_usage_user{rack=..,
        region=..})`` with the rack and region of a seeded host of the
        container: the counts an os are the law's over every host of the
        deployment, so a store that registered a tag wrongly misses them;
    (c) the timed path's own leaf: ``max(max_over_time(cpu_usage_user{
        hostname=~"<8 hosts of the container>"}[1m]))`` at four steps an
        interval apart, of which the last window holds scrape ``col``: the
        stored integers, exactly."""
    iv = _iv(deploy)
    ids = np.asarray(ids, np.int64)
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 0x75B5])
    cols = np.arange(col - PROBE_STEPS + 1, col + 1)
    span = {"start_ms": scrape_ms(cols[0], deploy),
            "end_ms": scrape_ms(cols[-1], deploy), "step_ms": iv}
    out = []
    idle = datagen.FIELDS.index("usage_idle")
    hosts = _hosts_with(ids, idle)
    for h in rng.choice(hosts, min(n, len(hosts)), replace=False).tolist():
        want = reference.raw_values(seed, [datagen.NF * h + idle], cols)[0]
        stamps = (datagen.BASE_TS + cols * iv) / 1000.0
        sel = f'{datagen.METRICS[idle]}{{hostname="host_{h}"}}'
        for promql, w in ((sel, want), (f"timestamp({sel})", stamps)):
            out.append({"promql": promql, **span,
                        "want": [({"hostname": f"host_{h}"}, w)]})
    users = _hosts_with(ids, 0)
    out.append(_tag_probe(int(rng.choice(users)), deploy, span))
    picked = np.sort(rng.choice(users, min(PROBE_HOSTS, len(users)),
                                replace=False))
    ref = {"agg": "max", "fn": "max_over_time", "window_s": PROBE_WINDOW_S,
           "metric": datagen.METRICS[0], "hosts": picked.tolist()}
    steps = np.arange(span["start_ms"], span["end_ms"] + 1, iv)
    want = reference.evaluate(seed, ids, ref, steps, iv, col)[()]
    out.append({"promql": text_of(ref), **span, "want": [({}, want)]})
    return out


def text_of(ref: dict) -> str:
    """The PromQL text of a ``ref``: TSBS's ``single-groupby`` as a
    Prometheus data source sends it, one metric a query."""
    hosts = "|".join(f"host_{h}" for h in ref["hosts"])
    return (f'{ref["agg"]}({ref["fn"]}({ref["metric"]}{{hostname=~"{hosts}"}}'
            f'[{int(ref["window_s"]) // 60}m]))')


def _tag_probe(host: int, deploy: dict, span: dict) -> dict:
    """``count by (os)(cpu_usage_user{rack=.., region=..})`` with ``host``'s
    rack and region; every host of the deployment that shares them counts
    (each has held a sample since scrape 0)."""
    n_hosts = -(-int(deploy["series"]) // datagen.NF)
    # a host's cpu_usage_user is series 10 h: it exists for every host
    d = datagen.tag_draws(np.arange(n_hosts))
    mine = datagen.tag_draws([host])
    hit = ((d["rack"] == mine["rack"][0])
           & (d["region"] == mine["region"][0]))
    steps = (span["end_ms"] - span["start_ms"]) // span["step_ms"] + 1
    want = [({"os": datagen.OSES[o]}, np.full(steps, float(c)))
            for o, c in enumerate(np.bincount(d["os"][hit],
                                              minlength=len(datagen.OSES)))
            if c]
    return {"promql": f'count by (os)({datagen.METRICS[0]}{{rack="'
                      f'{int(mine["rack"][0])}", region="'
                      f'{datagen.REGIONS[int(mine["region"][0])]}"}})',
            **span, "want": want}


# 6. the gather's needed bytes

def query_bytes(rows: int, ref: dict, out_ts, deploy: dict, head_col: int,
                capacity: int) -> float:
    """Selected rows x the columns the query's windows touch x (4 + 8) B: a
    narrow query reads its own rows of the value and stamp blocks, whatever
    the store's height (``rows`` is not in it)."""
    lo, hi = reference.window_cells(out_ts, int(ref["window_s"]) * 1000,
                                    _iv(deploy), min(head_col, capacity - 1))
    ok = hi >= lo
    if not ok.any():
        return 0.0
    cols = int(hi[ok].max() - lo[ok].min() + 1)
    return float(len(ref["hosts"]) * cols * (4 + 8))
