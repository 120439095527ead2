"""TSBS DevOps ``cpu-only`` as a law of (seed, series, scrape).

A host reports one ``cpu`` row every 10 s: ten integer fields under ten
tags. In a Prometheus-model store a field is a series, so series ``s`` is
field ``s % 10`` of host ``s // 10``, named ``cpu_<field>`` and labelled
with the host's ten tags. Stamps lie exactly on the grid,
``BASE_TS + k * interval``, as TSBS's generator writes them.

**Tags** are draws a host, fixed by the host's number alone (a store's
labels do not change with the run's seed): region 1 of 9, datacenter 1 of
3 within it, rack 0-99, os 1 of 3, arch 1 of 2, team 1 of 4, service 0-19,
service_version 0-1, service_environment 1 of 3 — TSBS's choices, written
from memory (the configuration lists them under ``assumed``).

**Values** are a walk, sequential in the scrape and independent a series:

    x(s, 0) = u(seed, s)                       uniform integer in [0, 100]
    x(s, k) = clip(x(s, k-1) + d(seed, s, k), 0, 100)

with ``d`` an integer in [-3, 3] drawn with a rounded unit normal's weights
(0: 38.3 %, +-1: 24.2 % each, +-2: 6.1 %, +-3: 0.6 %). **Departure, stated**:
TSBS walks a FLOAT by N(0, 1) a step, clamps it to [0, 100] and emits its
integer part; here the state itself is the integer, so that numpy on the
host and ``jax.numpy`` on the device walk to the SAME integers and every
sample is exact in f32. To a store and a query the two are the same kind of
data: integers in [0, 100] that move by a few units a scrape.

The mixer is ``counter``'s (imported, not copied): two multiply rounds over
(word, s, c) in uint32 with wrap-around, which numpy arrays and XLA
integers share.
"""

from __future__ import annotations

import numpy as np

from ..counter.datagen import BASE_TS, _mix, fold_seed  # noqa: F401

FIELDS = ("usage_user", "usage_system", "usage_idle", "usage_nice",
          "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
          "usage_guest", "usage_guest_nice")
NF = len(FIELDS)
METRICS = tuple(f"cpu_{f}" for f in FIELDS)

REGIONS = ("us-east-1", "us-west-1", "us-west-2", "eu-west-1",
           "eu-central-1", "ap-southeast-1", "ap-southeast-2",
           "ap-northeast-1", "sa-east-1")
ZONES = ("a", "b", "c")                    # datacenter = region + zone
OSES = ("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")
ARCHES = ("x64", "x86")
TEAMS = ("SF", "NYC", "LON", "CHI")
ENVS = ("production", "staging", "test")
RACKS, SERVICES, VERSIONS = 100, 20, 2
# tag -> (how many values, the word its draw is mixed with)
TAG_DRAWS = {"region": (len(REGIONS), 0x7A601), "datacenter": (len(ZONES), 0x7A602),
             "rack": (RACKS, 0x7A603), "os": (len(OSES), 0x7A604),
             "arch": (len(ARCHES), 0x7A605), "team": (len(TEAMS), 0x7A606),
             "service": (SERVICES, 0x7A607),
             "service_version": (VERSIONS, 0x7A608),
             "service_environment": (len(ENVS), 0x7A609)}
TAGS = ("hostname",) + tuple(TAG_DRAWS)

V_MAX = 100
_START_COL = 0xFFFF_FFFF                   # no store has this column
# d = (how many of these the draw's 16 bits reach) - 3: the weights of a
# unit normal rounded to the nearest integer, in 65536ths, tails folded in
STEP_CUTS = (407, 4378, 20218, 45318, 61158, 65129)


def tag_draws(hosts) -> dict:
    """{tag: int64[n]}: the index of each drawn tag's value, a host."""
    h = np.asarray(hosts, np.uint32)
    c = np.zeros(h.shape, np.uint32)
    with np.errstate(over="ignore"):
        return {t: ((_mix(np, word, h, c) >> np.uint32(8))
                    % np.uint32(n)).astype(np.int64)
                for t, (n, word) in TAG_DRAWS.items()}


def tag_strings(hosts) -> dict:
    """{tag: [str]}: the ten tags of each host, ``hostname`` first."""
    hosts = np.asarray(hosts, np.int64)
    d = tag_draws(hosts)
    region = [REGIONS[i] for i in d["region"]]
    return {
        "hostname": [f"host_{h}" for h in hosts.tolist()],
        "region": region,
        "datacenter": [r + ZONES[z] for r, z in zip(region, d["datacenter"])],
        "rack": [str(i) for i in d["rack"].tolist()],
        "os": [OSES[i] for i in d["os"]],
        "arch": [ARCHES[i] for i in d["arch"]],
        "team": [TEAMS[i] for i in d["team"]],
        "service": [str(i) for i in d["service"].tolist()],
        "service_version": [str(i) for i in d["service_version"].tolist()],
        "service_environment": [ENVS[i] for i in d["service_environment"]]}


def start_of(xp, word, s):
    """x(s, 0) as int32, uniform in [0, V_MAX]."""
    c = xp.full(s.shape, _START_COL, dtype=xp.uint32)
    return ((_mix(xp, word, s, c) >> xp.uint32(8))
            % xp.uint32(V_MAX + 1)).astype(xp.int32)


def step_of(xp, word, s, k):
    """d(seed, s, k) as int32 in [-3, 3]; ``s`` and ``k`` broadcast."""
    h = (_mix(xp, word, s, k) >> xp.uint32(16)).astype(xp.int32)
    d = xp.zeros(h.shape, xp.int32) - xp.int32(3)
    for cut in STEP_CUTS:
        d = d + (h >= xp.int32(cut)).astype(xp.int32)
    return d


def advance(xp, x, d):
    """One scrape on: the walk's state after the step ``d``."""
    return xp.clip(x + d, 0, V_MAX)


CHUNK = 64        # scrapes whose steps the host draws at once
SLICE = 1 << 14   # series a thread walks (numpy releases the interpreter)
# the host's draw by table: d of every 16-bit value (what step_of's
# comparisons give, looked up)
_STEP_LUT = (np.searchsorted(np.asarray(STEP_CUTS), np.arange(1 << 16),
                             side="right") - 3).astype(np.int32)


def _walk_slice(word: int, s: np.ndarray, x: np.ndarray, k_from: int,
                k_hi: int, out=None) -> np.ndarray:
    """x(s, k_hi) from ``x`` = x(s, k_from); with ``out``, ``out[:, j]`` =
    x(s, k_from + j) on the way. The steps are drawn a block of scrapes at
    a time, scrapes down and series across (a scrape's steps lie together);
    the clip is what is sequential."""
    if out is not None:
        out[:, 0] = x
    with np.errstate(over="ignore"):
        for lo in range(k_from + 1, k_hi + 1, CHUNK):
            ks = np.arange(lo, min(lo + CHUNK, k_hi + 1), dtype=np.uint32)
            d = _STEP_LUT[_mix(np, word, s[None, :], ks[:, None])
                          >> np.uint32(16)]
            for j in range(len(ks)):
                x = advance(np, x, d[j])
                if out is not None:
                    out[:, lo - k_from + j] = x
    return x


def walk_np(seed: int, sids, k_hi: int, x=None, k_from: int = 0,
            last_only: bool = False) -> np.ndarray:
    """int32 [n, k_hi - k_from + 1]: x(s, k_from..k_hi) on the host, from
    ``x`` = x(s, k_from) (None: from the start, ``k_from`` 0); with
    ``last_only`` int32 [n], x(s, k_hi) alone (a live scrape). Many series
    over many scrapes are walked in slices on a few threads."""
    word = fold_seed(seed)
    s = np.asarray(sids, np.uint32)
    if x is None:
        if k_from:
            raise ValueError("a walk without a state starts at scrape 0")
        with np.errstate(over="ignore"):
            x = start_of(np, word, s)
    out = (None if last_only
           else np.empty((len(s), k_hi - k_from + 1), np.int32))
    last = np.empty(len(s), np.int32)

    def part(a: int, b: int) -> None:
        last[a:b] = _walk_slice(word, s[a:b], x[a:b], k_from, k_hi,
                                None if out is None else out[a:b])

    cuts = range(0, len(s), SLICE)
    if len(cuts) < 2 or k_hi - k_from < 8:
        part(0, len(s))
    else:
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(6) as ex:
            list(ex.map(lambda a: part(a, a + SLICE), cuts))
    return last if last_only else out


def values_np(seed: int, sids, cols) -> np.ndarray:
    """float64 [len(sids), len(cols)]: x(s, c) for the scrapes ``cols``
    (any order), walked from scrape 0 on the host."""
    cols = np.asarray(cols, np.int64)
    if not len(cols) or not len(sids):
        return np.zeros((len(sids), len(cols)))
    return walk_np(seed, sids, int(cols.max()))[:, cols].astype(np.float64)
