"""The small store of ``tests/test_births.py``: a seeded fleet whose series
are born at several cells and end, ingested through the shard's write path
— and the queries asked of it. Used by the test AND, run as a script from
the PARENT commit's tree, to write the golden answers of that tree
(``tests/fixtures/births_parent.json``: its rule holds every row from
column 0 and answers the minority through ``_correct_minority_cohort``):

    PYTHONPATH=<a checkout of the parent> python tests/births_scenario.py \\
        tests/fixtures/births_parent.json

It uses nothing of the program but what both trees have: the memstore, a
``RecordBuilder`` and the engine.
"""

from __future__ import annotations

import json
import sys

import numpy as np

BASE = 1_700_000_000_000
IV = 10_000
HEAD = 720                      # scrapes 0..719 ingested
ROWS, C = 64, 768
WINDOW = "5m"
# (born, end): end exclusive, None = alive at the head. Births at six
# cells, three ends, one series born and ended inside one 5 m window
LIVES = ([(0, None)] * 30 + [(0, 200), (0, 480), (0, 640)]
         + [(60, None)] * 5 + [(130, None)] * 4 + [(300, 500)] * 2
         + [(300, None)] * 3 + [(555, None)] * 4 + [(600, 612)]
         + [(700, None)] * 3)
GROUPS = 4
TEXTS = {
    "sum_rate": ("sum(rate(m[5m]))", "sum", "rate", False),
    "sum_by_rate": ("sum by (g)(rate(m[5m]))", "sum", "rate", True),
    "avg_avg": ("avg(avg_over_time(m[5m]))", "avg", "avg_over_time", False),
    "stddev_sum": ("stddev(sum_over_time(m[5m]))", "stddev", "sum_over_time",
                   False),
    "sum_count": ("sum(count_over_time(m[5m]))", "sum", "count_over_time",
                  False),
    "sum_increase": ("sum(increase(m[5m]))", "sum", "increase", False),
    "sum_delta": ("sum by (g)(delta(m[5m]))", "sum", "delta", True),
}
# (range seconds, step seconds): ending at the head, 61 steps each
RANGES = {"15m": (900, 15), "1h": (3600, 60), "2h": (7200, 120)}


def values(seed: int = 49) -> list[np.ndarray]:
    """Monotone integer counters, exact in f32; a new series starts over."""
    rng = np.random.default_rng(seed)
    out = []
    for born, end in LIVES:
        n = (HEAD if end is None else end) - born
        out.append(rng.integers(0, 1000)
                   + np.cumsum(rng.integers(1, 128, n)).astype(np.float64))
    return out


def labels(i: int) -> dict:
    return {"_metric_": "m", "host": f"h{i}", "g": f"g{i % GROUPS}"}


def out_ts(name: str) -> tuple[int, int, int]:
    rng_s, step_s = RANGES[name]
    end = BASE + (HEAD - 1) * IV
    return end - rng_s * 1000, end, step_s * 1000


def build(dtype: str = "float32"):
    """(memstore, shard, engine) with the fleet ingested, sixty scrapes a
    container."""
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.query.engine import QueryEngine
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", GAUGE, 0, StoreConfig(
        max_series_per_shard=ROWS, samples_per_series=C,
        flush_batch_size=10**9, dtype=dtype))
    vals = values()
    for k0 in range(0, HEAD, 60):
        b = RecordBuilder(GAUGE)
        for k in range(k0, k0 + 60):
            for i, (born, end) in enumerate(LIVES):
                if born <= k < (HEAD if end is None else end):
                    b.add(labels(i), BASE + k * IV, float(vals[i][k - born]))
        shard.ingest(b.build())
        shard.flush()
    return ms, shard, QueryEngine(ms, "prometheus")


def by_group(result, steps: np.ndarray) -> dict:
    """{group: f64 [T]} of an answer on the step grid ``steps``, NaN where
    a step has none (a rendered series drops such points)."""
    out = {}
    for k, t, v in result.matrix.to_host().iter_series():
        row = np.full(len(steps), np.nan)
        row[np.searchsorted(steps, np.asarray(t, np.int64))] = v
        out[k.as_dict().get("g", "")] = row
    return out


def answers(eng) -> dict:
    """{text: {range: {group: [values]}}}, as the engine gives them."""
    out = {}
    for name, (text, *_rest) in TEXTS.items():
        out[name] = {}
        for rname in RANGES:
            start, end, step = out_ts(rname)
            r = eng.query_range(text, start, end, step)
            out[name][rname] = {
                g: v.tolist() for g, v in by_group(
                    r, np.arange(start, end + 1, step)).items()}
    return out


if __name__ == "__main__":
    import os
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _ms, _shard, engine = build()
    with open(sys.argv[1], "w") as f:
        json.dump({"about": "answers of the parent commit's tree (PR 48, "
                            "47be710) for tests/births_scenario.py: every "
                            "row from column 0, the minority through "
                            "_correct_minority_cohort",
                   "answers": answers(engine)}, f)
    print("written", sys.argv[1])
