#!/usr/bin/env python3
"""Writes ``tsbs_single.json``: TSBS DevOps ``single-groupby`` over one hour.

    python3 benchmark/traffic/tsbs_single_gen.py          # rewrites the file

TSBS's four one-hour ``single-groupby-M-H-1`` types (M metrics of the first
five on H random hosts, ``max`` a minute), as its VictoriaMetrics generator
writes them in MetricsQL —
``max(max_over_time({__name__=~"cpu_(usage_user|..)", hostname=~"host_3|.."}[1m])) by (__name__)``
— rendered in PromQL, where ``max_over_time`` drops the metric name and an
aggregation's group key never holds it: ``by (__name__)`` over a five-metric
regex would fold five metrics into one series. A Prometheus data source
sends a five-metric panel as five queries, and so does this mix. One seeded
host draw gives a block of 12 texts:

    single-groupby-1-1-1    1 text   cpu_usage_user, 1 host
    single-groupby-1-8-1    1 text   cpu_usage_user, 8 hosts
    single-groupby-5-1-1    5 texts  one a metric of the first five, the same host
    single-groupby-5-8-1    5 texts  one a metric, the same 8 hosts

each type with hosts of its own, drawn uniformly (8 hosts: without
replacement), as TSBS draws them. 4 texts in 12 ask ``usage_user``, 2 each
of the four others; 6 name one host, 6 eight: 4.5 series a query. The file
is a function of (SEED, HOSTS, DRAWS): tier-1 regenerates and compares it.
"""

from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SEED, HOSTS, DRAWS = 41, 100_000, 32
FIRST_FIVE = ("cpu_usage_user", "cpu_usage_system", "cpu_usage_idle",
              "cpu_usage_nice", "cpu_usage_iowait")
WINDOW_S = 60


def text_of(metric: str, hosts) -> str:
    alt = "|".join(f"host_{h}" for h in hosts)
    return (f'max(max_over_time({metric}{{hostname=~"{alt}"}}'
            f'[{WINDOW_S // 60}m]))')


def block(rng, hosts: int) -> list[dict]:
    """One host draw: the 12 texts, with what the reference needs."""
    def draw(n):
        return sorted(int(h) for h in rng.choice(hosts, n, replace=False))

    asks = ([("single-groupby-1-1-1", FIRST_FIVE[0], draw(1)),
             ("single-groupby-1-8-1", FIRST_FIVE[0], draw(min(8, hosts)))]
            + [("single-groupby-5-1-1", m, h)
               for h in (draw(1),) for m in FIRST_FIVE]
            + [("single-groupby-5-8-1", m, h)
               for h in (draw(min(8, hosts)),) for m in FIRST_FIVE])
    return [{"promql": text_of(metric, h), "tsbs": kind,
             "ref": {"agg": "max", "fn": "max_over_time",
                     "window_s": WINDOW_S, "metric": metric, "hosts": h}}
            for kind, metric, h in asks]


def generate(seed: int = SEED, hosts: int = HOSTS, draws: int = DRAWS) -> dict:
    rng = np.random.default_rng([seed, hosts, draws, 0x75B5])
    queries = [q for _ in range(draws) for q in block(rng, hosts)]
    return {
        "name": "tsbs_single",
        "about": ("TSBS DevOps cpu-only, single-groupby-{1,5}-{1,8}-1: a "
                  "host dashboard or an alert drill-down on a 1M-series "
                  "node — this cpu metric, these 1-8 hosts, the last hour, "
                  "by the minute; 8 closed-loop users, live ingest "
                  "underneath; every query selects 1 or 8 series of 10^6 "
                  "by matcher"),
        "source": ("timescale/tsbs cmd/tsbs_generate_queries, use case "
                   "cpu-only, query types single-groupby-1-1-1, -1-8-1, "
                   "-5-1-1, -5-8-1 (written from memory: no network here)"),
        "rendering": ("MetricsQL's by (__name__) over a five-metric regex "
                      "has no PromQL equivalent (max_over_time drops the "
                      "name, a group key never holds it): a five-metric "
                      "query is sent as five queries, one a metric, as a "
                      "Prometheus data source sends a five-metric panel"),
        "generated_by": (f"benchmark/traffic/tsbs_single_gen.py seed {seed} "
                         f"hosts {hosts} draws {draws}: {draws} host draws "
                         f"x a block of 12 texts"),
        "clients": 8,
        "tenant": None,
        "queries": queries,
        "ranges": [{"range_s": 3600, "step_s": 60,
                    "end_back_s": [0, 1800, 3300]}],
        "order": "shared_deck",
        "cache_defeat": ("as adhoc: a start phase in whole milliseconds, "
                         "1009 ms further on per query of a (promql, step) "
                         "key; cache_route_pct shows whether it worked"),
        "warmup": "deck",
        "warm_caches": ("warmup \"deck\" sends every card once, so each "
                        "text's matcher has been resolved once before the "
                        "window and the index's filter and regex caches are "
                        "warm, as for a dashboard that repeats; TSBS's "
                        "fresh host draw a query needs a generator that "
                        "fills a template (a benchmark PR's): "
                        "matcher_miss_pct reads 0 here by construction"),
        "expect_routes": ["local-gather"],
    }


def main() -> None:
    path = os.path.join(HERE, "tsbs_single.json")
    with open(path, "w") as f:
        json.dump(generate(), f, indent=1)
        f.write("\n")
    print(f"wrote {path}: {len(generate()['queries'])} texts")


if __name__ == "__main__":
    main()
