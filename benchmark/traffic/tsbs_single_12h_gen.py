#!/usr/bin/env python3
"""Writes ``tsbs_single_12h.json``: TSBS DevOps ``single-groupby`` over
twelve hours.

    python3 benchmark/traffic/tsbs_single_12h_gen.py      # rewrites the file

TSBS's four twelve-hour ``single-groupby-M-H-12`` types, rendered as
``tsbs_single_gen.py`` (beside this file, imported and not copied) renders
the one-hour ones: a block of 12 texts a host draw — ``-1-1-12`` (1 text),
``-1-8-12`` (1), ``-5-1-12`` and ``-5-8-12`` (five texts each, one a metric
of the first five) — ``max(max_over_time(cpu_<field>{hostname=~"host_a|.."}
[1m]))``, over ONE range: 43,200 s at a 60 s step, 721 steps, ending 0, 300
or 600 s before the head. The file is a function of (SEED, HOSTS, DRAWS):
tier-1 regenerates and compares it.
"""

from __future__ import annotations

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
SEED, HOSTS, DRAWS = 44, 100_000, 32
RANGE_S, STEP_S, END_BACK_S = 43_200, 60, [0, 300, 600]


def _hourly():
    spec = importlib.util.spec_from_file_location(
        "tsbs_single_gen", os.path.join(HERE, "tsbs_single_gen.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generate(seed: int = SEED, hosts: int = HOSTS, draws: int = DRAWS) -> dict:
    mix = _hourly().generate(seed, hosts, draws)
    for q in mix["queries"]:
        q["tsbs"] = q["tsbs"][:-1] + "12"       # single-groupby-M-H-12
    mix.update(
        name="tsbs_single_12h",
        about=("TSBS DevOps cpu-only, single-groupby-{1,5}-{1,8}-12: an "
               "on-call engineer widening a host panel from 'last hour' to "
               "'last 12 h' on a 1M-series node that keeps half a day in "
               "memory — this cpu metric, these 1-8 hosts, 12 h by the "
               "minute (721 steps); 8 closed-loop users, live ingest "
               "underneath; every query selects 1 or 8 series of 10^6 by "
               "matcher and decodes them from a one-byte-a-sample store"),
        source=("timescale/tsbs cmd/tsbs_generate_queries, use case "
                "cpu-only, query types single-groupby-1-1-12, -1-8-12, "
                "-5-1-12, -5-8-12 (written from memory: no network here)"),
        generated_by=(f"benchmark/traffic/tsbs_single_12h_gen.py seed {seed} "
                      f"hosts {hosts} draws {draws}: {draws} host draws x a "
                      f"block of 12 texts, rendered by tsbs_single_gen.py"),
        ranges=[{"range_s": RANGE_S, "step_s": STEP_S,
                 "end_back_s": END_BACK_S}])
    return mix


def main() -> None:
    path = os.path.join(HERE, "tsbs_single_12h.json")
    mix = generate()
    with open(path, "w") as f:
        json.dump(mix, f, indent=1)
        f.write("\n")
    print(f"wrote {path}: {len(mix['queries'])} texts")


if __name__ == "__main__":
    main()
