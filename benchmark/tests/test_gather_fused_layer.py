"""The reader of how often a gathered leaf ran as one program (PR 42):
``gather_fused_pct``, on hand-made windows in the manner of
``test_tsbs_layers.py`` (whose cases stay as they are: this file adds, the
accepted one is not edited) — every gather span ``programs`` = 1 -> 100; a
mix -> the share; spans without the tag, as the parent commit under this
PR's benchmark files records them -> None; no gather span at all -> None;
a gather outside any query is not counted.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.run import load_layer  # noqa: E402
from benchmark.tests.test_tsbs_layers import tsp  # noqa: E402


def window(*programs, rule=None):
    """A query a given ``programs`` tag (None: a gather span without the
    tag), one more query that gathered nothing, and a rule evaluation's
    gather outside any query."""
    spans = [tsp("query", "wide", 80),
             tsp("query.exec.select", "wide", 0.5, series=1_000_000,
                 route="wide")]
    for i, p in enumerate(programs):
        tags = dict(rows=8, padded=8, bytes=73728)
        if p is not None:
            tags["programs"] = p
        spans += [tsp("query", f"q{i}", 9),
                  tsp("query.exec.select", f"q{i}", 1.0, series=8,
                      route="gather"),
                  tsp("query.exec.gather", f"q{i}", 0.25, **tags)]
    if rule is not None:
        spans.append(tsp("query.exec.gather", "r", 2.0, rows=5, padded=8,
                         bytes=46080, programs=rule))
    return {"spans": spans}


@pytest.mark.parametrize("programs,rule,want", [
    ((1, 1, 1), None, 100.0),                  # every leaf one program
    ((1, 10, 1, 3), None, 50.0),               # two of four kept their steps
    ((10, 3), None, 0.0),                      # none: a number, not None
    ((1, 1), 10, 100.0),                       # the rule's leaf is no query's
    ((None, None), None, None),                # the parent: no such tag
    ((None, 1), None, 100.0),                  # only tagged spans count
    ((), None, None),                          # no gather span in the window
    ((), 1, None),                             # ... but a rule's
], ids=["all-one", "mixed", "all-steps", "rule-not-counted", "no-tag",
        "tagged-only", "no-gather", "rule-only"])
def test_gather_fused_reader(programs, rule, want):
    got = load_layer("gather_fused_pct").read(window(*programs, rule=rule))
    assert got == (want if want is None else pytest.approx(want, rel=1e-12))
    assert load_layer("gather_fused_pct").read({"spans": []}) is None


def test_the_entry_is_as_the_issue_names_it():
    """By membership, not by the tail: entries appended after it change
    nothing here."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert per_layer["gather_fused_pct"] == {
        "name": "gather_fused_pct", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "leaf under the shard lock",
        "moves": "query_rate", "workloads": ["tsbs_single"]}
    assert os.path.exists(os.path.join(ROOT, "benchmark", "layers",
                                       "gather_fused_pct.py"))
