"""The reader of ``fall_tiles_pct`` (PR 40): the share of a line rate
program's row tiles that took the band product, from the fetch spans that
carry ``fall_tiles`` AND ``tiles``. On a hand-made window (known spans ->
the known value), on the window of a program that records no ``tiles`` (->
None: the result line leaves the metric out), and its entry in
``BENCHMARK.json``.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.run import load_layer  # noqa: E402


def fsp(trace, phase, **tags):
    return {"name": "query.exec.kernel", "trace_id": trace, "t0": 1.0,
            "dur_s": 0.01, "tags": {"phase": phase, **tags}}


def test_fall_tiles_pct_reads_the_fetch_spans_that_carry_both_tags():
    read = load_layer("fall_tiles_pct").read
    spans = [
        # two line rate programs: none of 2,048 tiles fell, 3 of 2,048 did
        fsp("a", "dispatch", stamps="line", packed=2, holes=0, rows=1 << 20),
        fsp("a", "fetch", fall_tiles=0, tiles=2048),
        fsp("b", "dispatch", stamps="line", packed=2, holes=1, rows=1 << 20),
        fsp("b", "fetch", fall_tiles=3, tiles=2048),
        # a window text on the same store: its fetch span carries neither
        fsp("c", "dispatch", stamps="line", packed=2, holes=0, rows=1 << 20),
        fsp("c", "fetch"),
        # the fused-hist route: fall_tiles alone, not read
        fsp("d", "dispatch", variant="hist-raw", packed=1),
        fsp("d", "fetch", fall_tiles=7),
        # tags of those names elsewhere are not a fetch's
        fsp("e", "dispatch", fall_tiles=9, tiles=9),
        {"name": "query.exec.select", "trace_id": "a", "t0": 1.0,
         "dur_s": 0.001, "tags": {"fall_tiles": 5, "tiles": 5}},
    ]
    assert read({"spans": spans}) == pytest.approx(100.0 * 3 / 4096)
    sound = [s for s in spans if s["trace_id"] in ("a", "c", "d")]
    assert read({"spans": sound}) == 0.0


def test_fall_tiles_pct_finds_nothing_in_the_parents_window():
    """The parent commit's spans: no fetch span carries ``tiles``."""
    read = load_layer("fall_tiles_pct").read
    old = [fsp("a", "dispatch", stamps="line", packed=2, holes=0),
           fsp("a", "fetch"), fsp("d", "fetch", fall_tiles=7)]
    assert read({"spans": old}) is None
    assert read({"spans": []}) is None
    assert read({"spans": [fsp("a", "fetch", fall_tiles=0, tiles=0)]}) is None


def test_benchmark_json_lists_fall_tiles_pct_for_the_two_line_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert per_layer["fall_tiles_pct"] == {
        "name": "fall_tiles_pct", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "fused kernel",
        "moves": "query_rate",
        "workloads": ["adhoc_prom", "adhoc_prom_miss"]}
    cells = {w["name"] for w in bench["workloads"]}
    assert set(per_layer["fall_tiles_pct"]["workloads"]) <= cells
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "layers",
                                       "fall_tiles_pct.py"))
