"""The four readers of what a narrow (gathered) leaf does (PR 41):
``gather_mean_ms``, ``selected_series_mean``, ``matcher_miss_pct`` (program
spans) and ``leaf_device_ms`` (the device trace). Each on a hand-made window
(known spans, tags and events -> the known value) and on the window of a
program that records none of them (-> None: the result line leaves the
metric out), as the parent commit under this PR's benchmark files is.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.run import load_layer  # noqa: E402

TSBS_LAYERS = ("gather_mean_ms", "selected_series_mean", "matcher_miss_pct",
               "leaf_device_ms")


def tsp(name, trace, dur_ms, **tags):
    return {"name": name, "trace_id": trace, "t0": 1.0, "dur_s": dur_ms / 1e3,
            "tags": tags}


def op(start_ms, dur_ms, name="%fusion = f32[8,768] fusion(...)"):
    return [name, start_ms * 1e6, dur_ms * 1e6]


def tsbs_window():
    """Four queries: a gathers one series (matcher resolved: a miss), b
    gathers eight (from the filter cache), c is a wide selection (no
    gather), d a cache answer without a leaf; a rule's leaf outside any
    query; device events inside and outside the traced part, a write-path
    program's among them, on the one chip's op line and on another line."""
    spans = [
        tsp("query", "a", 12), tsp("query", "b", 9), tsp("query", "c", 80),
        tsp("query", "d", 1),
        tsp("query.exec.select", "a", 1.5, series=1, route="gather",
            resolve="miss", matchers="eq+re", memo="bypass"),
        tsp("query.exec.gather", "a", 0.75, rows=1, padded=8, bytes=9216),
        tsp("query.exec.select", "b", 1.0, series=8, route="gather",
            resolve="hit", matchers="eq+re", memo="bypass"),
        tsp("query.exec.gather", "b", 0.25, rows=8, padded=8, bytes=73728),
        tsp("query.exec.select", "c", 0.5, series=1_000_000, route="wide",
            resolve="hit", matchers="eq", memo="hit"),
        # not a query's
        tsp("query.exec.select", "r", 3.0, series=5, route="gather",
            resolve="miss", matchers="eq", memo="bypass"),
        tsp("query.exec.gather", "r", 2.0, rows=5, padded=8, bytes=46080),
    ]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                op(900, 5),                      # before the traced part
                op(1000, 0.25), op(1010, 0.5),   # a query's gather, kernel
                op(1500, 1.25, "%select = s64[1048576,768] fusion(...)"),
                op(2999.5, 2.0),                 # starts inside: counted
                op(3000, 7)]},                   # after the window
            {"name": "XLA Modules", "events": [op(1000, 100, "jit_x(1)")]}]},
        {"name": "/device:CUSTOM:0", "lines": [
            {"name": "XLA Ops", "events": [op(1200, 50)]}]}]}
    done = [{"t1": 1.0}, {"t1": 2.0}, {"t1": 2.5}, {"t1": 2.9}]
    return {"spans": spans, "trace": trace, "tw0_ns": 1000e6, "w1_ns": 3000e6,
            "done_traced": done}


TSBS_WANT = {
    "gather_mean_ms": (0.75 + 0.25) / 4,         # per QUERY, the rule's not
    "selected_series_mean": (1 + 8) / 2,         # per GATHERING leaf
    "matcher_miss_pct": 100 * 1 / 3,             # a miss of three selects
    "leaf_device_ms": (0.25 + 0.5 + 1.25 + 2.0) / 4,
}


@pytest.mark.parametrize("name", TSBS_LAYERS)
def test_tsbs_reader_gives_the_known_value(name):
    got = load_layer(name).read(tsbs_window())
    assert got == pytest.approx(TSBS_WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", TSBS_LAYERS[:3])
def test_tsbs_span_reader_finds_nothing_in_the_parents_window(name):
    """The parent records no gather span and tags no route or resolution:
    nothing to read, and nothing raised."""
    ctx = tsbs_window()
    ctx["spans"] = [dict(s, tags={k: v for k, v in s["tags"].items()
                                  if k not in ("route", "resolve",
                                               "matchers")})
                    for s in ctx["spans"] if s["name"] != "query.exec.gather"]
    assert load_layer(name).read(ctx) is None
    assert load_layer(name).read(dict(ctx, spans=[])) is None


def test_tsbs_device_reader_needs_an_answered_query_and_no_kernel():
    """No query answered in the traced part: None. No operation at all: 0,
    a number — the chip idled, the cell still drove it in set-up."""
    ctx = tsbs_window()
    assert load_layer("leaf_device_ms").read(dict(ctx, done_traced=[])) is None
    empty = {"planes": [{"name": "/device:TPU:0", "lines": []}]}
    assert load_layer("leaf_device_ms").read(dict(ctx, trace=empty)) == 0.0
    # where kernel_ms is blind: no event of the window is a Pallas call
    ctx["peaks"] = {"kernel_names": ["tpu_custom_call"]}
    assert load_layer("kernel_ms").read(ctx) == 0.0
    assert load_layer("leaf_device_ms").read(ctx) > 0


def test_tsbs_selected_mean_counts_an_empty_gather_as_zero_series():
    ctx = tsbs_window()
    ctx["spans"].append(tsp("query", "e", 2))
    ctx["spans"].append(tsp("query.exec.select", "e", 0.2, series=0,
                            route="gather", resolve="hit", matchers="eq+re"))
    assert load_layer("selected_series_mean").read(ctx) == pytest.approx(3.0)
