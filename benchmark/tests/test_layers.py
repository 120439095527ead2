"""The span readers this benchmark gained with the program's inner spans:
each on a hand-made window (known spans and tags -> the known value), on a
window of a program that records none of them (-> None, and the result
line leaves the metric out), and with a span outside any query's trace
(-> not counted in a per-query mean).

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.run import load_layer  # noqa: E402


def sp(name, trace, dur_ms, **tags):
    return {"name": name, "trace_id": trace, "t0": 1.0, "dur_s": dur_ms / 1e3,
            "tags": tags}


def window():
    """Two queries (traces a, b) and what happened around them in 10 s."""
    spans = [
        # query a: a by (g) query that waited, walked and ran its kernel
        sp("http.request", "a", 1000), sp("query.queue", "a", 300),
        sp("query", "a", 690), sp("query.exec.leaf", "a", 600,
                                  lock_wait_ms=100.0, shard=0),
        sp("query.exec.select", "a", 20), sp("query.exec.groupids", "a", 400),
        sp("query.exec.kernel", "a", 6, phase="dispatch"),
        sp("query.exec.kernel", "a", 10, phase="fetch"),
        sp("http.render", "a", 4),
        # query b: a global aggregate on the mesh route: a select a shard,
        # no walk, an uncontended leaf
        sp("http.request", "b", 100), sp("query.queue", "b", 50),
        sp("query", "b", 40), sp("query.exec.leaf", "b", 30,
                                 lock_wait_ms=0.0, shard="all"),
        sp("query.exec.select", "b", 4), sp("query.exec.select", "b", 6),
        sp("query.exec.kernel", "b", 8, phase="dispatch"),
        sp("http.render", "b", 2),
        # not a query's: a metadata request's queue wait, a rule's leaf
        sp("query.queue", "m", 5000), sp("http.render", "m", 5000),
        sp("query.exec.leaf", "r", 5000, lock_wait_ms=5000.0),
        sp("query.exec.select", "r", 5000),
        sp("query.exec.groupids", "r", 5000),
        sp("query.exec.kernel", "r", 5000, phase="fetch"),
        # the write path and the runtime
        sp("ingest.flush", "f1", 200, rows=131072, lock_wait_ms=150.0),
        sp("ingest.flush", "f2", 400, rows=131072, lock_wait_ms=0.0),
        sp("ingest.flush", "f3", 900, rows=131072, lock_wait_ms=0.0),
        sp("ingest.consume", "c1", 1000, rows=131072, lock_wait_ms=800.0),
        sp("ingest.consume", "c2", 3000, rows=262144, lock_wait_ms=1200.0),
        sp("runtime.gc", "a", 300, collected=0),
        sp("runtime.gc", "g", 200, collected=12),
    ]
    return {"spans": spans, "w0_ns": 5e9, "w1_ns": 15e9}


WANT = {
    "queue_mean_ms": (300 + 50) / 2,
    "render_mean_ms": (4 + 2) / 2,
    "lock_wait_mean_ms": (100 + 0) / 2,
    "select_mean_ms": (20 + 4 + 6) / 2,
    "groupids_mean_ms": 400 / 2,            # query b counts with 0
    "kernel_host_mean_ms": (6 + 10 + 8) / 2,
    "flush_ms": 400.0,                      # the median flush
    "ingest_lock_wait_pct": 100 * (800 + 1200) / (1000 + 3000),
    "gc_pause_pct": 100 * (0.3 + 0.2) / 10.0,
}


def test_every_new_metric_of_benchmark_json_has_a_case_here():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [m["name"] for m in json.load(f)["per_layer"]]
    assert names[-len(WANT):] == list(WANT)


@pytest.mark.parametrize("name", list(WANT))
def test_reader_gives_the_known_value(name):
    assert load_layer(name).read(window()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", list(WANT))
def test_reader_finds_nothing_in_a_program_without_the_span(name):
    """The parent commit's spans: a root ``query``, a leaf and a consume
    span with no ``lock_wait_ms``, nothing else of this PR's."""
    old = {"spans": [sp("query", "a", 700),
                     sp("query.exec.leaf", "a", 600, shard=0),
                     sp("query.execute", "a", 650),
                     sp("ingest.consume", "c", 1000, rows=131072)],
           "w0_ns": 0.0, "w1_ns": 10e9}
    # a window with spans and no collection is a window without a pause
    assert load_layer(name).read(old) == (0.0 if name == "gc_pause_pct"
                                          else None)
    assert load_layer(name).read({"spans": [], "w0_ns": 0.0,
                                  "w1_ns": 10e9}) is None


@pytest.mark.parametrize("name", [n for n in WANT if n.endswith("mean_ms")])
def test_a_span_outside_a_query_trace_is_not_counted(name):
    """Only the other traces' spans of the reader's name, and one query:
    nothing to read. With the queries back, the strays change nothing."""
    w = window()
    strays = [s for s in w["spans"] if s["trace_id"] in ("m", "r")]
    lone = {"spans": strays + [sp("query", "z", 10)], "w0_ns": 0.0,
            "w1_ns": 10e9}
    assert load_layer(name).read(lone) is None
    clean = dict(w, spans=[s for s in w["spans"]
                           if s["trace_id"] not in ("m", "r")])
    assert load_layer(name).read(clean) == pytest.approx(WANT[name])
