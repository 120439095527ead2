"""The five readers of what a worker waits for between its dispatch and its
answer (PR 39): the shard lock's hold from the lock's side (``lock_hold_pct``)
and the holder's (``lock_hold_mean_ms``), the device queue ahead of a
dispatch (``device_ahead_mean``), the interpreter's wake-up
(``wakeup_mean_ms``) and its worst (``stall_max_ms``). Each on a hand-made
window (known spans and tags -> the known value), on the window of a program
that records none of them (-> None: the result line leaves the metric out),
with nested holds (counted once) and on the mesh route (divided by the
leaf's ``locks``).

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.run import load_layer  # noqa: E402

WAIT_LAYERS = ("lock_hold_pct", "lock_hold_mean_ms", "device_ahead_mean",
               "wakeup_mean_ms", "stall_max_ms")


def wsp(name, trace, dur_ms, **tags):
    return {"name": name, "trace_id": trace, "t0": 1.0, "dur_s": dur_ms / 1e3,
            "tags": tags}


def wait_window():
    """Three queries in three seconds: a on one chip behind two programs,
    b on the mesh (four locks), c a cache answer that took no lock; a
    flush nested in a consume; a rule's leaf outside any query; three
    beats, one of them over a full collection."""
    spans = [
        # query a: probe 0.5 ms + leaf 6.5 ms held; the leaf's tag repeats
        # its share of the query's
        wsp("query", "a", 60, lock_wait_ms=2.0, lock_hold_ms=7.0),
        wsp("query.exec.leaf", "a", 9, shard=0, lock_wait_ms=2.0,
            lock_hold_ms=6.5),
        wsp("query.exec.kernel", "a", 1, phase="dispatch", ahead=2),
        wsp("query.exec.kernel", "a", 40, phase="fetch"),
        # query b: the mesh leaf held four locks 30 ms each, the probe each
        # of them 0.25 ms
        wsp("query", "b", 200, lock_wait_ms=30.0, lock_hold_ms=121.0),
        wsp("query.exec.leaf", "b", 62, shard="all", route="mesh", locks=4,
            lock_wait_ms=30.0, lock_hold_ms=120.0),
        wsp("query.exec.kernel", "b", 18, phase="dispatch", ahead=0),
        wsp("query.exec.kernel", "b", 4, phase="fetch"),
        # query c: a fragment-cache answer with a two-leaf sub-execution
        wsp("query", "c", 5, lock_wait_ms=0.0, lock_hold_ms=1.0),
        wsp("query.exec.kernel", "c", 1, phase="dispatch", ahead=1),
        wsp("query.exec.kernel", "c", 1, phase="dispatch", ahead=3),
        # not a query's: a rule's leaf and dispatch, the write path's holds
        wsp("query.exec.leaf", "r", 50, lock_wait_ms=0.0, lock_hold_ms=50.0),
        wsp("query.exec.kernel", "r", 1, phase="dispatch", ahead=9),
        wsp("ingest.consume", "w", 900, rows=131072, lock_wait_ms=100.0,
            lock_hold_ms=400.0),
        wsp("ingest.flush", "w", 300, rows=131072, lock_wait_ms=0.0,
            lock_hold_ms=250.0),
        # the heartbeat: a beat's interval is its second's worst wake-up
        wsp("runtime.beat", "h1", 4.0, ticks=49, late_ms=20.0,
            period_ms=1010.0, inflight=2, lock="shard-0-lock",
            lock_hold_ms=700.0),
        wsp("runtime.beat", "h2", 260.0, ticks=37, late_ms=300.0,
            period_ms=1250.0, inflight=3, lock="shard-0-lock",
            lock_hold_ms=900.0),
        wsp("runtime.beat", "h3", 1.5, ticks=50, late_ms=5.0,
            period_ms=1000.5, inflight=0, lock="shard-2-lock",
            lock_hold_ms=40.0),
        wsp("runtime.gc", "h2", 255, collected=0),
    ]
    return {"spans": spans, "w0_ns": 5e9, "w1_ns": 8.3e9}


WAIT_WANT = {
    # the busiest lock's growth over the beats' own periods
    "lock_hold_pct": 100 * (700 + 900 + 40) / (1010 + 1250 + 1000.5),
    # the query spans' tags alone (a leaf's hold is IN its query's), b's
    # divided by its four locks; the rule's and the write path's not at all
    "lock_hold_mean_ms": (7.0 + 121.0 / 4 + 1.0) / 3,
    # every dispatch span of a query, c's two; the rule's not
    "device_ahead_mean": (2 + 0 + 1 + 3) / 4,
    "wakeup_mean_ms": (20 + 300 + 5) / (49 + 37 + 50),
    "stall_max_ms": 260.0,
}


def test_benchmark_json_lists_the_five_with_their_layers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert tuple(WAIT_WANT) == WAIT_LAYERS
    want = {"lock_hold_pct": ("%", "leaf under the shard lock", "query_rate"),
            "lock_hold_mean_ms": ("ms", "leaf under the shard lock",
                                  "query_rate"),
            "device_ahead_mean": ("programs", "fused kernel", "query_p50_ms"),
            "wakeup_mean_ms": ("ms", "runtime", "query_rate"),
            "stall_max_ms": ("ms", "runtime", "query_rate")}
    for name, (unit, layer, moves) in want.items():
        assert per_layer[name] == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": layer, "moves": moves}, name
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "layers",
                                           f"{name}.py"))


@pytest.mark.parametrize("name", WAIT_LAYERS)
def test_wait_reader_gives_the_known_value(name):
    assert load_layer(name).read(wait_window()) == pytest.approx(
        WAIT_WANT[name])


@pytest.mark.parametrize("name", WAIT_LAYERS)
def test_wait_reader_finds_nothing_in_the_parents_window(name):
    """The parent commit's spans: queries, leaves, dispatches, a consume and
    a collection, with no ``lock_hold_ms``, no ``ahead``, no beat."""
    old = {"spans": [wsp("query", "a", 700, lock_wait_ms=3.0),
                     wsp("query.exec.leaf", "a", 600, shard=0,
                         lock_wait_ms=3.0),
                     wsp("query.exec.kernel", "a", 1, phase="dispatch"),
                     wsp("query.exec.kernel", "a", 9, phase="fetch"),
                     wsp("ingest.consume", "c", 1000, rows=131072,
                         lock_wait_ms=10.0),
                     wsp("runtime.gc", "g", 250, collected=0)],
           "w0_ns": 0.0, "w1_ns": 10e9}
    assert load_layer(name).read(old) is None
    assert load_layer(name).read({"spans": [], "w0_ns": 0.0,
                                  "w1_ns": 10e9}) is None


def test_nested_holds_are_counted_once():
    """A flush inside a consume, a leaf inside a query: the inner span's
    hold is in the outer span's tag too. The holder's reader takes the
    outermost query span alone, the lock's reader no span tag at all."""
    w = wait_window()
    no_leaf_tags = [dict(s, tags={k: v for k, v in s["tags"].items()
                                  if k != "lock_hold_ms"})
                    if s["name"] == "query.exec.leaf" else s
                    for s in w["spans"]]
    assert load_layer("lock_hold_mean_ms").read(dict(w, spans=no_leaf_tags)) \
        == pytest.approx(WAIT_WANT["lock_hold_mean_ms"])
    no_write_path = [s for s in w["spans"] if not s["name"].startswith("ingest")]
    for name in ("lock_hold_pct", "lock_hold_mean_ms"):
        assert load_layer(name).read(dict(w, spans=no_write_path)) \
            == pytest.approx(WAIT_WANT[name])


def test_the_mesh_leafs_sum_is_divided_by_its_locks():
    """One mesh query alone: 121 ms over four locks is 30.25 ms a lock; with
    the leaf's ``locks`` tag missing the sum stands as it is."""
    w = wait_window()
    b = [s for s in w["spans"] if s["trace_id"] == "b"]
    assert load_layer("lock_hold_mean_ms").read(dict(w, spans=b)) \
        == pytest.approx(121.0 / 4)
    bare = [dict(s, tags={k: v for k, v in s["tags"].items() if k != "locks"})
            for s in b]
    assert load_layer("lock_hold_mean_ms").read(dict(w, spans=bare)) \
        == pytest.approx(121.0)


def test_a_beat_without_a_server_has_wakeups_and_no_lock():
    """A heartbeat with no shard lock to read (no ``lock`` tags) still gives
    the wake-up and its worst; a beat that does not say its period counts
    as a second."""
    beats = [wsp("runtime.beat", "h", 3.0, ticks=50, late_ms=10.0,
                 period_ms=1000.0, inflight=0)]
    w = {"spans": beats, "w0_ns": 0.0, "w1_ns": 1e9}
    assert load_layer("lock_hold_pct").read(w) is None
    assert load_layer("wakeup_mean_ms").read(w) == pytest.approx(0.2)
    assert load_layer("stall_max_ms").read(w) == pytest.approx(3.0)
    older = [wsp("runtime.beat", "h", 3.0, ticks=50, late_ms=10.0,
                 lock="shard-0-lock", lock_hold_ms=650.0)]
    assert load_layer("lock_hold_pct").read(dict(w, spans=older)) \
        == pytest.approx(65.0)
