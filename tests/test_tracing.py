"""Tracer mechanics (no device): monotonic starts and durations, nested and
cross-thread parentage, root-decided sampling, ring bounds, Zipkin shape,
intervals recorded after the fact; and the served query's spans through an
in-process server: one root, the profiler's clock, lock wait and hold, the
device queue ahead of a dispatch, the gc hook, the heartbeat."""

import contextlib
import gc
import glob
import json
import threading
import time
import urllib.parse
import urllib.request

import pytest

from filodb_tpu.utils import diagnostics
from filodb_tpu.utils.tracing import (SPAN_HTTP_RENDER, SPAN_HTTP_REQUEST,
                                      SPAN_INGEST_CONSUME, SPAN_INGEST_FLUSH,
                                      SPAN_QUERY, SPAN_QUERY_GROUPIDS,
                                      SPAN_QUERY_KERNEL, SPAN_QUERY_LEAF,
                                      SPAN_QUERY_QUEUE, SPAN_QUERY_SELECT,
                                      SPAN_RUNTIME_BEAT, SPAN_RUNTIME_GC,
                                      Tracer, _Heartbeat, tracer)


@pytest.fixture()
def tr():
    return Tracer(capacity=64)


def _by_name(tr):
    return {s.name: s for s in tr.snapshot()}


def test_nested_parentage_single_trace(tr):
    with tr.span("outer"):
        with tr.span("mid"):
            with tr.span("inner", k="v"):
                pass
    spans = _by_name(tr)
    assert set(spans) == {"outer", "mid", "inner"}
    assert len({s.trace_id for s in spans.values()}) == 1
    assert spans["outer"].parent_id is None
    assert spans["mid"].parent_id == spans["outer"].span_id
    assert spans["inner"].parent_id == spans["mid"].span_id
    assert spans["inner"].tags == {"k": "v"}


def test_duration_is_monotonic_not_wall_clock(tr, monkeypatch):
    """A stepped (frozen) system clock must not zero span durations: only
    the exporter's anchor reads time.time(), once, at close; start and
    duration come from perf_counter_ns (the PR-7 no-wall-clock satellite)."""
    frozen = time.time()
    monkeypatch.setattr(time, "time", lambda: frozen)
    with tr.span("work"):
        # burn >= 1ms of real (monotonic) time under the frozen wall clock
        t0 = time.perf_counter_ns()
        while time.perf_counter_ns() - t0 < 2_000_000:
            pass
    rec = tr.snapshot()[0]
    assert rec.duration_us >= 1_000
    # the wall anchor is "now" at close less the monotonic time since start
    assert rec.start_us == int(frozen * 1e6) - rec.duration_us


def test_start_ns_is_monotonic_and_consistent_with_duration(tr):
    before = time.perf_counter_ns()
    with tr.span("outer"):
        with tr.span("inner"):
            time.sleep(0.002)
    after = time.perf_counter_ns()
    sp = _by_name(tr)
    outer, inner = sp["outer"], sp["inner"]
    assert before <= outer.start_ns <= inner.start_ns
    # each interval [start_ns, start_ns + duration] nests on the one clock
    # (duration_us is floored, so an end is known to within a microsecond)
    inner_end = inner.start_ns + inner.duration_us * 1000
    outer_end = outer.start_ns + (outer.duration_us + 1) * 1000
    assert inner.duration_us >= 2_000
    assert inner_end <= outer_end <= after + 1000
    # the debug plane carries it; the Zipkin shape does not change
    assert tr.traces()[0]["spans"][0]["start_ns"] == outer.start_ns
    assert "start_ns" not in outer.to_zipkin()


@pytest.mark.parametrize("enabled,context,want", [
    (True, "span", "child"),        # under an active span: its child
    (True, None, "root"),           # no context: roots a trace of its own
    (False, None, None),            # tracing off, no context: nothing
    (False, "remote", "child"),     # a sampled remote context decides
    (True, "sampled_out", None),    # the root said no
])
def test_record_follows_span_rules(tr, enabled, context, want):
    tr.enabled = enabled
    t0 = time.perf_counter_ns()
    t1 = t0 + 5_000_000
    if context == "span":
        with tr.span("parent"):
            tr.record("waited", t0, t1, priority="QUERY")
        parent = _by_name(tr)["parent"]
        ids = (parent.trace_id, parent.span_id)
    elif context == "remote":
        ids = ("a" * 16, "b" * 16)
        with tr.activate({"trace_id": ids[0], "span_id": ids[1],
                          "sampled": True}):
            tr.record("waited", t0, t1, priority="QUERY")
    elif context == "sampled_out":
        tr.sample_rate = 0.0
        with tr.span("parent"):
            tr.record("waited", t0, t1, priority="QUERY")
    else:
        tr.record("waited", t0, t1, priority="QUERY")
    rec = _by_name(tr).get("waited")
    if want is None:
        assert rec is None and not tr._handoff
        return
    assert (rec.start_ns, rec.duration_us) == (t0, 5_000)
    assert rec.tags == {"priority": "QUERY"} and rec.seq > 0
    if want == "child":
        assert (rec.trace_id, rec.parent_id) == ids
    else:
        assert rec.parent_id is None and len(rec.trace_id) == 16


def test_record_takes_no_lock_and_reaches_ring_with_next_span(tr):
    """The gc hook calls record() from wherever a collection started, also
    on a thread inside the tracer's critical section: it must not need the
    tracer's lock. The record lands with the next span."""
    with tr._lock:
        tr.record("late", time.perf_counter_ns() - 1000,
                  time.perf_counter_ns())
    assert len(tr._handoff) == 1 and not tr.spans
    with tr.span("next"):
        pass
    assert [s.name for s in tr.spans] == ["next", "late"]
    assert [s.seq for s in tr.spans] == [1, 2]


def test_cross_thread_activate_joins_trace(tr):
    """activate() adopts a parent frame on another thread: the worker's
    span joins the caller's trace, parented under the activating span."""
    got = {}

    def worker(ctx):
        with tr.activate(ctx):
            with tr.span("child"):
                pass
        got["done"] = True

    with tr.span("root"):
        ctx = tr.current_context()
        t = threading.Thread(target=worker, args=(ctx,))
        t.start()
        t.join()
    spans = _by_name(tr)
    assert got["done"]
    assert spans["child"].trace_id == spans["root"].trace_id
    assert spans["child"].parent_id == spans["root"].span_id


def test_activate_none_and_malformed_are_noops(tr):
    with tr.activate(None), tr.activate({"junk": 1}), tr.span("solo"):
        pass
    rec = tr.snapshot()[0]
    assert rec.parent_id is None


def test_activate_rejects_hostile_ids(tr):
    """Wire-supplied trace ids reach /metrics exemplar LABELS: anything
    that isn't bounded lowercase hex (quotes, braces, overlong) must be
    refused at adoption so no carrier can corrupt the exposition."""
    for bad in ('x"} garbage', "T" * 16, "a" * 33, "", 7, None):
        with tr.activate({"trace_id": bad, "span_id": "c" * 16,
                          "sampled": True}):
            assert tr.current_context() is None
        with tr.activate({"trace_id": "c" * 16, "span_id": bad,
                          "sampled": True}):
            assert tr.current_context() is None


def test_sampling_decided_at_root_and_propagates(tr):
    tr.sample_rate = 0.0
    with tr.span("root"):
        ctx = tr.current_context()
        assert ctx["sampled"] is False
        with tr.span("child"):
            pass
    assert tr.snapshot() == []          # nothing recorded, no clocks read
    # a REMOTE sampled context overrides even a disabled local tracer:
    # the root decided, every node records
    tr.enabled = False
    with tr.activate({"trace_id": "a" * 16, "span_id": "b" * 16,
                      "sampled": True}):
        with tr.span("adopted"):
            pass
    recs = tr.snapshot()
    assert [s.name for s in recs] == ["adopted"]
    assert recs[0].trace_id == "a" * 16
    assert recs[0].parent_id == "b" * 16


def test_disabled_tracer_records_nothing(tr):
    tr.enabled = False
    with tr.span("ghost"):
        pass
    assert tr.snapshot() == []
    assert tr.current_context() is None


def test_ring_is_bounded(tr):
    for i in range(200):
        with tr.span("s"):
            pass
    assert len(tr.snapshot()) == 64


def test_traces_assemble_parent_then_child(tr):
    with tr.span("a"):
        with tr.span("b"):
            pass
        with tr.span("c"):
            pass
    with tr.span("other"):
        pass
    traces = tr.traces()
    assert len(traces) == 2
    assert traces[0]["spans"][0]["name"] == "other"     # newest first
    names = [s["name"] for s in traces[1]["spans"]]
    assert names[0] == "a" and set(names[1:]) == {"b", "c"}
    # children follow their parent and carry its span_id
    a = traces[1]["spans"][0]
    assert all(s["parent_id"] == a["span_id"] for s in traces[1]["spans"][1:])


def test_span_yields_mutable_tags(tr):
    with tr.span("pub") as tags:
        tags["failovers"] = 2
    assert tr.snapshot()[0].tags["failovers"] == 2


def test_zipkin_reporter_watermark_never_drains_ring(tr, monkeypatch):
    """The exporter must coexist with the debug plane: exporting leaves the
    ring intact, a failed POST retries the same spans, a successful one
    advances the watermark so nothing ships twice."""
    from filodb_tpu.utils.tracing import ZipkinReporter
    posted, fail = [], {"on": True}

    def fake_post(endpoint, spans=None):
        if fail["on"]:
            raise OSError("collector down")
        posted.append([s.seq for s in spans])
        return len(spans)

    monkeypatch.setattr(tr, "post_zipkin", fake_post)
    rep = ZipkinReporter(tr, "http://collector", interval_s=999)
    with tr.span("a"):
        pass
    with pytest.raises(OSError):
        rep.tick()                      # failed export: watermark holds
    assert rep._watermark == 0 and len(tr.snapshot()) == 1
    fail["on"] = False
    assert rep.tick() == 1              # retried the SAME span
    with tr.span("b"):
        pass
    assert rep.tick() == 1              # only the new span ships
    assert posted == [[1], [2]]
    assert len(tr.snapshot()) == 2      # ring untouched throughout
    assert rep.tick() == 0


def test_zipkin_export_shape(tr):
    with tr.span("z", endpoint="e"):
        pass
    rows = json.loads(tr.export_zipkin_json())
    assert len(rows) == 1
    row = rows[0]
    assert set(row) >= {"traceId", "id", "name", "timestamp", "duration",
                        "tags"}
    assert row["name"] == "z" and row["tags"] == {"endpoint": "e"}
    # filtered export by trace id
    assert json.loads(tr.export_zipkin_json(trace_id="nope")) == []


# -- the served query, through an in-process server -------------------------

BASE = 1_700_000_000_000
N_SERIES, N_SAMPLES = 16, 90


@contextlib.contextmanager
def _served():
    """A FiloServer with the shipped defaults (scheduler, tracing on) and
    16 series in 4 groups on a 10 s grid; yields (server, get)."""
    from filodb_tpu.config import Config
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.standalone import FiloServer
    srv = FiloServer(Config({
        "num_shards": 1, "http": {"port": 0},
        "store": {"max_series_per_shard": 32, "samples_per_series": 128,
                  "flush_batch_size": 10**9}})).start()
    b = RecordBuilder(GAUGE)
    for t in range(N_SAMPLES):
        for i in range(N_SERIES):
            b.add({"_metric_": "m", "host": f"h{i}", "g": f"g{i % 4}"},
                  BASE + t * 10_000, float(i + 3 * t))
    srv.memstore.ingest("prometheus", 0, b.build())
    srv.memstore.flush_all()

    def get(promql="sum by (g)(rate(m[2m]))", shift_ms=0):
        # a shifted range is off every cached grid: it executes in full
        q = urllib.parse.urlencode({
            "query": promql, "start": (BASE + 300_000 + shift_ms) / 1000,
            "end": (BASE + 800_000 + shift_ms) / 1000, "step": 10})
        url = (f"http://127.0.0.1:{srv.http.port}/promql/prometheus/api/v1/"
               f"query_range?{q}")
        with urllib.request.urlopen(url, timeout=60) as r:
            return json.load(r)
    try:
        yield srv, get
    finally:
        srv.shutdown()


@pytest.fixture()
def served():
    with _served() as srv_get:
        yield srv_get


def _trace_of_last_query():
    # the request's span closes after the client has read the answer
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        roots = [s for s in tracer.snapshot() if s.name == SPAN_HTTP_REQUEST]
        if roots:
            return [s for s in tracer.snapshot()
                    if s.trace_id == roots[-1].trace_id]
        time.sleep(0.01)
    raise AssertionError("no http.request span was recorded")


def test_http_query_is_one_trace_rooted_at_the_request(served):
    _srv, get = served
    get()                                   # compiles; not the one we read
    _trace_of_last_query()                  # ... and has closed its spans
    tracer.drain()
    body = get(shift_ms=1_000)
    assert body["status"] == "success" and len(body["data"]["result"]) == 4
    members = _trace_of_last_query()
    names = [s.name for s in members]
    roots = [s for s in members if s.parent_id is None]
    assert [s.name for s in roots] == [SPAN_HTTP_REQUEST]
    req = roots[0]
    assert req.tags["route"] == "query_range" and req.tags["status"] == 200
    for name in (SPAN_QUERY_QUEUE, SPAN_QUERY, SPAN_QUERY_LEAF,
                 SPAN_QUERY_SELECT, SPAN_QUERY_GROUPIDS, SPAN_QUERY_KERNEL,
                 SPAN_HTTP_RENDER):
        assert name in names, (name, names)
    assert len(members) <= 24, names
    by = {s.name: s for s in members}
    # queue and query hang under the request, on the worker's side of it
    assert by[SPAN_QUERY_QUEUE].parent_id == req.span_id
    assert by[SPAN_QUERY].parent_id == req.span_id
    assert by[SPAN_QUERY_QUEUE].tags == {"priority": "QUERY"}
    q = by[SPAN_QUERY].tags
    assert (q["start_ms"], q["end_ms"], q["step_ms"]) == (
        BASE + 301_000, BASE + 801_000, 10_000)
    assert q["status"] == "ok" and q["exec_path"] \
        == body["stats"]["exec_path"]
    assert by[SPAN_QUERY_SELECT].tags["series"] == N_SERIES
    assert by[SPAN_QUERY_GROUPIDS].tags == {"keys": N_SERIES, "groups": 4,
                                            "route": "walk"}
    assert by[SPAN_HTTP_RENDER].tags["series"] == 4
    assert by[SPAN_HTTP_RENDER].tags["bytes"] == req.tags["bytes"] > 0
    kernels = [s for s in members if s.name == SPAN_QUERY_KERNEL]
    assert {k.tags["phase"] for k in kernels} == {"dispatch", "fetch"}
    disp = next(k for k in kernels if k.tags["phase"] == "dispatch")
    assert disp.tags["groups"] >= 4 and disp.tags["steps"] == 51
    assert disp.tags["cols"] > 0 and "interpret" in disp.tags["kernel"]
    # the means add up because the parts nest: queue + query + render fit
    # in the request, select + group ids + dispatch in the leaf
    parts = sum(by[n].duration_us for n in
                (SPAN_QUERY_QUEUE, SPAN_QUERY, SPAN_HTTP_RENDER))
    assert parts <= req.duration_us + 3
    leaf = by[SPAN_QUERY_LEAF]
    inner = by[SPAN_QUERY_SELECT].duration_us \
        + by[SPAN_QUERY_GROUPIDS].duration_us + disp.duration_us
    assert inner <= leaf.duration_us + 3
    assert leaf.tags["lock_wait_ms"] == 0
    # nothing was in flight before this query's one program, and its fetch
    # gave the place back
    assert disp.tags["ahead"] == 0 and diagnostics.inflight.count == 0


@contextlib.contextmanager
def _hist_served():
    """A FiloServer over one prom-histogram shard: 16 series in 4 groups,
    8 buckets, 90 samples on a 10 s grid (dataset ``hists``)."""
    import numpy as np

    from filodb_tpu.config import Config
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import PROM_HISTOGRAM
    from filodb_tpu.standalone import FiloServer
    srv = FiloServer(Config({
        "num_shards": 1, "http": {"port": 0}, "dataset": "hists",
        "schema": "prom-histogram",
        "store": {"max_series_per_shard": 32, "samples_per_series": 128,
                  "flush_batch_size": 10**9}})).start()
    try:
        les = np.array([1., 2., 4., 8., 16., 32., 64., np.inf])
        b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
        for t in range(N_SAMPLES):
            for i in range(N_SERIES):
                b.add({"_metric_": "h", "host": f"h{i}", "g": f"g{i % 4}"},
                      BASE + t * 10_000,
                      np.cumsum(np.arange(8) + i + 1.0) * (t + 1))
        srv.memstore.ingest("hists", 0, b.build())
        srv.memstore.flush_all()
        yield srv
    finally:
        srv.shutdown()


@pytest.mark.parametrize("end_s, fall_tiles", [
    (800, 0),     # every window inside the rows' 90 samples
    (950, 1),     # the last ones past them: the one tile with rows in it
])
def test_fused_hist_route_is_a_leaf_with_its_parts_and_a_fetch(end_s,
                                                               fall_tiles):
    """The fused-hist route (query/engine.py ``_try_fused_hist``) records
    what the ExecPlan leaf records: ``query.exec.leaf`` (tags
    ``lock_wait_ms``, ``lock_hold_ms``) > select, group ids, kernel
    dispatch (``ahead``, the tiled raw hist kernel's tags, ``packed``
    among them), and the kernel's fetch
    beside the leaf, after it and outside the lock (tag ``fall_tiles``: the
    tiles that ran the correction matmul) — the sums the benchmark's means
    are read from."""
    from filodb_tpu.ops import fusedresident
    with _hist_served() as srv:
        def get(shift_ms):
            q = urllib.parse.urlencode({
                "query": "histogram_quantile(0.9, sum by (g)(rate(h[2m])))",
                "start": (BASE + (end_s - 500) * 1000 + shift_ms) / 1000,
                "end": (BASE + end_s * 1000 + shift_ms) / 1000, "step": 10})
            url = (f"http://127.0.0.1:{srv.http.port}/promql/hists/api/v1/"
                   f"query_range?{q}")
            with urllib.request.urlopen(url, timeout=60) as r:
                return json.load(r)
        tracer.drain()                      # no earlier test's request
        get(0)                              # compiles; not the one we read
        _trace_of_last_query()
        tracer.drain()
        body = get(1_000)
        path = f"fused-hist[{fusedresident.tag()}]"
        assert body["status"] == "success" \
            and body["stats"]["exec_path"] == path
        members = _trace_of_last_query()
    by = {}
    for s in members:
        by.setdefault(s.name, []).append(s)
    assert by[SPAN_QUERY][0].tags["exec_path"] == path
    (leaf,), (sel,), (gid,) = (by[SPAN_QUERY_LEAF], by[SPAN_QUERY_SELECT],
                               by[SPAN_QUERY_GROUPIDS])
    kern = {k.tags["phase"]: k for k in by[SPAN_QUERY_KERNEL]}
    assert sorted(kern) == ["dispatch", "fetch"]
    disp, fetch = kern["dispatch"], kern["fetch"]
    assert sel.parent_id == gid.parent_id == disp.parent_id == leaf.span_id
    assert fetch.parent_id == leaf.parent_id
    assert fetch.start_ns >= leaf.start_ns + (leaf.duration_us - 2) * 1e3
    assert 0 < leaf.tags.pop("lock_hold_ms") <= leaf.duration_us / 1e3
    assert leaf.tags == {"shard": 0, "lock_wait_ms": 0}
    assert sel.tags["series"] == N_SERIES
    assert gid.tags == {"keys": N_SERIES, "groups": 4, "route": "walk"}
    assert diagnostics.inflight.count == 0          # the fetch gave it back
    assert disp.tags == {
        "phase": "dispatch", "ahead": 0, "kernel": fusedresident.tag(),
        "rows": 32,
        "c0": 0, "cols": 128, "steps": 51, "groups": 4, "buckets": 8,
        "variant": "hist-raw", "packed": 1}
    assert fetch.tags == {"phase": "fetch", "fall_tiles": fall_tiles}
    inner = sel.duration_us + gid.duration_us + disp.duration_us
    assert inner <= leaf.duration_us + 3


def test_global_aggregate_opens_no_groupids_span(served):
    _srv, get = served
    tracer.drain()
    get("sum(rate(m[2m]))")
    assert SPAN_QUERY_LEAF in [s.name for s in _trace_of_last_query()]
    assert SPAN_QUERY_GROUPIDS not in [s.name for s in _trace_of_last_query()]


def _groupids_counts(srv):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.http.port}/metrics", timeout=30) as r:
        text = r.read().decode()
    out = {"index": 0.0, "walk": 0.0}
    for line in text.splitlines():
        if line.startswith("filodb_groupids_total{"):
            route = line.split('route="')[1].split('"')[0]
            out[route] = float(line.rsplit(" ", 1)[1])
    return out


def test_groupids_route_follows_the_selection(served, monkeypatch):
    """A selection wider than GATHER_THRESHOLD is still pids at the
    aggregation and groups by the index's label columns; a narrow one has
    its keys and walks them. The span's tag and the /metrics counter say
    which."""
    from filodb_tpu.query import exec as qexec
    srv, get = served
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", N_SERIES // 2)
    get()                                   # compiles
    _trace_of_last_query()
    before = _groupids_counts(srv)
    tracer.drain()
    wide = get(shift_ms=1_000)
    span_w, = [s for s in _trace_of_last_query()
               if s.name == SPAN_QUERY_GROUPIDS]
    # the selector and the grouping were asked before: the memo's arrays
    assert span_w.tags == {"keys": N_SERIES, "groups": 4, "route": "index",
                           "memo": "hit"}
    mid = _groupids_counts(srv)
    assert (mid["index"], mid["walk"]) == (before["index"] + 1,
                                           before["walk"])
    tracer.drain()
    narrow = get('sum by (g)(rate(m{g=~"g0|g1"}[2m]))', shift_ms=2_000)
    span_n, = [s for s in _trace_of_last_query()
               if s.name == SPAN_QUERY_GROUPIDS]
    assert span_n.tags == {"keys": N_SERIES // 2, "groups": 2,
                           "route": "walk"}
    after = _groupids_counts(srv)
    assert (after["index"], after["walk"]) == (mid["index"], mid["walk"] + 1)
    # the same groups either way, in the same order
    assert [s["metric"] for s in wide["data"]["result"]][:2] \
        == [s["metric"] for s in narrow["data"]["result"]] \
        == [{"g": "g0"}, {"g": "g1"}]


def test_spans_are_in_the_profiler_trace_on_its_clock(served, tmp_path):
    """Every recorded span runs inside a TraceAnnotation of its name: a
    jax.profiler trace of the process holds them in a host plane, nested by
    start and end on the trace's own clock."""
    import jax
    _srv, get = served
    tracer.drain()
    get()                                   # compile outside the trace
    _trace_of_last_query()                  # ... and close its spans there
    tracer.drain()
    jax.profiler.start_trace(str(tmp_path))
    try:
        get(shift_ms=1_000)
        # the request's span, and the annotation around it, close after the
        # client has read the answer: a trace stopped before that lacks it
        _trace_of_last_query()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[-1]
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in (SPAN_HTTP_REQUEST, SPAN_QUERY, SPAN_QUERY_LEAF,
                               SPAN_QUERY_GROUPIDS):
                    found[ev.name] = (ev.start_ns, ev.start_ns
                                      + ev.duration_ns)
    chain = [SPAN_HTTP_REQUEST, SPAN_QUERY, SPAN_QUERY_LEAF,
             SPAN_QUERY_GROUPIDS]
    assert list(found) and set(found) == set(chain), sorted(found)
    for outer, inner in zip(chain, chain[1:]):
        assert found[outer][0] <= found[inner][0] \
            and found[inner][1] <= found[outer][1], (outer, inner, found)


def test_leaf_tags_the_wait_for_a_held_shard_lock(served):
    srv, _get = served
    eng = srv.engines["prometheus"]
    shard = srv.memstore.shards_of("prometheus")[0]
    # with the caches off no epoch probe touches the shard lock before the
    # leaf does (with them on, that probe is where a query waits first, and
    # only the ``query`` span's tag holds that wait)
    eng.result_cache = eng.fragment_cache = None

    def ask():
        return eng.query_range("sum by (g)(rate(m[2m]))", BASE + 300_000,
                               BASE + 800_000, 10_000)
    ask()                                   # compiled
    quiet = [s for s in tracer.snapshot() if s.name == SPAN_QUERY_LEAF][-1]
    assert quiet.tags["lock_wait_ms"] == 0  # uncontended: no clock read
    held, hold_s = threading.Event(), 0.4
    took = {}

    def holder():
        with shard.lock:
            t0 = time.perf_counter()
            held.set()
            time.sleep(hold_s)
            took["s"] = time.perf_counter() - t0

    t = threading.Thread(target=holder)
    t.start()
    assert held.wait(5)
    tracer.drain()
    ask()
    t.join(5)
    assert not t.is_alive()
    spans = {s.name: s for s in tracer.snapshot()}
    leaf = spans[SPAN_QUERY_LEAF]
    assert abs(leaf.tags["lock_wait_ms"] - took["s"] * 1e3) \
        <= 0.2 * took["s"] * 1e3, (leaf.tags, took)
    assert leaf.duration_us / 1e3 >= leaf.tags["lock_wait_ms"]
    assert spans[SPAN_QUERY].tags["lock_wait_ms"] \
        >= leaf.tags["lock_wait_ms"]
    # the lock's own totals moved with it, and /metrics exports them
    assert shard.lock.wait_s >= 0.8 * took["s"]
    assert shard.lock.hold_s >= took["s"]
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{srv.http.port}/metrics", timeout=10).read().decode()
    for name in ("filodb_shard_lock_wait_seconds",
                 "filodb_shard_lock_hold_seconds"):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith(name + "{") and 'shard="0"' in ln)
        assert float(line.rsplit(" ", 1)[1]) > 0, line


def test_every_span_that_tags_a_lock_wait_tags_the_hold(served):
    """``lock_hold_ms`` sits wherever ``lock_wait_ms`` does: what the span's
    thread held shard locks for, counted as each hold is released. A hold
    released inside a nested span is in both tags: the leaf's is in its
    query's, which holds the epoch probe's beside it."""
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    srv, get = served
    shard = srv.memstore.shards_of("prometheus")[0]
    get()                                   # compiled
    _trace_of_last_query()
    tracer.drain()
    held0 = shard.lock.hold_s
    get(shift_ms=1_000)
    by = {s.name: s for s in _trace_of_last_query()}
    leaf, query = by[SPAN_QUERY_LEAF].tags, by[SPAN_QUERY].tags
    assert 0 < leaf["lock_hold_ms"] <= query["lock_hold_ms"]
    assert leaf["lock_hold_ms"] <= by[SPAN_QUERY_LEAF].duration_us / 1e3
    assert query["lock_hold_ms"] <= by[SPAN_QUERY].duration_us / 1e3
    # the lock's own total grew by what the query's thread says it held
    assert abs((shard.lock.hold_s - held0) * 1e3
               - query["lock_hold_ms"]) < 1e-3
    b = RecordBuilder(GAUGE)
    for i in range(N_SERIES):
        b.add({"_metric_": "m", "host": f"h{i}", "g": f"g{i % 4}"},
              BASE + N_SAMPLES * 10_000, 1.0)
    srv.memstore.ingest("prometheus", 0, b.build())
    srv.memstore.flush_all()
    spans = tracer.snapshot()
    (flush,) = [s for s in spans if s.name == SPAN_INGEST_FLUSH]
    assert 0 < flush.tags["lock_hold_ms"] <= flush.duration_us / 1e3
    tagged = [s for s in spans if "lock_wait_ms" in s.tags]
    assert {s.name for s in tagged} == {SPAN_QUERY, SPAN_QUERY_LEAF,
                                        SPAN_INGEST_FLUSH}
    assert all("lock_hold_ms" in s.tags for s in tagged)


def test_consume_span_tags_the_holds_of_the_flushes_inside_it(tmp_path):
    """The consumer's drain holds the shard lock to stage rows and, through
    the flush nested in it, to land them: its ``lock_hold_ms`` has both."""
    from filodb_tpu.config import Config
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.ingest.bus import FileBus
    from filodb_tpu.standalone import FiloServer
    srv = FiloServer(Config({
        "num_shards": 1, "http": {"port": 0}, "bus_dir": str(tmp_path),
        "store": {"max_series_per_shard": 32, "samples_per_series": 128,
                  "flush_batch_size": 8}})).start()
    try:
        tracer.drain()
        b = RecordBuilder(GAUGE)
        for i in range(N_SERIES):
            b.add({"_metric_": "m", "host": f"h{i}"}, BASE, float(i))
        FileBus(str(tmp_path / "shard0.log")).publish(b.build())
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            spans = tracer.snapshot()
            drains = [s for s in spans if s.name == SPAN_INGEST_CONSUME]
            if drains:
                break
            time.sleep(0.05)
    finally:
        srv.shutdown()
    (drain,) = drains
    assert drain.tags["rows"] == N_SERIES
    inside = [s for s in spans if s.name == SPAN_INGEST_FLUSH
              and s.parent_id == drain.span_id]
    assert inside, [s.name for s in spans]
    assert 0 < sum(s.tags["lock_hold_ms"] for s in inside) \
        <= drain.tags["lock_hold_ms"] <= drain.duration_us / 1e3


def test_mesh_leaf_tags_the_sum_over_its_locks_and_how_many():
    """The mesh route's one leaf takes every shard's lock: ``lock_hold_ms``
    is the sum over them, ``locks`` how many, so that a reader can give a
    per-lock figure; the dispatch is counted in flight until its fetch."""
    from filodb_tpu.query.engine import QueryEngine

    from .test_distributed import START, build_f32_store
    mesh, ms, shards = build_f32_store()
    eng = QueryEngine(ms, "prometheus", mesh=mesh)
    before = [sh.lock.hold_s for sh in shards]
    tracer.drain()
    r = eng.query_range("sum(rate(m[5m]))", START + 300_000, START + 500_000,
                        20_000)
    assert r.exec_path == "mesh[pjit]-fused"
    by = {}
    for s in tracer.snapshot():
        by.setdefault(s.name, []).append(s)
    (leaf,), (query,) = by[SPAN_QUERY_LEAF], by[SPAN_QUERY]
    assert leaf.tags["route"] == "mesh" and leaf.tags["locks"] == len(shards)
    grown = sum(sh.lock.hold_s - b for sh, b in zip(shards, before)) * 1e3
    assert 0 < leaf.tags["lock_hold_ms"] <= query.tags["lock_hold_ms"]
    assert abs(query.tags["lock_hold_ms"] - grown) < 1e-3
    # every lock is held for about the leaf's length, so their sum is
    # about ``locks`` times one lock's share
    assert leaf.tags["lock_hold_ms"] / leaf.tags["locks"] \
        <= leaf.duration_us / 1e3
    (disp,) = [k for k in by[SPAN_QUERY_KERNEL]
               if k.tags["phase"] == "dispatch"]
    assert disp.tags["ahead"] == 0 and diagnostics.inflight.count == 0


def _engine_of(route, stack):
    """(engine, promql, start) of one route to the device; servers and
    stores leave with ``stack``."""
    from filodb_tpu.query.engine import QueryEngine

    from .test_distributed import START, build_f32_store
    if route == "fused-hist":
        srv = stack.enter_context(_hist_served())
        return (srv.engines["hists"],
                "histogram_quantile(0.9, sum by (g)(rate(h[2m])))", BASE)
    if route.startswith("mesh"):
        mesh, ms, _shards = build_f32_store()
        return (QueryEngine(ms, "prometheus", mesh=mesh),
                {"mesh": "sum by (grp)(rate(m[5m]))",
                 "mesh-topk": "topk(2, rate(m[5m]))",
                 "mesh-quantile": "quantile(0.5, rate(m[5m]))"}[route], START)
    srv, _get = stack.enter_context(_served())
    return (srv.engines["prometheus"],
            {"fused": "sum by (g)(rate(m[2m]))",
             # a narrow selection: its rows gathered, then the fused kernel
             "gathered": 'sum by (host)(rate(m{g="g0"}[2m]))'}[route], BASE)


@pytest.mark.parametrize("route, path, selects", [
    ("fused", "local-fused[pallas-interpret]", 1),
    ("gathered", "local-fused[pallas-interpret]", 1),
    ("fused-hist", "fused-hist[pallas-interpret]", 1),
    ("mesh", "mesh[pjit]-fused", 8),
    ("mesh-topk", "mesh[pjit]-topk", 8),
    ("mesh-quantile", "mesh[pjit]-sketch", 8),
])
def test_every_route_to_the_device_is_the_one_leaf_protocol(route, path,
                                                            selects):
    """The in-process leaf (wide and gathered), the fused-hist route and the
    mesh route record the SAME tree (``exec.LeafFrame``, ``diagnostics
    .dispatching`` / ``Dispatched``): ``query.exec.leaf`` with the lock's
    wait and hold > select(s), group ids, ``kernel[dispatch]`` with
    ``ahead``; then ``kernel[fetch]`` beside the leaf, after the locks'
    release; and the in-flight count back where it started."""
    with contextlib.ExitStack() as stack:
        eng, promql, t0 = _engine_of(route, stack)
        eng.result_cache = eng.fragment_cache = None
        start, end = t0 + 300_000, t0 + 500_000
        eng.query_range(promql, start, end, 20_000)         # compiled
        assert diagnostics.inflight.count == 0
        tracer.drain()
        r = eng.query_range(promql, start + 1_000, end + 1_000, 20_000)
        spans = tracer.drain()
    assert r.exec_path == path and diagnostics.inflight.count == 0
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    (leaf,) = by[SPAN_QUERY_LEAF]
    kern = {k.tags["phase"]: k for k in by[SPAN_QUERY_KERNEL]}
    assert sorted(kern) == ["dispatch", "fetch"] \
        and len(by[SPAN_QUERY_KERNEL]) == 2
    disp, fetch = kern["dispatch"], kern["fetch"]
    inside = by[SPAN_QUERY_SELECT] + [disp] + (
        [] if route in ("mesh-topk", "mesh-quantile")       # no by()
        else by[SPAN_QUERY_GROUPIDS])
    assert len(by[SPAN_QUERY_SELECT]) == selects
    assert all(s.parent_id == leaf.span_id for s in inside)
    assert all(s.start_ns >= leaf.start_ns for s in inside)
    assert sum(s.duration_us for s in inside) <= leaf.duration_us + 3
    assert leaf.tags["lock_wait_ms"] == 0
    assert 0 < leaf.tags["lock_hold_ms"] <= max(selects, 1) \
        * (leaf.duration_us + 1) / 1e3
    assert disp.tags["ahead"] == 0
    assert fetch.parent_id == leaf.parent_id
    assert fetch.start_ns >= leaf.start_ns + (leaf.duration_us - 2) * 1e3


@pytest.mark.parametrize("promql, reason", [
    ("topk(0, rate(m[5m]))", "topk_caps"),
    ("quantile(0.5, rate(m[5m]))", "order_stat_caps"),
])
def test_a_mesh_leaf_that_gives_up_after_its_ticket_gives_the_place_back(
        promql, reason, monkeypatch):
    """The mesh's caps are met inside the dispatch span, after the place in
    the in-flight count was taken: the route returns without a result, the
    handle is dropped, the count is back, and the host path answers."""
    from filodb_tpu.parallel import distributed
    from filodb_tpu.query import exec as qexec
    from filodb_tpu.query.engine import QueryEngine

    from .test_distributed import START, build_f32_store
    mesh, ms, _shards = build_f32_store()
    eng = QueryEngine(ms, "prometheus", mesh=mesh)
    monkeypatch.setattr(qexec.AggregateMapReduce, "ORDER_STAT_MAX_GROUPS", 0)
    fell = []
    monkeypatch.setattr(distributed, "count_mesh_fallback", fell.append)
    assert diagnostics.inflight.count == 0
    tracer.drain()
    r = eng.query_range(promql, START + 300_000, START + 500_000, 20_000)
    assert fell == [reason] and not r.exec_path.startswith("mesh")
    assert diagnostics.inflight.count == 0
    spans = tracer.drain()
    # the mesh's leaf closed with its tags, and no fetch followed its dispatch
    mesh_leaf = next(s for s in spans if s.name == SPAN_QUERY_LEAF
                     and s.tags.get("route") == "mesh")
    assert {"lock_wait_ms", "lock_hold_ms"} <= set(mesh_leaf.tags)
    (disp,) = [s for s in spans if s.name == SPAN_QUERY_KERNEL
               and s.parent_id == mesh_leaf.span_id]
    assert disp.tags["phase"] == "dispatch" and disp.tags["ahead"] == 0


def test_a_dispatch_is_in_flight_until_fetched_batched_or_dropped():
    """The in-flight count across the fused tier's handles: ``ahead`` of the
    second of two unfetched dispatches is 1; a ``resolve()`` gives a place
    back, so does the batched fetch of a cross-shard merge (``parts_of``),
    so does a handle dropped unfetched."""
    import jax.numpy as jnp
    import numpy as np

    from filodb_tpu.ops import fusedgrid
    from filodb_tpu.query.exec import AggPartial, _merge_partials
    S, C, iv = 8, 128, 10_000
    val = jnp.asarray(np.cumsum(np.ones((S, C), np.float32), axis=1))
    n = jnp.full(S, C, jnp.int32)
    gids = jnp.zeros(S, jnp.int32)
    out_ts = np.arange(BASE + 300_000, BASE + 800_001, iv, dtype=np.int64)

    def dispatch():
        return fusedgrid.fused_grid_aggregate(
            "sum", "rate", val, n, gids, 1, out_ts, 120_000, BASE, iv,
            fetch=False)

    assert diagnostics.inflight.count == 0
    tracer.drain()
    a, b = dispatch(), dispatch()
    aheads = [s.tags["ahead"] for s in tracer.snapshot()
              if s.name == SPAN_QUERY_KERNEL]
    assert aheads == [0, 1] and diagnostics.inflight.count == 2
    want = a.resolve()["sum"]
    assert diagnostics.inflight.count == 1
    c = dispatch()
    merged = _merge_partials("sum", [
        AggPartial("sum", out_ts, p, ["k"], 1, None) for p in (b, c)])
    assert diagnostics.inflight.count == 0              # the batched fetch
    np.testing.assert_allclose(merged.parts["sum"][:1], 2 * want)
    d = dispatch()
    assert diagnostics.inflight.count == 1
    del d                                               # never fetched
    assert diagnostics.inflight.count == 0


def test_gc_hook_records_full_collections_and_leaves_with_the_server(served):
    srv, _get = served
    assert tracer._on_gc in gc.callbacks
    gc.collect()                            # settle what start-up left
    tracer.drain()
    gc.collect()
    gc.collect(1)                           # a younger generation: no span
    spans = [s for s in tracer.snapshot() if s.name == SPAN_RUNTIME_GC]
    assert len(spans) == 1 and spans[0].tags["collected"] >= 0
    assert spans[0].duration_us > 0
    srv.shutdown()
    assert tracer._on_gc not in gc.callbacks
    tracer.drain()
    gc.collect()
    assert not [s for s in tracer.snapshot() if s.name == SPAN_RUNTIME_GC]


# -- the heartbeat ------------------------------------------------------------

def _beat_threads():
    return [t for t in threading.enumerate() if t.name == "trace-heartbeat"]


def _beats(spans):
    return [s for s in spans if s.name == SPAN_RUNTIME_BEAT]


def test_heartbeat_is_shared_like_the_gc_hook_and_beats_once_a_second():
    """Two servers, one heartbeat thread, gone after the last shutdown; one
    ``runtime.beat`` a second of ~50 wake-ups, whose interval is the worst
    wake-up (not the second) and whose lock tags are the LOCK's side: a
    hold by a thread that opens no span is in them."""
    from filodb_tpu.config import Config
    from filodb_tpu.standalone import FiloServer

    def server():
        return FiloServer(Config({
            "num_shards": 1, "http": {"port": 0},
            "store": {"max_series_per_shard": 32, "samples_per_series": 128,
                      "flush_batch_size": 10**9}})).start()
    assert not _beat_threads()
    a = server()
    try:
        b = server()
        try:
            assert len(_beat_threads()) == 1
            tracer.drain()
            lock = a.memstore.shards_of("prometheus")[0].lock
            with lock:                      # no span around this hold
                time.sleep(0.3)
            deadline = time.monotonic() + 4     # two beats: ~2 s
            while time.monotonic() < deadline:
                beats = _beats(tracer.snapshot())
                if len(beats) >= 2 and sum(
                        s.tags["lock_hold_ms"] for s in beats) >= 290:
                    break
                time.sleep(0.05)
        finally:
            b.shutdown()
        assert len(_beat_threads()) == 1    # the first server's still
    finally:
        a.shutdown()
    assert not _beat_threads()
    assert 2 <= len(beats) <= 3, beats
    for s in beats:
        t = s.tags
        assert 20 <= t["ticks"] <= 50, t    # fewer under a loaded host
        assert 1000 <= t["period_ms"] < 1500 and t["inflight"] == 0
        assert 0 <= t["late_ms"] <= t["period_ms"] - 20 * t["ticks"] + 1
        assert s.duration_us / 1e3 <= t["late_ms"] + 1e-3   # the worst one
        assert "stall" not in t and t["lock"] == "shard-0-lock"
    assert 290 <= sum(s.tags["lock_hold_ms"] for s in beats) <= 600
    assert max(s.tags["lock_hold_ms"] for s in beats) >= 150


def test_no_heartbeat_and_no_beat_with_tracing_off(tr):
    """``trace.enabled: false`` starts no heartbeat thread; a heartbeat
    whose tracer is switched off under it counts and records nothing."""
    from filodb_tpu.config import Config
    from filodb_tpu.standalone import FiloServer
    was = tracer.enabled
    try:
        srv = FiloServer(Config({
            "num_shards": 1, "http": {"port": 0}, "trace": {"enabled": False},
            "store": {"max_series_per_shard": 32, "samples_per_series": 128,
                      "flush_batch_size": 10**9}})).start()
        try:
            assert not tracer.enabled and not _beat_threads()
        finally:
            srv.shutdown()
    finally:
        tracer.enabled = was
    tr.enabled = False
    beat = _Heartbeat(tr)
    for k in range(1, 120):                 # 2.4 s on a clock of its own
        beat.tick(beat._t0 + k * 20_000_000, beat._t0 + k * 20_000_000 + 10)
    assert not tr.snapshot() and beat._ticks == 0


def test_beat_is_the_seconds_worst_wakeup_on_a_stubbed_clock(tr):
    """``tick`` takes its clock readings as arguments. 45 wake-ups 0.1 ms
    late and one 30 ms late make one beat: ``late_ms`` their sum, the
    interval the worst one alone (due -> woke), so that an idle gap it names
    is one in which the interpreter really was not to be had."""
    lock = diagnostics.TimedRLock("shard-7-lock", order_class="shard")
    quiet = diagnostics.TimedRLock("shard-8-lock", order_class="shard")
    tr._beat_probes.append(lambda: [lock, quiet])
    beat = _Heartbeat(tr)
    t0, due, worst = beat._t0, beat._t0, None
    with lock:
        pass                                # held a moment: the busiest
    for k in range(45):
        due += 20_100_000
        late = 30_000_000 if k == 20 else 100_000
        if k == 20:
            worst = due
        beat.tick(due, due + late)
        due += late - 100_000
    assert not tr.snapshot()                # 45 x 20.1 ms + 29.9 < a second
    beat.tick(t0 + 1_000_000_000, t0 + 1_000_100_000)
    (rec,) = tr.snapshot()
    assert rec.name == SPAN_RUNTIME_BEAT and rec.parent_id is None
    assert (rec.start_ns, rec.duration_us) == (worst, 30_000)
    t = rec.tags
    assert t["ticks"] == 46 and abs(t["late_ms"] - (45 * 0.1 + 30)) < 1e-6
    assert t["period_ms"] == 1000.1 and t["inflight"] == 0
    assert t["lock"] == "shard-7-lock"
    assert abs(t["lock_hold_ms"] - lock.hold_s * 1e3) < 1e-9
    assert "stall" not in t
    # the next period starts from the locks' totals as they stand
    beat.tick(t0 + 2_000_100_000, t0 + 2_000_100_000)
    assert tr.snapshot()[-1].tags["lock_hold_ms"] == 0


def test_heartbeat_reads_a_late_wakeup_while_a_python_loop_holds_the_gil(tr):
    """A thread coming out of its sleep needs the GIL back as a worker
    coming out of a device fetch does: with a pure-Python loop holding it
    for a 20 ms switch interval at a time, the mean wake-up is milliseconds
    (it is tens of microseconds in a quiet process)."""
    import sys
    stop = threading.Event()

    def spin():
        x = 0
        while not stop.is_set():
            x += 1

    probe = list
    was = sys.getswitchinterval()
    sys.setswitchinterval(0.02)
    busy = threading.Thread(target=spin)
    try:
        busy.start()
        tr.start_heartbeat(probe)
        deadline = time.monotonic() + 5
        while not _beats(tr.snapshot()) and time.monotonic() < deadline:
            time.sleep(0.05)
    finally:
        sys.setswitchinterval(was)
        stop.set()
        busy.join(5)
        tr.stop_heartbeat(probe)
    assert not busy.is_alive() and not _beat_threads()
    t = _beats(tr.snapshot())[0].tags
    assert t["late_ms"] / t["ticks"] >= 2.0, t
    assert "lock" not in t                  # no server, no shard lock


def test_a_wakeup_over_a_second_late_is_a_stall_with_what_stood(tr, caplog):
    """A stubbed wake-up 1.5 s late: the beat carries ``stall`` = 1, the
    shard lock that is held, its holder and since when, the oldest
    unfetched dispatch's age, whether a full collection overlapped; ONE
    warning says the same and ``filodb_runtime_stalls_total`` counts it."""
    import logging

    from filodb_tpu.utils.metrics import FILODB_RUNTIME_STALLS, registry
    lock = diagnostics.TimedRLock("shard-3-lock", order_class="shard")
    free = diagnostics.TimedRLock("shard-4-lock", order_class="shard")
    tr._beat_probes.append(lambda: [free, lock])
    beat = _Heartbeat(tr)
    stalls = registry.counter(FILODB_RUNTIME_STALLS)
    n0 = stalls.value
    held, release = threading.Event(), threading.Event()

    def holder():
        with lock:
            held.set()
            release.wait(5)

    th = threading.Thread(target=holder, name="flush-of-shard-3")
    th.start()
    ticket = diagnostics.inflight.dispatched()
    try:
        assert held.wait(5)
        time.sleep(0.02)
        due = beat._t0 + 20_000_000
        with caplog.at_level(logging.WARNING, logger="filodb_tpu.trace"):
            beat.tick(due, due + 1_500_000_000)
            # a full collection inside the next late wake-up
            tr._gc_last = (due + 1_600_000_000, due + 1_900_000_000)
            beat.tick(due + 1_520_000_000, due + 3_000_000_000)
    finally:
        ticket.fetched()
        release.set()
        th.join(5)
    assert not th.is_alive()
    first, second = _beats(tr.snapshot())
    t = first.tags
    assert (first.start_ns, first.duration_us) == (due, 1_500_000)
    assert t["stall"] == 1 and t["gc"] == 0 and t["ticks"] == 1
    assert (t["held_lock"], t["holder"]) == ("shard-3-lock",
                                             "flush-of-shard-3")
    assert 20 <= t["held_ms"] < 5000 and 20 <= t["oldest_dispatch_ms"] < 5000
    assert t["inflight"] >= 1 and t["late_ms"] == 1500.0
    assert second.tags["stall"] == 1 and second.tags["gc"] == 1
    warned = [r for r in caplog.records if r.name == "filodb_tpu.trace"]
    assert len(warned) == 2 and stalls.value == n0 + 2
    text = warned[0].getMessage()
    for part in ("1500 ms late", "shard-3-lock by flush-of-shard-3",
                 "oldest unfetched dispatch", "full collection inside it: no"):
        assert part in text, text
    assert "full collection inside it: yes" in warned[1].getMessage()


@pytest.mark.parametrize("missed", (0, 3))
def test_missed_scrapes_show_in_the_flush_the_select_and_the_dispatch(
        served, missed):
    """PR 35's three tags, over HTTP: a flush says how many cells it left
    without a sample, the select span how many of the selected rows' used
    cells are holes, the dispatch span which mode of the line program ran.
    A store without holes (``missed`` 0) runs the mode it ran before and
    says so; a grid store's spans carry none of them."""
    from filodb_tpu.core.chunkstore import STALE_NAN
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.utils.tracing import SPAN_INGEST_FLUSH
    srv, get = served
    tracer.drain()                          # no other test's request
    get()
    grid = _trace_of_last_query()
    (sel,) = [s for s in grid if s.name == SPAN_QUERY_SELECT]
    (disp,) = [s for s in grid if s.name == SPAN_QUERY_KERNEL
               and s.tags["phase"] == "dispatch"]
    assert disp.tags["stamps"] == "grid" and "holes" not in disp.tags
    assert "hole_cells" not in sel.tags and "used_cells" not in sel.tags
    tracer.drain()
    # scrapes 90..95, 7 ms late (the form turns); of scrape 92 ``missed``
    # series carry a staleness marker instead of a sample
    b = RecordBuilder(GAUGE)
    for t in range(N_SAMPLES, N_SAMPLES + 6):
        for i in range(N_SERIES):
            stale = t == N_SAMPLES + 2 and i < missed
            b.add({"_metric_": "m", "host": f"h{i}", "g": f"g{i % 4}"},
                  BASE + t * 10_000 + (0 if stale else 7),
                  STALE_NAN if stale else float(i + 3 * t))
    srv.memstore.ingest("prometheus", 0, b.build())
    srv.memstore.flush_all()
    flushes = [s for s in tracer.drain() if s.name == SPAN_INGEST_FLUSH]
    assert sum(s.tags["holes"] for s in flushes) == missed
    assert sum(s.tags["rows"] for s in flushes) == 6 * N_SERIES
    body = get(shift_ms=150_001)         # off the cached grid
    assert body["stats"]["exec_path"].startswith("local-fused[")
    line = _trace_of_last_query()
    (sel,) = [s for s in line if s.name == SPAN_QUERY_SELECT]
    (disp,) = [s for s in line if s.name == SPAN_QUERY_KERNEL
               and s.tags["phase"] == "dispatch"]
    assert (sel.tags["hole_cells"], sel.tags["used_cells"]) == (
        missed, N_SERIES * (N_SAMPLES + 6))
    assert (disp.tags["stamps"], disp.tags["holes"]) == (
        "line", int(missed > 0))
    store = srv.memstore.shards_of("prometheus")[0].store
    assert store.hole_cells == missed == store.stats.stale_markers


def test_births_show_in_the_dispatch_the_select_and_slash_metrics(served):
    """PR 49's tags, over HTTP: a store without a row born late runs the
    grid program it ran before and says ``births`` 0; a series that appears
    later is given a birth cell, the NEXT query's one dispatch says
    ``births`` 1 and how many of its selected rows were born late, its
    select span ``demoted`` 0 and why the memo did not serve it, and
    ``/metrics`` counts the series given a birth cell."""
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import GAUGE
    srv, get = served
    tracer.drain()
    get()
    (sel,) = [s for s in _trace_of_last_query() if s.name == SPAN_QUERY_SELECT]
    (disp,) = [s for s in _trace_of_last_query()
               if s.name == SPAN_QUERY_KERNEL
               and s.tags["phase"] == "dispatch"]
    assert (disp.tags["stamps"], disp.tags["births"],
            disp.tags["born_late"]) == ("grid", 0, 0)
    # (sixteen series are a selection the memo does not keep, and says so)
    assert sel.tags["demoted"] == 0 and sel.tags["memo_why"] == "narrow"
    tracer.drain()
    # scrapes 90..95 of every series and of three NEW ones, born at 92
    b = RecordBuilder(GAUGE)
    for t in range(N_SAMPLES, N_SAMPLES + 6):
        for i in range(N_SERIES + 3):
            if i >= N_SERIES and t < N_SAMPLES + 2:
                continue
            b.add({"_metric_": "m", "host": f"h{i}", "g": f"g{i % 4}"},
                  BASE + t * 10_000, float(i + 3 * t))
    srv.memstore.ingest("prometheus", 0, b.build())
    srv.memstore.flush_all()
    store = srv.memstore.shards_of("prometheus")[0].store
    assert store.born_late == 3 and store.grid_cohorts() == ("uniform", 0)
    assert store.born[N_SERIES:N_SERIES + 3].tolist() == [N_SAMPLES + 2] * 3
    body = get(shift_ms=150_001)            # off the cached grid
    assert body["stats"]["exec_path"].startswith("local-fused[")
    spans = _trace_of_last_query()
    (sel,) = [s for s in spans if s.name == SPAN_QUERY_SELECT]
    disp = [s for s in spans if s.name == SPAN_QUERY_KERNEL
            and s.tags["phase"] == "dispatch"]
    assert len(disp) == 1                   # ONE program
    assert (disp[0].tags["births"], disp[0].tags["born_late"]) == (1, 3)
    assert sel.tags["demoted"] == 0
    # the query ends before the newest birth: the select runs the index's
    # time-masked pass and says so (sixteen series: still not kept)
    tracer.drain()
    get(shift_ms=-150_001)
    (sel,) = [s for s in _trace_of_last_query() if s.name == SPAN_QUERY_SELECT]
    assert (sel.tags["memo"], sel.tags["memo_why"]) == ("bypass", "time_mask")
    url = f"http://127.0.0.1:{srv.http.port}/metrics"
    with urllib.request.urlopen(url, timeout=30) as r:
        lines = r.read().decode().splitlines()
    assert 'filodb_store_births_total{aligned="true",shard="0"} 3' in lines
    assert 'filodb_store_births_total{aligned="false",shard="0"} 0' in lines
