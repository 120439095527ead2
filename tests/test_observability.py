"""PR 7 observability plane: per-query stats accounting, the slow-query
ring + debug HTTP endpoints, exemplar-tagged latency histograms, and the
ingest trace surviving a fault-injected leader failover."""

import contextlib
import json
import time
import urllib.request

import numpy as np
import pytest

from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE
from filodb_tpu.http.api import FiloHttpServer
from filodb_tpu.ingest.faults import FaultPlan, FaultRule
from filodb_tpu.query import wire
from filodb_tpu.query.engine import QueryEngine, slow_query_log
from filodb_tpu.query.rangevector import (QueryStats, RangeVectorKey,
                                          ResultMatrix)
from filodb_tpu.utils.tracing import (SPAN_BROKER_APPEND, SPAN_INGEST_PUBLISH,
                                      SPAN_REPLICATE_SERVE, tracer)

from .test_replication import make_pair, mk, sleepless_bus

START = 1_000_000
STEP = 10_000


@pytest.fixture()
def engine():
    ms = TimeSeriesMemStore()
    ms.setup("obs", GAUGE, 0, StoreConfig(max_series_per_shard=32,
                                          samples_per_series=256,
                                          flush_batch_size=10**9))
    b = RecordBuilder(GAUGE)
    for t in range(60):
        for s in range(6):
            b.add({"_metric_": "m", "_ws_": "w", "_ns_": "n",
                   "host": f"h{s}"}, START + t * STEP, float(s + t))
    ms.ingest("obs", 0, b.build())
    ms.flush_all()
    return QueryEngine(ms, "obs")


def test_query_stats_accounting_local(engine):
    res = engine.query_range("sum(rate(m[2m]))", START + 200_000,
                             START + 500_000, 30_000)
    st = res.stats.to_dict()
    assert st["series_matched"] == 6
    assert st["blocks_raw"] + st["blocks_narrow"] == 1     # one shard leaf
    T = len(np.arange(START + 200_000, START + 500_001, 30_000))
    assert st["result_cells"] == 1 * T
    for stage in ("parse", "plan", "execute"):
        assert st["stage_ms"].get(stage, 0) >= 0
        assert stage in st["stage_ms"]


def test_stats_wrapper_codec_merges_peer_stats():
    m = ResultMatrix(np.arange(3, dtype=np.int64),
                     np.ones((1, 3)), [RangeVectorKey(())])
    peer = QueryStats()
    peer.add("series_matched", 7)
    peer.add("rows_paged_in", 5)
    with peer.stage("peer_exec"):
        pass
    buf = wire.serialize_result(m, stats=peer)
    acc = QueryStats()
    back = wire.deserialize_result(buf, stats=acc)
    assert isinstance(back, ResultMatrix)
    assert acc.series_matched == 7 and acc.rows_paged_in == 5
    assert "peer_exec" in acc.stage_ms
    # stats-blind callers unwrap transparently
    back2 = wire.deserialize_result(buf)
    np.testing.assert_array_equal(np.asarray(back2.values),
                                  np.asarray(m.values))


@pytest.fixture()
def server(engine):
    engine.config.slow_log_threshold_ms = 0.0      # log every query
    slow_query_log.clear()
    srv = FiloHttpServer({"obs": engine}, port=0).start()
    try:
        yield srv
    finally:
        srv.stop()


def _get(srv, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}{path}", timeout=10.0) as r:
        return r.read()


def test_http_response_carries_stats_and_slow_log(server):
    body = json.loads(_get(
        server, "/promql/obs/api/v1/query_range?query=sum(m)"
        f"&start={(START + 200_000) / 1000}&end={(START + 500_000) / 1000}"
        "&step=30"))
    assert body["status"] == "success"
    assert body["stats"]["series_matched"] == 6
    assert body["stats"]["result_cells"] > 0

    entries = json.loads(_get(server, "/api/v1/debug/slow_queries"))["data"]
    assert entries, "threshold 0 must log every query"
    e = entries[0]
    assert e["promql"] == "sum(m)"
    assert e["duration_ms"] > 0
    assert e["plan"] == "local"
    assert e["stats"]["series_matched"] == 6
    assert e["trace_id"] and len(e["trace_id"]) == 16
    # the slow query's trace is queryable by exactly that id; the request
    # on its handler thread is the root and the query hangs under it (its
    # span closes once the answer is written, a moment after we read it)
    for _ in range(200):
        data = json.loads(_get(
            server, f"/api/v1/debug/traces?trace_id={e['trace_id']}"))["data"]
        assert len(data) == 1
        names = [s["name"] for s in data[0]["spans"]]
        if "http.request" in names:
            break
        time.sleep(0.01)
    assert names[0] == "http.request" and "query" in names
    query = next(s for s in data[0]["spans"] if s["name"] == "query")
    assert query["parent_id"] == data[0]["spans"][0]["span_id"]


def test_metrics_exemplar_carries_trace_id(server):
    _get(server, "/promql/obs/api/v1/query_range?query=sum(m)"
         f"&start={(START + 200_000) / 1000}&end={(START + 500_000) / 1000}"
         "&step=30")
    text = _get(server, "/metrics").decode()
    assert 'filodb_query_latency_ms_bucket{dataset="obs",le="1"}' in text
    # the metrics registry is process-global: scope to THIS dataset's series
    ex = [ln for ln in text.splitlines()
          if ln.startswith('filodb_query_latency_ms_exemplar{dataset="obs"')]
    assert len(ex) == 1
    assert 'trace_id="' in ex[0]
    tid = ex[0].split('trace_id="')[1].split('"')[0]
    assert len(tid) == 16
    # the exemplar points at a real, queryable trace
    data = json.loads(_get(server,
                           f"/api/v1/debug/traces?trace_id={tid}"))["data"]
    assert len(data) == 1


def test_debug_started_profiler_dies_with_server(engine):
    """A profiler started over the debug plane must not outlive the
    server: its sampling thread wakes every 100ms forever otherwise."""
    import threading
    srv = FiloHttpServer({"obs": engine}, port=0).start()
    _get(srv, "/api/v1/debug/profile?action=start")
    prof = srv.profiler
    assert prof is not None and prof._thread.is_alive()
    srv.stop()
    assert srv.profiler is None
    assert not prof._thread.is_alive()
    assert not any(t.name == "filodb-profiler" and t.is_alive()
                   for t in threading.enumerate())


def test_profile_debug_endpoint_lifecycle(server):
    st = json.loads(_get(server, "/api/v1/debug/profile"))["data"]
    assert st == {"running": False, "report": None}
    st = json.loads(_get(server,
                         "/api/v1/debug/profile?action=start"))["data"]
    assert st["running"] is True
    st = json.loads(_get(server, "/api/v1/debug/profile"))["data"]
    assert st["running"] is True and "SimpleProfiler report" in st["report"]
    st = json.loads(_get(server,
                         "/api/v1/debug/profile?action=stop"))["data"]
    assert st["running"] is False and "SimpleProfiler report" in st["report"]
    st = json.loads(_get(server, "/api/v1/debug/profile"))["data"]
    assert st == {"running": False, "report": None}


def test_sampled_out_queries_log_no_dead_end_trace_id(engine):
    """With sampling, an unsampled query's slow-log entry (and exemplar)
    must carry NO trace id — a recorded id that /api/v1/debug/traces can't
    resolve is worse than none."""
    engine.config.slow_log_threshold_ms = 0.0
    slow_query_log.clear()
    was = (tracer.enabled, tracer.sample_rate)
    tracer.sample_rate = 0.0
    try:
        engine.query_range("sum(m)", START + 200_000, START + 500_000,
                           30_000)
    finally:
        tracer.enabled, tracer.sample_rate = was
    e = slow_query_log.entries()[0]
    assert e["trace_id"] is None
    assert e["plan"] == "local"        # per-query path still recorded


def test_slow_log_threshold_null_disables_and_int_parses():
    from filodb_tpu.config import Config
    assert Config({"query": {"slow_log_threshold_ms": None}}) \
        .query_config().slow_log_threshold_ms is None
    assert Config({"query": {"slow_log_threshold_ms": 250}}) \
        .query_config().slow_log_threshold_ms == 250.0


def test_failed_query_still_reaches_latency_and_slow_log(engine):
    """A query that runs and then raises is exactly what the slow-query log
    exists to surface — accounting happens in a finally, with the error
    recorded on the entry."""
    from filodb_tpu.query.rangevector import QueryError
    from filodb_tpu.utils.metrics import FILODB_QUERY_LATENCY_MS, registry
    engine.config.slow_log_threshold_ms = 0.0
    engine.config.sample_limit = 1            # force a sample-limit failure
    slow_query_log.clear()
    hist = registry.histogram(FILODB_QUERY_LATENCY_MS,
                              {"dataset": engine.dataset})
    n0 = hist.count
    with pytest.raises(QueryError):
        engine.query_range("m", START + 200_000, START + 500_000, 30_000)
    assert hist.count == n0 + 1
    e = slow_query_log.entries()[0]
    assert e["promql"] == "m" and e["error"].startswith("QueryError")
    assert e["stats"]["series_matched"] == 6   # work done before the raise


def test_publish_histogram_skips_failed_groups(tmp_path):
    """Breaker-shed / dead-broker publish groups never completed a round
    trip — they must not record into the publish-latency histogram."""
    from filodb_tpu.utils.metrics import (FILODB_INGEST_PUBLISH_LATENCY_MS,
                                          registry)
    dead = "127.0.0.1:1"                      # nothing listens there
    bus = sleepless_bus([dead], 0, max_retries=2)
    hist = registry.histogram(FILODB_INGEST_PUBLISH_LATENCY_MS,
                              {"partition": "0"})
    n0 = hist.count
    with pytest.raises(OSError):
        bus.publish_batch([mk("x")])
    assert hist.count == n0
    bus.close()


def test_ingest_trace_survives_leader_failover(tmp_path):
    """Fault-injected: the leader dies mid-window (kill-at-offset). The
    client replays the SAME publish span's context at the survivor, so the
    whole publish — original append, failover, survivor append — is ONE
    trace, with the failover tagged on the client span and append spans
    from BOTH broker nodes."""
    plan = FaultPlan([FaultRule("append", "kill_server", partition=0,
                                at_offset=4)])
    peers, a, b = make_pair(tmp_path, fault_plan_a=plan)
    try:
        tracer.drain()
        bus = sleepless_bus(peers, 0, publish_window=2)
        offs = bus.publish_batch([mk(f"k{i}") for i in range(10)])
        assert sorted(offs) == list(range(10))
        assert bus._cur == 1                      # failed over

        spans = tracer.snapshot()
        pubs = [s for s in spans if s.name == SPAN_INGEST_PUBLISH]
        assert len(pubs) == 1                     # one pipelined group
        tid = pubs[0].trace_id
        assert pubs[0].tags.get("failovers", 0) >= 1
        members = [s for s in spans if s.trace_id == tid]
        # every span of the publish — client, both brokers' appends, the
        # replication legs — shares the one trace id
        assert {s.name for s in members} >= {SPAN_INGEST_PUBLISH,
                                             SPAN_BROKER_APPEND}
        append_brokers = {s.tags["broker"] for s in members
                          if s.name == SPAN_BROKER_APPEND}
        assert append_brokers == {a.port, b.port}, append_brokers
        # before the kill, the replication leg reached the follower under
        # the same trace
        assert any(s.name == SPAN_REPLICATE_SERVE and s.trace_id == tid
                   for s in spans)
        bus.close()
    finally:
        with contextlib.suppress(Exception):
            a.stop()
        b.stop()
