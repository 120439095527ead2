"""Three-node topology proofs (VERDICT item 4): shards spread over three
nodes, spanning-query parity from every entry point, kill one node and assert
its shards split across BOTH survivors with replan-once handling the
partially-changed routes (ref: coordinator/src/multi-jvm/
ClusterRecoverySpec.scala, doc/sharding.md §Automatic Reassignment)."""

import json
import urllib.request

import numpy as np
import pytest

from filodb_tpu.core.memstore import TimeSeriesMemStore
from filodb_tpu.core.schemas import GAUGE
from filodb_tpu.http.api import FiloHttpServer
from filodb_tpu.parallel.cluster import ShardManager
from filodb_tpu.parallel.shardmapper import ShardMapper
from filodb_tpu.query import wire
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.utils.tracing import (SPAN_QUERY, SPAN_QUERY_DISPATCH,
                                      SPAN_QUERY_SERVE, tracer)

from .test_remote_exec import DATASET, START, _as_comparable, _cfg, _ingest

NODES = ("a", "b", "c")
# 8 shards (the mapper is power-of-two) over 3 nodes: the least-loaded
# strategy deals a=3, b=3, c=2 — killing c exercises a SPLIT reassignment
NSHARDS = 8


@pytest.fixture()
def three_node():
    """Three nodes, two shards each. EVERY node's memstore holds every
    shard's data (the post-takeover state any survivor reaches after
    recovery) so reassignment is immediately servable; routing before the
    kill still honors the ShardManager's ownership map."""
    mgr = ShardManager()
    for n in NODES:
        mgr.add_node(n)
    mgr.add_dataset(DATASET, NSHARDS)
    owner = {s: mgr.node_of(DATASET, s) for s in range(NSHARDS)}
    per_node = {n: sorted(mgr.shards_of_node(DATASET, n)) for n in NODES}
    assert all(len(v) >= 2 for v in per_node.values())

    stores = {n: TimeSeriesMemStore() for n in NODES}
    oracle_ms = TimeSeriesMemStore()
    for s in range(NSHARDS):
        oracle_ms.setup(DATASET, GAUGE, s, _cfg())
        for n in NODES:
            stores[n].setup(DATASET, GAUGE, s, _cfg())
    for i in range(12):
        s = i % NSHARDS
        _ingest(oracle_ms, s, i)
        for n in NODES:
            _ingest(stores[n], s, i)
    for ms in (*stores.values(), oracle_ms):
        ms.flush_all()

    eps: dict[str, str] = {}
    engines = {n: QueryEngine(stores[n], DATASET, ShardMapper(8),
                              cluster=mgr, node=n, endpoint_resolver=eps.get)
               for n in NODES}
    servers = {n: FiloHttpServer({DATASET: engines[n]}, port=0).start()
               for n in NODES}
    for n, srv in servers.items():
        eps[n] = f"127.0.0.1:{srv.port}"
    oracle = QueryEngine(oracle_ms, DATASET, ShardMapper(8))
    try:
        yield engines, oracle, mgr, eps, servers, owner
    finally:
        for srv in servers.values():
            srv.stop()


def test_three_node_spanning_parity(three_node):
    """A spanning query issued to ANY of the three nodes matches the
    single-node oracle bit-for-bit, and costs one round-trip per PEER (two
    peers, each owning two shards => exactly two /exec POSTs)."""
    engines, oracle, _mgr, eps, _servers, _owner = three_node
    start, end, step = START + 600_000, START + 900_000, 30_000
    for query in ('sum(rate(m[2m]))', 'avg by (dc) (m)', 'topk(3, m)',
                  'count(m)'):
        want = _as_comparable(oracle.query_range(query, start, end, step))
        for n in NODES:
            before = wire.breakers.total_requests()
            got = _as_comparable(
                engines[n].query_range(query, start, end, step))
            made = wire.breakers.total_requests() - before
            assert got == want, f"node {n} diverged from oracle on {query!r}"
            assert made == 2, (f"node {n} cost {made} round-trips on "
                               f"{query!r}; expected one per peer")


def test_one_query_one_trace_with_spans_from_every_node(three_node):
    """PR 7 acceptance: a spanning query yields ONE trace id whose spans
    cover BOTH remote peers (context crosses the /exec wire), the response
    stats equal the single-node oracle's (peer stats merge into the
    caller's accumulator), and the trace is queryable at
    /api/v1/debug/traces — valid Zipkin v2 JSON under ?format=zipkin."""
    engines, oracle, _mgr, eps, servers, owner = three_node
    start, end, step = START + 600_000, START + 900_000, 30_000
    want = oracle.query_range('sum(rate(m[2m]))', start, end, step)
    tracer.drain()
    got = engines["a"].query_range('sum(rate(m[2m]))', start, end, step)
    assert _as_comparable(got) == _as_comparable(want)

    # stats: cluster-aggregated counters equal the oracle's local-only run
    ws, gs = want.stats.to_dict(), got.stats.to_dict()
    for field in ("series_matched", "result_cells"):
        assert gs[field] == ws[field] > 0, field
    assert gs["blocks_raw"] + gs["blocks_narrow"] \
        == ws["blocks_raw"] + ws["blocks_narrow"] == NSHARDS
    # the peers really contributed: their stage time crossed the wire
    assert gs["stage_ms"].get("peer_exec", 0) > 0

    # one trace id, spans from every participating node
    spans = tracer.snapshot()
    roots = [s for s in spans if s.name == SPAN_QUERY]
    assert len(roots) == 1
    tid = roots[0].trace_id
    members = [s for s in spans if s.trace_id == tid]
    serve_nodes = {s.tags.get("node") for s in members
                   if s.name == SPAN_QUERY_SERVE}
    assert serve_nodes == {"b", "c"}, serve_nodes
    dispatches = [s for s in members if s.name == SPAN_QUERY_DISPATCH]
    assert len(dispatches) == 2                 # one POST per peer
    leaf_shards = {s.tags.get("shard") for s in members
                   if s.name == "query.exec.leaf"}
    assert leaf_shards == set(range(NSHARDS))   # every shard's leaf joined

    # the debug plane serves the assembled trace...
    url = f"http://{eps['a']}/api/v1/debug/traces?trace_id={tid}"
    with urllib.request.urlopen(url, timeout=10.0) as r:
        data = json.load(r)["data"]
    assert len(data) == 1 and data[0]["trace_id"] == tid
    assert data[0]["spans"][0]["name"] == SPAN_QUERY    # parent -> child
    assert len(data[0]["spans"]) == len(members)
    # ...and valid Zipkin v2 JSON under ?format=zipkin
    with urllib.request.urlopen(url + "&format=zipkin", timeout=10.0) as r:
        zk = json.load(r)
    assert {z["traceId"] for z in zk} == {tid}
    assert all(set(z) >= {"traceId", "id", "name", "timestamp", "duration"}
               for z in zk)


def test_kill_one_node_splits_shards_and_replans(three_node):
    """Kill node c: its two shards must split across BOTH survivors (least-
    loaded reassignment), and a query in flight across the takeover window
    replans exactly once — only c's routes changed, a/b legs keep their
    original routing."""
    engines, oracle, mgr, eps, servers, _owner = three_node
    c_shards = sorted(mgr.shards_of_node(DATASET, "c"))
    assert len(c_shards) == 2

    # node c browns out hard: server stopped, THEN the membership monitor
    # declares it dead concurrently with the next dispatch (the resolver
    # hook plays the monitor, as in the two-node takeover test)
    servers["c"].stop()
    dead_ep = eps.pop("c")
    state = {"failed": False}

    def resolver(node):
        if node == "c" and not state["failed"]:
            state["failed"] = True
            mgr.remove_node("c")
            return "127.0.0.1:1"          # nothing listens there
        return eps.get(node)

    engines["a"].endpoint_resolver = resolver
    start, end, step = START + 600_000, START + 900_000, 30_000
    want_res = oracle.query_range("sum by (dc) (m)", start, end, step)
    want = _as_comparable(want_res)
    got_res = engines["a"].query_range("sum by (dc) (m)", start, end, step)
    got = _as_comparable(got_res)
    assert state["failed"], "the dead peer was never dispatched to"
    assert got_res.exec_path == "local-replanned"
    assert got == want
    # the replan retry re-executed every leg: the first attempt's partial
    # counts (successful peers, local leaves) must not double into stats
    assert got_res.stats.to_dict()["series_matched"] \
        == want_res.stats.to_dict()["series_matched"]

    # the dead node's shards split across BOTH survivors
    new_owner = {s: mgr.node_of(DATASET, s) for s in c_shards}
    assert set(new_owner.values()) == {"a", "b"}, (
        f"expected {c_shards} split across both survivors, got {new_owner}")
    # and steady-state queries (no replan) stay correct on the new topology
    got2_res = engines["b"].query_range("sum by (dc) (m)", start, end, step)
    got2 = _as_comparable(got2_res)
    assert got2 == want
    assert got2_res.exec_path == "local"
    # unreferenced, but documents the window: the dead endpoint is gone
    assert dead_ep not in eps.values()


# -- PR 16: one-program mesh queries vs the host-loop path --------------------
#
# The dist_* collectives now fold shard partials in HOST SHARD ORDER (an
# all_gather + static left fold replaces psum/pmin/pmax) and hand the folded
# partial dicts to the same numpy presenter the scatter-gather path uses —
# so the mesh answer is bit-identical to the host loop BY CONSTRUCTION, not
# within a tolerance. This grid proves it end to end: every dist_* shape,
# on raw f32 and narrow-resident gauge stores, pjit mesh == three-node
# host loop == single-node oracle under exact `_as_comparable` equality.
#
# Scalar narrow blocks are KIND-tagged since ISSUE 17 (ops/decodereg.py:
# quant16 i16, delta16 i16, delta8 i8 — the encoder prefers the narrowest
# that round-trips, so this leg's small-integer counters land on delta8 and
# the mesh streams i8 blocks through dist_fused_aggregate_narrow). The
# histogram i8 tier is the 2D-delta form (`compressed_residency="all"`);
# histogram stores are host-merged by design (engine._mesh_executor refuses
# bucketed stores), so the hist leg asserts the CLEAN FALLBACK plus exact
# parity instead of a mesh tag.

MESH_IV = 10_000
MESH_N = 64

# per-residency query plans: route coverage × what each leaf kernel can
# answer BIT-equally on both sides of the comparison. Grid-aligned f32/narrow
# drive the fused map phase for the windowed functions (the host loop serves
# those through the identical fusedgrid kernel); their twostep/topk/sketch
# legs use instant selectors, whose leaf values are exact sample COPIES on
# either path. The f64 leg jitters the timestamps OFF the grid so both the
# host leaf and the mesh leaf evaluate windowed functions through the same
# periodic-samples kernel — covering rate/avg_over_time through twostep,
# topk and sketch with real window arithmetic.
MESH_PARITY_QUERIES = {
    "f32": ('sum(rate(m[2m]))', 'avg by (grp) (rate(m[2m]))',
            'stddev by (grp) (rate(m[2m]))', 'max by (grp) (m)',
            'topk(2, m)', 'quantile(0.5, m)'),
    "narrow": ('sum(rate(m[2m]))', 'avg by (grp) (rate(m[2m]))',
               'stddev by (grp) (rate(m[2m]))', 'max by (grp) (m)',
               'topk(2, m)', 'quantile(0.5, m)'),
    "f64": ('sum(sum_over_time(m[2m]))', 'max by (grp) (avg_over_time(m[2m]))',
            'topk(2, rate(m[2m]))', 'quantile(0.5, rate(m[2m]))'),
}


def _mesh_parity_rows():
    rng = np.random.default_rng(16)
    # integer cumsums: exactly representable in f32 AND in the narrow
    # encoders' round-trip domains checked at flush (increments 1..49 fit
    # i8 deltas, so the preference ladder lands these rows on delta8)
    return [np.cumsum(rng.integers(1, 50, MESH_N)).astype(np.float64)
            for _ in range(24)]


def _mesh_parity_fill(ms, rows, jitter=None):
    from filodb_tpu.core.record import RecordBuilder
    for i, vals in enumerate(rows):
        b = RecordBuilder(GAUGE)
        for t in range(MESH_N):
            ts = START + t * MESH_IV + (int(jitter[i][t]) if jitter is not None
                                        else 0)
            b.add({"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 4}"},
                  ts, float(vals[t]))
        ms.ingest(DATASET, i % NSHARDS, b.build())
    ms.flush_all()


@pytest.mark.parametrize("residency", ["f32", "narrow", "f64"])
def test_mesh_bit_parity_grid_vs_host_loop_and_oracle(residency):
    """ISSUE 16 satellite: every dist_* shape (fused / fused-narrow,
    twostep, topk, sketch), pjit mesh == 3-node host loop == single-node
    oracle, EXACT equality, exec path tagged mesh[pjit]-*."""
    from filodb_tpu.core.memstore import StoreConfig
    from filodb_tpu.parallel.distributed import make_mesh

    def cfg():
        return StoreConfig(max_series_per_shard=16, samples_per_series=MESH_N,
                           flush_batch_size=10**9,
                           dtype="float64" if residency == "f64"
                           else "float32",
                           narrow_resident=(residency == "narrow"))

    rows = _mesh_parity_rows()
    jitter = (np.random.default_rng(17).integers(0, MESH_IV // 2,
                                                 (24, MESH_N))
              if residency == "f64" else None)
    mesh = make_mesh()
    mesh_ms = TimeSeriesMemStore()
    for s, dev in enumerate(mesh.devices.ravel()):
        mesh_ms.setup(DATASET, GAUGE, s, cfg(), device=dev)
    _mesh_parity_fill(mesh_ms, rows, jitter)
    mesh_eng = QueryEngine(mesh_ms, DATASET, ShardMapper(NSHARDS), mesh=mesh)

    oracle_ms = TimeSeriesMemStore()
    mgr = ShardManager()
    for n in NODES:
        mgr.add_node(n)
    mgr.add_dataset(DATASET, NSHARDS)
    stores = {n: TimeSeriesMemStore() for n in NODES}
    for s in range(NSHARDS):
        oracle_ms.setup(DATASET, GAUGE, s, cfg())
        for n in NODES:
            stores[n].setup(DATASET, GAUGE, s, cfg())
    _mesh_parity_fill(oracle_ms, rows, jitter)
    for n in NODES:
        _mesh_parity_fill(stores[n], rows, jitter)
    if residency == "narrow":
        assert all(sh.store.is_narrow_resident
                   for sh in mesh_ms.shards_of(DATASET))
        # the small-integer counters must land on the NARROWEST variant —
        # the mesh leg below streams i8 blocks, not the quant16 i16 form
        assert {sh.store.narrow_operands()[0]
                for sh in mesh_ms.shards_of(DATASET)} == {"delta8"}

    eps: dict[str, str] = {}
    engines = {n: QueryEngine(stores[n], DATASET, ShardMapper(NSHARDS),
                              cluster=mgr, node=n, endpoint_resolver=eps.get)
               for n in NODES}
    servers = {n: FiloHttpServer({DATASET: engines[n]}, port=0).start()
               for n in NODES}
    for n, srv in servers.items():
        eps[n] = f"127.0.0.1:{srv.port}"
    oracle = QueryEngine(oracle_ms, DATASET, ShardMapper(NSHARDS))

    start, end, step = START + 300_000, START + 800_000, 30_000
    queries = MESH_PARITY_QUERIES[residency]
    tags = set()
    try:
        for q in queries:
            rm = mesh_eng.query_range(q, start, end, step)
            assert rm.exec_path.startswith("mesh[pjit]-"), (q, rm.exec_path)
            tags.add(rm.exec_path)
            want = _as_comparable(oracle.query_range(q, start, end, step))
            got_loop = _as_comparable(
                engines["a"].query_range(q, start, end, step))
            got_mesh = _as_comparable(rm)
            assert got_loop == want, f"host loop diverged from oracle: {q!r}"
            assert got_mesh == want, f"mesh diverged from oracle: {q!r}"
    finally:
        for srv in servers.values():
            srv.stop()
    if residency != "f64":
        fused_tag = ("mesh[pjit]-fused-narrow" if residency == "narrow"
                     else "mesh[pjit]-fused")
        assert fused_tag in tags, tags
    assert {"mesh[pjit]-twostep", "mesh[pjit]-topk",
            "mesh[pjit]-sketch"} <= tags, tags


def test_mesh_engine_i8_hist_residency_host_merges_with_parity():
    """The i8 leg of the residency matrix: 2D-delta histogram blocks
    (`compressed_residency=\"all\"`, quiet rows take the i8 tier) are the
    only i8-resident form, and engine._mesh_executor refuses bucketed
    stores — the mesh-configured engine must fall back to the host merge
    CLEANLY (no mesh tag, fallback metric ticks via the eligibility gate)
    and match a no-mesh oracle over the identical ingests bit-for-bit."""
    from filodb_tpu.core.memstore import StoreConfig
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.core.schemas import PROM_HISTOGRAM
    from filodb_tpu.parallel.distributed import make_mesh

    B = 8
    les = np.concatenate([2.0 ** np.arange(B - 1), [np.inf]])

    def build(device_mesh):
        ms = TimeSeriesMemStore()
        cfg = StoreConfig(max_series_per_shard=16, samples_per_series=128,
                          flush_batch_size=10**9, dtype="float32",
                          compressed_residency="all")
        devs = (list(device_mesh.devices.ravel()) if device_mesh is not None
                else [None] * NSHARDS)
        for s in range(NSHARDS):
            ms.setup(DATASET, PROM_HISTOGRAM, s, cfg, device=devs[s])
        rng = np.random.default_rng(7)
        for i in range(16):
            b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
            c = np.cumsum(np.cumsum(rng.poisson(0.4, (96, B)), axis=0),
                          axis=1).astype(np.float64)
            for t in range(96):
                b.add({"_metric_": "h", "host": f"x{i}"},
                      START + t * MESH_IV, c[t])
            ms.ingest(DATASET, i % NSHARDS, b.build())
        ms.flush_all()
        return ms

    mesh = make_mesh()
    ms_mesh = build(mesh)
    ms_host = build(None)
    assert any(sh.store._nhist[0].dtype == np.int8
               for sh in ms_mesh.shards_of(DATASET)
               if sh.store.is_narrow_resident)
    em = QueryEngine(ms_mesh, DATASET, ShardMapper(NSHARDS), mesh=mesh)
    eo = QueryEngine(ms_host, DATASET, ShardMapper(NSHARDS))
    start, end, step = START + 300_000, START + 800_000, 30_000
    for q in ('histogram_quantile(0.9, sum(rate(h[2m])))',
              'sum(rate(h[2m]))'):
        rm = em.query_range(q, start, end, step)
        assert not rm.exec_path.startswith("mesh"), (q, rm.exec_path)
        assert _as_comparable(rm) \
            == _as_comparable(eo.query_range(q, start, end, step)), q
