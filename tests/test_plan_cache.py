"""Compiled-plan cache: compile-count harness (ISSUE 8).

The cache instruments REAL traces (a counter inside the traced body runs
only at trace time) and records a ``query.compile`` span per new program, so
these tests assert the serving contract directly: the second identical query
compiles NOTHING — across the in-process path AND the mesh path — warmup
pre-traces a dashboard's shape before its first query, and the LRU capacity
bound actually evicts (with the metric to prove it)."""

import numpy as np

from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE, PROM_COUNTER
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.query.plancache import plan_cache, warmup
from filodb_tpu.utils.metrics import (FILODB_QUERY_COMPILE_CACHE_EVICTIONS,
                                      registry)
from filodb_tpu.utils.tracing import SPAN_QUERY_COMPILE, tracer

BASE = 1_700_000_000_000
IV = 10_000


def _counter_store(n_series=64, n_samples=90, max_series=64,
                   dataset="plancache"):
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=max_series,
                      samples_per_series=128, flush_batch_size=10**9,
                      dtype="float32")
    ms.setup(dataset, PROM_COUNTER, 0, cfg)
    rng = np.random.default_rng(7)
    for s in range(n_series):
        b = RecordBuilder(PROM_COUNTER)
        vals = np.cumsum(rng.exponential(5.0, n_samples))
        for t in range(n_samples):
            b.add({"_metric_": "rt", "job": f"J{s % 4}", "inst": f"i{s}"},
                  BASE + t * IV, float(vals[t]))
        ms.ingest(dataset, 0, b.build())
    ms.flush_all()
    return ms


def _compile_spans():
    return [s for s in tracer.snapshot() if s.name == SPAN_QUERY_COMPILE]


def test_second_identical_query_compiles_nothing_in_process():
    ms = _counter_store()
    eng = QueryEngine(ms, "plancache")
    start, end, step = BASE + 300_000, BASE + 890_000, 60_000
    q = 'sum(rate(rt[1m]))'
    r1 = eng.query_range(q, start, end, step)
    tracer.drain()
    t0, h0 = plan_cache.traces, plan_cache.stats()["hits"]
    r2 = eng.query_range(q, start, end, step)
    assert plan_cache.traces == t0, \
        "second identical query must trace/compile nothing"
    assert _compile_spans() == [], "no query.compile span on the warm path"
    assert plan_cache.stats()["hits"] > h0, "the warm path must HIT the cache"
    np.testing.assert_array_equal(np.asarray(r1.matrix.values),
                                  np.asarray(r2.matrix.values))


def test_second_identical_query_compiles_nothing_on_mesh():
    from filodb_tpu.parallel.distributed import make_mesh
    mesh = make_mesh()
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=16, samples_per_series=64,
                      flush_batch_size=10**9, dtype="float32")
    for i, dev in enumerate(mesh.devices.ravel()):
        ms.setup("meshpc", GAUGE, i, cfg, device=dev)
    rng = np.random.default_rng(5)
    for i in range(24):
        b = RecordBuilder(GAUGE)
        vals = np.cumsum(rng.exponential(5.0, 60))
        for t in range(60):
            b.add({"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 4}"},
                  BASE + t * IV, float(vals[t]))
        ms.ingest("meshpc", i % 8, b.build())
    ms.flush_all()
    eng = QueryEngine(ms, "meshpc", mesh=mesh)
    start, end, step = BASE + 300_000, BASE + 500_000, 20_000
    for q in ("sum(rate(m[5m]))", "max(rate(m[5m]))"):
        r1 = eng.query_range(q, start, end, step)
        assert r1.exec_path.startswith("mesh[pjit]-"), r1.exec_path
        tracer.drain()
        t0 = plan_cache.traces
        r2 = eng.query_range(q, start, end, step)
        assert plan_cache.traces == t0, \
            f"second identical mesh query must compile nothing ({q})"
        assert _compile_spans() == []
        assert r2.exec_path == r1.exec_path
        np.testing.assert_array_equal(np.asarray(r1.matrix.values),
                                      np.asarray(r2.matrix.values))


def _mesh_store(dataset="meshiso"):
    from filodb_tpu.parallel.distributed import make_mesh
    mesh = make_mesh()
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=16, samples_per_series=64,
                      flush_batch_size=10**9, dtype="float32")
    for i, dev in enumerate(mesh.devices.ravel()):
        ms.setup(dataset, GAUGE, i, cfg, device=dev)
    rng = np.random.default_rng(5)
    for i in range(24):
        b = RecordBuilder(GAUGE)
        vals = np.cumsum(rng.exponential(5.0, 60))
        for t in range(60):
            b.add({"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 4}"},
                  BASE + t * IV, float(vals[t]))
        ms.ingest(dataset, i % 8, b.build())
    ms.flush_all()
    return mesh, ms


def test_mesh_program_never_aliases_a_per_shard_entry():
    """Key audit: a mesh dist_* program is keyed on (padded shape, mesh
    axes) under a kernel name of its own — it must neither reuse nor
    overwrite a per-shard in-process entry of the same query shape, a
    query shape owns exactly ONE mesh entry, the second identical query
    traces 0, and the answer equals the host scatter-gather's bit for
    bit."""
    mesh, ms = _mesh_store()
    eng = QueryEngine(ms, "meshiso", mesh=mesh)
    host = QueryEngine(ms, "meshiso")
    plan_cache.clear()      # a cold process: both paths compile their own
    start, end, step = BASE + 300_000, BASE + 500_000, 20_000
    q = 'sum(rate(m[5m]))'
    r_host = host.query_range(q, start, end, step)
    assert not r_host.exec_path.startswith("mesh"), r_host.exec_path
    per_shard = set(plan_cache.keys())
    t_host = plan_cache.traces
    r_mesh = eng.query_range(q, start, end, step)
    assert r_mesh.exec_path.startswith("mesh[pjit]-"), r_mesh.exec_path
    added = set(plan_cache.keys()) - per_shard
    assert plan_cache.traces > t_host, \
        "the mesh program must compile: no per-shard entry serves it"
    assert [k[0] for k in added if k[0].startswith("dist-")] \
        == ["dist-fused"], added
    assert per_shard <= set(plan_cache.keys()), \
        "per-shard entries must survive the mesh compile untouched"
    # identical mesh query: warm, traces nothing, adds nothing
    t0, size = plan_cache.traces, len(plan_cache)
    r_mesh2 = eng.query_range(q, start, end, step)
    assert plan_cache.traces == t0 and len(plan_cache) == size
    # and the host path re-hits its own entries at zero traces
    r_host2 = host.query_range(q, start, end, step)
    assert plan_cache.traces == t0
    for r in (r_mesh, r_mesh2, r_host2):
        assert (np.asarray(r.matrix.values).tolist()
                == np.asarray(r_host.matrix.values).tolist())


def test_warmup_covers_mesh_variants():
    """query.warmup_shapes with ``mesh: true`` pre-traces the mesh dist_*
    programs: the first real mesh query of the warmed shape compiles
    nothing."""
    mesh, ms = _mesh_store("meshwarm")
    eng = QueryEngine(ms, "meshwarm", mesh=mesh)
    start, end, step = BASE + 300_000, BASE + 500_000, 20_000
    steps = (end - start) // step + 1
    spec = {"fn": "rate", "op": "sum", "series": 16, "samples": 64,
            "steps": steps, "step_ms": step, "window_ms": 300_000,
            "interval_ms": IV, "groups": 1, "mesh": True}
    warmup([spec])
    tracer.drain()
    t0 = plan_cache.traces
    r = eng.query_range('sum(rate(m[5m]))', start, end, step)
    assert r.exec_path.startswith("mesh[pjit]-"), r.exec_path
    assert plan_cache.traces == t0, \
        "warmed mesh shape must not compile at serve time"
    assert _compile_spans() == []


def test_warmup_pretraces_the_dashboard_shape():
    """query.warmup_shapes contract: after warming the (fn, op, series,
    samples, steps, window, interval) bucket, the first real dashboard query
    of that shape traces NOTHING new."""
    ms = _counter_store(dataset="warmshape")
    eng = QueryEngine(ms, "warmshape")
    plan_cache.clear()          # cold process: every program must rebuild
    info = warmup([{"fn": "rate", "op": "sum", "series": 64, "samples": 128,
                    "steps": 10, "step_ms": 60_000, "window_ms": 60_000,
                    "interval_ms": 10_000}])
    assert info["programs"] > 0, "a cold warmup must trace programs"
    tracer.drain()
    t0 = plan_cache.traces
    r = eng.query_range('sum(rate(rt[1m]))', BASE + 300_000, BASE + 840_000,
                        60_000)
    assert plan_cache.traces == t0, \
        "warmed dashboard shape must not compile on first load"
    assert _compile_spans() == []
    assert r.matrix.num_series == 1


def test_warmup_pretraces_the_fused_variant_in_every_mode():
    """ISSUE 9 satellite: query.warmup_shapes must cover the fused-resident
    kernel VARIANT the active query.fused_kernels mode serves — a warmed
    server previously still paid first-query compile on the fused path when
    the mode's program differed from the warmed one."""
    from filodb_tpu.ops import fusedresident
    ms = _counter_store(dataset="warmfused")
    eng = QueryEngine(ms, "warmfused")
    spec = {"fn": "rate", "op": "sum", "series": 64, "samples": 128,
            "steps": 10, "step_ms": 60_000, "window_ms": 60_000,
            "interval_ms": 10_000}
    old = fusedresident.mode()
    try:
        for mode in ("xla", "pallas"):
            fusedresident.set_mode(mode)
            plan_cache.clear()
            info = warmup([spec])
            assert info["programs"] > 0
            tracer.drain()
            t0 = plan_cache.traces
            r = eng.query_range('sum(rate(rt[1m]))', BASE + 300_000,
                                BASE + 840_000, 60_000)
            assert plan_cache.traces == t0, \
                f"warmed {mode} variant must not compile on first load"
            assert _compile_spans() == []
            assert r.stats.fused_kernels >= 1, \
                f"the {mode} fused variant must actually serve"
    finally:
        fusedresident.set_mode(old)


def test_warmup_pretraces_the_fused_hist_variant():
    """A warmup spec with ``buckets`` covers the hist-resident quantile
    variant: the map-phase AND finish programs trace at warmup, so the
    matching serve-time call compiles nothing."""
    import jax.numpy as jnp

    from filodb_tpu.ops import fusedresident
    from filodb_tpu.query.exec import _pad_steps
    plan_cache.clear()
    spec = {"fn": "rate", "op": "sum", "series": 64, "samples": 128,
            "steps": 10, "step_ms": 60_000, "window_ms": 60_000,
            "interval_ms": 10_000, "buckets": 8}
    info = warmup([spec])
    assert info["programs"] > 0
    t0 = plan_cache.traces
    # the serve-time shapes the engine would use for this spec
    out_ts = np.int64(60_000) + np.arange(10, dtype=np.int64) * 60_000
    out_eval, _T = _pad_steps(out_ts)
    dd = jnp.zeros((64, 128, 8), jnp.int16)
    fd = jnp.zeros((64, 8), jnp.float32)
    les = np.arange(1, 9, dtype=np.float64); les[-1] = np.inf
    fusedresident.fused_hist_quantile_resident(
        0.9, les, dd, fd, jnp.zeros(64, jnp.int32), np.zeros(64, np.int32),
        8, out_eval, 60_000, "rate", 0, 10_000)
    assert plan_cache.traces == t0, \
        "warmed hist-resident shape must not compile at serve time"


def test_eviction_respects_capacity_bound_and_counts():
    ev = registry.counter(FILODB_QUERY_COMPILE_CACHE_EVICTIONS)
    old_cap = plan_cache.capacity
    ev0 = ev.value
    try:
        plan_cache.resize(4)
        for i in range(9):
            plan_cache.program("evict-probe", (i,), lambda: (lambda x: x))
        assert len(plan_cache) <= 4
        assert ev.value >= ev0 + 5, "LRU overflow must count as evictions"
        # the survivors are the most recently inserted keys: re-requesting
        # the newest is a hit, the oldest a miss (rebuild)
        h0 = plan_cache.stats()["hits"]
        plan_cache.program("evict-probe", (8,), lambda: (lambda x: x))
        assert plan_cache.stats()["hits"] == h0 + 1
    finally:
        plan_cache.resize(old_cap)


def test_cache_stats_surface():
    s = plan_cache.stats()
    assert {"size", "capacity", "hits", "misses", "evictions",
            "traces"} <= set(s)
    assert s["capacity"] >= 1
