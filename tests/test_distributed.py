"""Distributed (8-device CPU mesh) query tests: shard_map + psum path vs the
in-process reference answer (ref analog: multi-jvm specs run multi-node logic in
one process)."""

import jax
import numpy as np
import pytest

from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE
from filodb_tpu.parallel.distributed import (DistributedStore, MeshQueryExecutor,
                                             make_mesh)

from .prom_reference import eval_range_fn

START = 1_000_000
INTERVAL = 10_000
N = 60


def build_store(dtype="float64", counter=False, seed=5):
    mesh = make_mesh()
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=16, samples_per_series=64,
                      flush_batch_size=10**9, dtype=dtype)
    shards = []
    for i, dev in enumerate(mesh.devices.ravel()):
        shards.append(ms.setup("prometheus", GAUGE, i, cfg, device=dev))
    rng = np.random.default_rng(seed)
    series = {}
    for i in range(24):  # 3 series per shard
        shard = i % 8
        b = RecordBuilder(GAUGE)
        if counter:
            vals = np.cumsum(rng.exponential(5.0, N))
        else:
            vals = 100.0 * (i + 1) + 5 * np.cos(np.arange(N) / 3 + i)
        labels = {"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 4}"}
        for t in range(N):
            b.add(labels, START + t * INTERVAL, float(vals[t]))
        ms.ingest("prometheus", shard, b.build())
        series[i] = vals
    ms.flush_all()
    return mesh, ms, shards, series


def test_mesh_sum_matches_reference():
    mesh, ms, shards, series = build_store()
    dstore = DistributedStore(mesh, shards)
    ex = MeshQueryExecutor(dstore)
    out_ts = np.arange(START + 300_000, START + 500_001, 20_000, dtype=np.int64)

    # group ids: all series -> group 0
    gids = [np.zeros(16, np.int32) for _ in range(8)]
    got = ex.aggregate("sum_over_time", "sum", out_ts, 60_000, gids, 1)
    ts_full = START + np.arange(N) * INTERVAL
    want = sum(eval_range_fn("sum_over_time", ts_full, v, out_ts, 60_000)
               for v in series.values())
    np.testing.assert_allclose(got[0], want, rtol=1e-12)


def test_mesh_grouped_avg_and_max():
    mesh, ms, shards, series = build_store()
    dstore = DistributedStore(mesh, shards)
    ex = MeshQueryExecutor(dstore)
    out_ts = np.arange(START + 300_000, START + 500_001, 20_000, dtype=np.int64)
    ts_full = START + np.arange(N) * INTERVAL

    # group by grp label (4 groups); map series -> its shard-local row
    gids = [np.zeros(16, np.int32) for _ in range(8)]
    for i in range(24):
        shard_obj = shards[i % 8]
        # row of this series within its shard store
        from filodb_tpu.core.schemas import part_key_of
        pid = shard_obj._part_key_to_id[part_key_of(
            {"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 4}"})]
        gids[i % 8][pid] = i % 4

    got = ex.aggregate("avg_over_time", "avg", out_ts, 60_000, gids, 4)
    for g in range(4):
        members = [series[i] for i in range(24) if i % 4 == g]
        per = [eval_range_fn("avg_over_time", ts_full, v, out_ts, 60_000) for v in members]
        np.testing.assert_allclose(got[g], np.mean(per, axis=0), rtol=1e-12)

    got = ex.aggregate("avg_over_time", "max", out_ts, 60_000, gids, 4)
    for g in range(4):
        members = [series[i] for i in range(24) if i % 4 == g]
        per = [eval_range_fn("avg_over_time", ts_full, v, out_ts, 60_000) for v in members]
        np.testing.assert_allclose(got[g], np.max(per, axis=0), rtol=1e-12)


def test_mesh_fused_rate_path_matches_twostep():
    """f32 grid-aligned shards route sum(rate)/avg(rate) through the fused
    single-pass map phase inside shard_map (asserted via last_path), and the
    psum-reduced result matches the general two-step mesh path."""
    mesh = make_mesh()
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=16, samples_per_series=64,
                      flush_batch_size=10**9, dtype="float32")
    shards = [ms.setup("prometheus", GAUGE, i, cfg, device=dev)
              for i, dev in enumerate(mesh.devices.ravel())]
    rng = np.random.default_rng(5)
    for i in range(24):
        b = RecordBuilder(GAUGE)
        vals = np.cumsum(rng.exponential(5.0, N))
        labels = {"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 4}"}
        for t in range(N):
            b.add(labels, START + t * INTERVAL, float(vals[t]))
        ms.ingest("prometheus", i % 8, b.build())
    ms.flush_all()
    dstore = DistributedStore(mesh, shards)
    ex = MeshQueryExecutor(dstore)
    out_ts = np.arange(START + 300_000, START + 500_001, 20_000, dtype=np.int64)
    gids = [np.zeros(16, np.int32) for _ in range(8)]

    fused = ex.aggregate("rate", "sum", out_ts, 60_000, gids, 1)
    assert ex.last_path == "fused"
    # force the general path by (temporarily) demoting one shard's grid
    shards[0].store.grid_ok = False
    general = ex.aggregate("rate", "sum", out_ts, 60_000, gids, 1)
    assert ex.last_path == "twostep"
    shards[0].store.grid_ok = True
    np.testing.assert_allclose(fused[0], general[0], rtol=2e-4, atol=1e-4)

    # grouped avg through the fused partial layout
    gids4 = [np.arange(16, dtype=np.int32) % 4 for _ in range(8)]
    fused4 = ex.aggregate("rate", "avg", out_ts, 60_000, gids4, 4)
    assert ex.last_path == "fused"
    shards[0].store.grid_ok = False
    general4 = ex.aggregate("rate", "avg", out_ts, 60_000, gids4, 4)
    shards[0].store.grid_ok = True
    np.testing.assert_allclose(fused4, general4, rtol=2e-4, atol=1e-4,
                               equal_nan=True)


def build_f32_store():
    mesh, ms, shards, _ = build_store(dtype="float32", counter=True, seed=7)
    return mesh, ms, shards


def test_engine_routes_promql_through_mesh():
    """A PromQL string executes end-to-end via shard_map/psum: the engine's
    planner-level dispatch (ref: queryengine2/QueryEngine.scala:59-67 routes
    every query through per-shard dispatchers), asserted via the per-query result exec_path —
    not by calling MeshQueryExecutor.aggregate directly."""
    from filodb_tpu.query.engine import QueryEngine

    mesh, ms, shards = build_f32_store()
    eng = QueryEngine(ms, "prometheus", mesh=mesh)
    local = QueryEngine(ms, "prometheus")     # host scatter-gather oracle
    start, end, step = START + 300_000, START + 500_000, 20_000

    r = eng.query_range("sum(rate(m[5m]))", start, end, step)
    assert r.exec_path == "mesh[pjit]-fused", r.exec_path
    want = local.query_range("sum(rate(m[5m]))", start, end, step)
    (_k, _t, got), = list(r.matrix.iter_series())
    (_k, _t, exp), = list(want.matrix.iter_series())
    np.testing.assert_allclose(got, exp, rtol=2e-4, atol=1e-4)

    # grouped aggregate: keys + values must match the local path per group
    r = eng.query_range("sum by (grp) (rate(m[5m]))", start, end, step)
    assert r.exec_path == "mesh[pjit]-fused"
    want = local.query_range("sum by (grp) (rate(m[5m]))", start, end, step)
    got = {k: v for k, _t, v in r.matrix.iter_series()}
    exp = {k: v for k, _t, v in want.matrix.iter_series()}
    assert set(got) == set(exp) and len(got) == 4
    for k in exp:
        np.testing.assert_allclose(got[k], exp[k], rtol=2e-4, atol=1e-4)

    # filtered selection: non-matching rows must not leak into the sum
    q = 'sum(rate(m{grp="g1"}[5m]))'
    r = eng.query_range(q, start, end, step)
    assert r.exec_path.startswith("mesh[pjit]-")
    want = local.query_range(q, start, end, step)
    (_k, _t, got), = list(r.matrix.iter_series())
    (_k, _t, exp), = list(want.matrix.iter_series())
    np.testing.assert_allclose(got, exp, rtol=2e-4, atol=1e-4)

    # min/max ride the twostep mesh path (pmin/pmax collectives)
    r = eng.query_range("max(rate(m[5m]))", start, end, step)
    assert r.exec_path == "mesh[pjit]-twostep"
    want = local.query_range("max(rate(m[5m]))", start, end, step)
    (_k, _t, got), = list(r.matrix.iter_series())
    (_k, _t, exp), = list(want.matrix.iter_series())
    np.testing.assert_allclose(got, exp, rtol=2e-4, atol=1e-4)

    # instant query through the same dispatch
    ri = eng.query_instant("sum(rate(m[5m]))", end)
    assert ri.exec_path == "mesh[pjit]-fused"
    wi = local.query_instant("sum(rate(m[5m]))", end)
    (_k, _t, got), = list(ri.matrix.iter_series())
    (_k, _t, exp), = list(wi.matrix.iter_series())
    np.testing.assert_allclose(got, exp, rtol=2e-4, atol=1e-4)


def test_engine_mesh_fallbacks():
    """Plans the collective layout can't express fall back to the local
    scatter-gather path — correctness never depends on the route."""
    from filodb_tpu.query.engine import QueryEngine

    mesh, ms, shards = build_f32_store()
    eng = QueryEngine(ms, "prometheus", mesh=mesh)
    start, end, step = START + 300_000, START + 500_000, 20_000

    # count_values partials are value-STRING keyed — host merge, local route
    r = eng.query_range('count_values("v", count(m) by (grp))', start, end, step)
    assert r.exec_path == "local"
    assert r.matrix.num_series > 0

    # bare selector (no aggregate): per-series results stay local
    r = eng.query_range("rate(m[5m])", start, end, step)
    assert r.exec_path == "local"
    assert r.matrix.num_series == 24

    # no matching series: mesh dispatch answers empty without kernels
    r = eng.query_range("sum(rate(nosuch[5m]))", start, end, step)
    assert r.exec_path == "mesh-empty"
    assert r.matrix.num_series == 0


def test_store_blocks_stay_on_their_devices():
    mesh, ms, shards, _ = build_store()
    devs = list(mesh.devices.ravel())
    for i, s in enumerate(shards):
        assert list(s.store.ts.devices())[0] == devs[i]
    dstore = DistributedStore(mesh, shards)
    ((ts_g, val_g, n_g),) = dstore.arrays()
    assert ts_g.shape == (8 * 16, 64)
    assert len(ts_g.sharding.device_set) == 8


def test_engine_mesh_topk_and_quantile():
    """topk/bottomk all_gather fixed-size candidate blocks over the mesh and
    quantile psums sketch counts — parity with the in-process order-stat
    path, keys included (ref: AggrOverRangeVectors.scala:244-900)."""
    from filodb_tpu.query.engine import QueryEngine

    mesh, ms, shards = build_f32_store()
    eng = QueryEngine(ms, "prometheus", mesh=mesh)
    local = QueryEngine(ms, "prometheus")
    start, end, step = START + 300_000, START + 500_000, 20_000

    for q, route in (("topk(3, rate(m[5m]))", "mesh[pjit]-topk"),
                     ("bottomk(2, rate(m[5m]))", "mesh[pjit]-topk"),
                     ("topk(2, rate(m[5m])) by (grp)", "mesh[pjit]-topk"),
                     ('topk(2, rate(m{grp="g1"}[5m]))', "mesh[pjit]-topk")):
        r = eng.query_range(q, start, end, step)
        assert r.exec_path == route, (q, r.exec_path)
        want = local.query_range(q, start, end, step)
        assert want.exec_path == "local"
        got = {k: (t.tolist(), v) for k, t, v in r.matrix.iter_series()}
        exp = {k: (t.tolist(), v) for k, t, v in want.matrix.iter_series()}
        # same winners at the same steps; values agree within the grid-vs-
        # general rate-kernel tolerance (the two routes legitimately use
        # different lowering of the same math)
        assert set(got) == set(exp), f"{q}: different winners"
        for k in exp:
            assert got[k][0] == exp[k][0], f"{q}: {k} selected at different steps"
            np.testing.assert_allclose(got[k][1], exp[k][1], rtol=2e-4,
                                       atol=1e-4)

    for q in ("quantile(0.5, rate(m[5m]))",
              "quantile(0.9, rate(m[5m])) by (grp)"):
        r = eng.query_range(q, start, end, step)
        assert r.exec_path == "mesh[pjit]-sketch", (q, r.exec_path)
        want = local.query_range(q, start, end, step)
        got = {k: v for k, _t, v in r.matrix.iter_series()}
        exp = {k: v for k, _t, v in want.matrix.iter_series()}
        assert set(got) == set(exp)
        for k in exp:
            np.testing.assert_allclose(got[k], exp[k], rtol=1e-9,
                                       equal_nan=True)


def test_mesh_two_shards_per_device():
    """16 shards on 8 devices: per-device slot blocks reduce locally before
    the collective (shards-per-device >= 1; the reference never requires one
    data node per shard either)."""
    from filodb_tpu.query.engine import QueryEngine

    mesh = make_mesh()
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=16, samples_per_series=64,
                      flush_batch_size=10**9, dtype="float32")
    devs = list(mesh.devices.ravel())
    shards = [ms.setup("prometheus", GAUGE, i, cfg, device=devs[i % 8])
              for i in range(16)]
    rng = np.random.default_rng(11)
    for i in range(48):   # 3 series per shard
        b = RecordBuilder(GAUGE)
        vals = np.cumsum(rng.exponential(5.0, N))
        for t in range(N):
            b.add({"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 4}"},
                  START + t * INTERVAL, float(vals[t]))
        ms.ingest("prometheus", i % 16, b.build())
    ms.flush_all()
    eng = QueryEngine(ms, "prometheus", mesh=mesh)
    local = QueryEngine(ms, "prometheus")
    start, end, step = START + 300_000, START + 500_000, 20_000
    for q in ("sum(rate(m[5m]))", "sum by (grp) (rate(m[5m]))",
              "max(rate(m[5m]))", "topk(3, rate(m[5m]))",
              "quantile(0.5, rate(m[5m]))"):
        r = eng.query_range(q, start, end, step)
        assert r.exec_path.startswith("mesh[pjit]-"), (q, r.exec_path)
        want = local.query_range(q, start, end, step)
        got = {k: v for k, _t, v in r.matrix.iter_series()}
        exp = {k: v for k, _t, v in want.matrix.iter_series()}
        assert set(got) == set(exp), q
        for k in exp:
            np.testing.assert_allclose(got[k], exp[k], rtol=2e-4, atol=1e-4,
                                       equal_nan=True)


# -- PR 16: composed two-step reduce is bit-stable across step buckets --------
#
# PR 13's fold-order caveat:
# the composed path's [G,R]x[R,T] segment reduce could differ in the last
# ulp across padded-T step buckets — XLA was free to reassociate the matmul
# fold per output shape. Closed by (a) the row-order stable segment reduce
# (ops/aggregators.partial_aggregate(stable=True), shared by the host
# composed path and the mesh per-shard map) and (b) the host-order f64
# cross-shard fold (no in-program psum). These sweeps pin it down: the same
# data queried at step counts landing in DIFFERENT _pad_steps buckets must
# return bit-IDENTICAL values on the shared step prefix.

# 7 / 40 / 100 steps pad to 32 / 64 / 128 — three distinct compile buckets
_SWEEP_STEPS = (7, 40, 100)


def test_mesh_twostep_fold_bit_stable_across_step_buckets():
    mesh, ms, shards, _series = build_store()          # f64 twostep route
    dstore = DistributedStore(mesh, shards)
    ex = MeshQueryExecutor(dstore)
    gids = [np.arange(16, dtype=np.int32) % 4 for _ in range(8)]
    got = {}
    for steps in _SWEEP_STEPS:
        out_ts = START + 300_000 + np.arange(steps, dtype=np.int64) * 5_000
        got[steps] = np.asarray(ex.aggregate("avg_over_time", "sum", out_ts,
                                             60_000, gids, 4))
        assert ex.last_path == "twostep"
        assert got[steps].shape[1] == steps
    for steps in _SWEEP_STEPS[:-1]:
        np.testing.assert_array_equal(got[steps], got[100][:, :steps])


def test_host_composed_reduce_bit_stable_across_step_buckets():
    """The in-process serving twin of the sweep above: the engine's composed
    (non-fused) segment reduce through exec._segment_partial."""
    from filodb_tpu.query.engine import QueryEngine

    _mesh, ms, _shards, _series = build_store()        # f64: composed path
    eng = QueryEngine(ms, "prometheus")
    step = 4_000                  # 100 steps stay inside the ingested range
    start = START + 150_000
    got = {}
    for steps in _SWEEP_STEPS:
        r = eng.query_range('sum by (grp) (avg_over_time(m[1m]))',
                            start, start + (steps - 1) * step, step)
        assert not r.exec_path.startswith("mesh"), r.exec_path
        got[steps] = {k: np.asarray(v) for k, _t, v in r.matrix.iter_series()}
        assert all(len(v) == steps for v in got[steps].values())
    assert set(got[7]) == set(got[40]) == set(got[100])
    for steps in _SWEEP_STEPS[:-1]:
        for k, v in got[steps].items():
            np.testing.assert_array_equal(v, got[100][k][:steps])


# -- PR 46: one dispatch under the shard locks --------------------------------
#
# A slot's global is [NDEV * S, ...] over the shards' own resident arrays (no
# reshape, no program); the group-id rows come from the engine's
# MeshLeafMemo, resident on their own devices; the window plan is built
# before the locks for the grid the last dispatch saw. Under the locks: four
# selects, handles, ONE pjit call.

KEEP_OVER = 2          # selections wider than this are kept (shards hold 6)


def _served_mesh(monkeypatch, narrow=False, keep=True):
    """Four shards on four devices, six integer counters each, behind a
    mesh engine and the host scatter-gather oracle over the SAME stores."""
    from filodb_tpu.query import exec as qexec
    from filodb_tpu.query.engine import QueryEngine
    if keep:
        monkeypatch.setattr(qexec, "GATHER_THRESHOLD", KEEP_OVER)
    mesh = make_mesh(jax.devices()[:4])
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=16, samples_per_series=64,
                      flush_batch_size=10**9, dtype="float32",
                      narrow_resident=narrow)
    shards = [ms.setup("prometheus", GAUGE, i, cfg, device=dev)
              for i, dev in enumerate(mesh.devices.ravel())]
    rng = np.random.default_rng(7)
    for i in range(24):
        b = RecordBuilder(GAUGE)
        vals = np.cumsum(rng.integers(1, 50, N)).astype(np.float64)
        for t in range(N):
            b.add({"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 4}"},
                  START + t * INTERVAL, float(vals[t]))
        ms.ingest("prometheus", i % 4, b.build())
    ms.flush_all()
    eng = QueryEngine(ms, "prometheus", mesh=mesh)
    host = QueryEngine(ms, "prometheus")
    for e in (eng, host):
        e.result_cache = e.fragment_cache = None
    return eng, host, shards


def _series(result):
    return {k: (t.tolist(), np.asarray(v))
            for k, t, v in result.matrix.iter_series()}


def _same_bits(got, want, what=""):
    got, want = _series(got), _series(want)
    assert set(got) == set(want), what
    for k in want:
        assert got[k][0] == want[k][0], (what, k)
        np.testing.assert_array_equal(got[k][1], want[k][1], err_msg=what)


def _ask(eng, q, rng):
    """(result, the spans of this one query) — the tracer's ring is drained
    on both sides, so an oracle's leaves never mix in."""
    from filodb_tpu.utils.tracing import tracer
    tracer.drain()
    res = eng.query_range(q, *rng)
    return res, tracer.drain()


def _leaf(spans):
    leaf, = [s for s in spans if s.name == "query.exec.leaf"]
    return leaf


def _prepared_counts():
    from filodb_tpu.utils.metrics import FILODB_QUERY_MESH_PREPARED, registry
    return {(part, how): registry.counter(
        FILODB_QUERY_MESH_PREPARED, {"part": part, "outcome": how}).value
        for part, how in (("plan", "ready"), ("plan", "built"),
                          ("gids", "memo"), ("gids", "built"),
                          ("gids", "bypass"))}


def _moved(before):
    return {k: v - before[k] for k, v in _prepared_counts().items()
            if v != before[k]}


# the five program forms, each by a text whose host path makes the same
# per-shard partials (the rate family's host leaf takes the grid kernels
# where the general mesh programs take the general ones: close, not equal)
FORMS = {
    "twostep": ("max by (grp) (sum_over_time(m[2m]))", "twostep", False),
    "sketch": ("quantile(0.9, rate(m[2m])) by (grp)", "sketch", False),
    "topk": ("topk(2, sum_over_time(m[2m])) by (grp)", "topk", False),
    "fused": ("sum by (grp) (rate(m[2m]))", "fused", False),
    "fused-narrow": ("sum by (grp) (rate(m[2m]))", "fused-narrow", True),
}
RANGE = (START + 300_000, START + 500_000, 20_000)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_every_program_form_answers_the_host_paths_bits(form, monkeypatch):
    """The global layout is the shards' own blocks side by side: every
    ``per_device`` body reads its block whole, and the answer is the host
    scatter-gather's bit for bit — grouped, so a row read from a
    neighbour's block would land in the wrong group."""
    q, route, narrow = FORMS[form]
    eng, host, _shards = _served_mesh(monkeypatch, narrow)
    got = eng.query_range(q, *RANGE)
    assert got.exec_path == f"mesh[pjit]-{route}", got.exec_path
    want = host.query_range(q, *RANGE)
    assert not want.exec_path.startswith("mesh"), want.exec_path
    _same_bits(got, want, q)
    # ... and again from the memo's rows, at another step grid
    shifted = (RANGE[0] + 7_000, RANGE[1] + 7_000, RANGE[2])
    _same_bits(eng.query_range(q, *shifted), host.query_range(q, *shifted), q)


@pytest.mark.parametrize("form", sorted(FORMS))
def test_the_locks_are_held_for_one_dispatch_alone(form, monkeypatch):
    """The second query of a selector and grouping, on a step grid nobody
    asked before: while a shard lock is owned nothing goes through
    ``jax.device_put``, ``jnp.asarray`` or an eager ``reshape`` — the rows
    come from the memo, the plan was placed before the locks, the globals
    are assembled from handles, and the program's host values ride its one
    call. The first query, which builds the rows, is what the watch sees."""
    import jax.numpy as jnp
    q, route, narrow = FORMS[form]
    eng, host, shards = _served_mesh(monkeypatch, narrow)
    trips = []

    def watch(owner, name):
        real = getattr(owner, name)

        def spy(*a, **kw):
            if any(sh.lock._is_owned() for sh in shards):
                trips.append(name)
            return real(*a, **kw)
        monkeypatch.setattr(owner, name, spy)

    watch(jax, "device_put")
    watch(jnp, "asarray")
    watch(type(shards[0].store.n), "reshape")
    eng.query_range(q, *RANGE)
    assert "device_put" in trips, "the watch sees the rows' upload"
    del trips[:]
    before = _prepared_counts()
    shifted = (RANGE[0] + 7_000, RANGE[1] + 7_000, RANGE[2])
    got, spans = _ask(eng, q, shifted)
    assert got.exec_path == f"mesh[pjit]-{route}"
    assert trips == []
    assert [s.tags["phase"] for s in spans
            if s.name == "query.exec.kernel"] == ["dispatch", "fetch"]
    leaf = _leaf(spans)
    assert leaf.tags["gids"] == "memo"
    if route.startswith("fused"):
        assert leaf.tags["plan"] == "ready"
        assert _moved(before) == {("plan", "ready"): 1, ("gids", "memo"): 1}
    else:
        assert "plan" not in leaf.tags
        assert _moved(before) == {("gids", "memo"): 1}
    monkeypatch.undo()
    _same_bits(got, host.query_range(q, *shifted), q)


def _new_series(shards):
    b = RecordBuilder(GAUGE)
    for t in range(N):
        b.add({"_metric_": "m", "host": "late", "grp": "g9"},
              START + t * INTERVAL, float(3 * t))
    shards[1].ingest(b.build())


def _off_grid_stamp(shards):
    b = RecordBuilder(GAUGE)
    b.add({"_metric_": "m", "host": "h2", "grp": "g2"},
          START + N * INTERVAL + 3_333, 1e6)
    shards[2].ingest(b.build())


def _stale_grid(eng):
    S, C, base, iv, kind = eng._mesh_memo.seen
    eng._mesh_memo.seen = (S, C, base - iv, iv, kind)


@pytest.mark.parametrize("change,route,tags,moved", [
    # the index moved: new selections, so the rows are built (and kept)
    ("series", "fused", {"plan": "ready", "gids": "built"},
     {("plan", "ready"): 1, ("gids", "built"): 1}),
    # a shard left the grid: the general program, no plan at all
    ("stamp", "twostep", {"gids": "memo"}, {("gids", "memo"): 1}),
    # the plan was made for a grid the stores are not on
    ("grid", "fused", {"plan": "built", "gids": "memo"},
     {("plan", "built"): 1, ("gids", "memo"): 1}),
])
def test_a_change_between_preparation_and_the_locks(change, route, tags,
                                                    moved, monkeypatch):
    """What was prepared is checked under the locks against what the leaf
    finds there: a series or an off-grid stamp that lands after the plan was
    prepared, or a plan prepared for another grid, gives the host path's
    answer, and the leaf's tags and ``/metrics`` say what was built."""
    q = "sum by (grp) (rate(m[2m]))"
    eng, host, shards = _served_mesh(monkeypatch)
    _same_bits(eng.query_range(q, *RANGE), host.query_range(q, *RANGE))
    memo = eng._mesh_memo
    real = memo.prepare_plan

    def prepare_then_change(*a):
        if change == "grid":
            _stale_grid(eng)
        key = real(*a)
        assert key is not None
        if change == "series":
            _new_series(shards)
        elif change == "stamp":
            _off_grid_stamp(shards)
        return key

    monkeypatch.setattr(memo, "prepare_plan", prepare_then_change)
    before = _prepared_counts()
    later = (RANGE[0] + 120_000, RANGE[1] + 120_000, RANGE[2])
    got, spans = _ask(eng, q, later)
    assert got.exec_path == f"mesh[pjit]-{route}", got.exec_path
    leaf = _leaf(spans)
    assert {k: leaf.tags[k] for k in ("plan", "gids") if k in leaf.tags} \
        == tags
    assert _moved(before) == moved
    want = host.query_range(q, *later)
    if route == "fused":
        _same_bits(got, want)
        assert len(_series(got)) == (5 if change == "series" else 4)
    else:
        # the host's leaf over an off-grid shard and the general mesh
        # program are different lowerings of one sum: the tier-1 bound
        got, want = _series(got), _series(want)
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_allclose(got[k][1], want[k][1], rtol=2e-4,
                                       atol=1e-4)


def test_the_row_memo_is_bounded_and_keeps_no_bypass():
    from types import SimpleNamespace

    from filodb_tpu.parallel.distributed import MeshLeafMemo
    memo = MeshLeafMemo(make_mesh(jax.devices()[:2]))
    kept = [SimpleNamespace(stamp=(1, 0, 16)) for _ in range(2)]
    for i in range(MeshLeafMemo.GIDS + 3):
        assert memo.keep_gids(kept, (f"l{i}",), (), (f"k{i}",), (i, i))
        assert len(memo) == min(i + 1, MeshLeafMemo.GIDS)
    assert memo.gids(kept, ("l0",), ()) is None          # the oldest went
    newest = f"l{MeshLeafMemo.GIDS + 2}"
    assert memo.gids(kept, (newest,), ()) == ((f"k{newest[1:]}",),
                                              (int(newest[1:]),) * 2)
    # a hit is the most recent: it outlives GIDS - 1 newcomers
    assert memo.gids(kept, ("l3",), ()) is not None
    for i in range(MeshLeafMemo.GIDS - 1):
        memo.keep_gids(kept, (), (f"w{i}",), ("k",), (0, 0))
    assert memo.gids(kept, ("l3",), ()) is not None
    # other selections of the same selector (a later index state) miss
    assert memo.gids([SimpleNamespace(stamp=(2, 0, 16)) for _ in range(2)],
                     ("l3",), ()) is None
    # one selection the shard does not keep: never a key, never kept
    mixed = [kept[0], SimpleNamespace(stamp=None)]
    size = len(memo)
    assert not memo.keep_gids(mixed, ("l3",), (), ("k",), (0, 0))
    assert memo.gids(mixed, ("l3",), ()) is None and len(memo) == size


def test_a_narrow_selection_builds_its_rows_every_query(monkeypatch):
    """Selections the shards do not keep (here: six series a shard under the
    shipped GATHER_THRESHOLD) are ``bypass``: built and uploaded a query, as
    before, and the memo stays empty."""
    eng, host, _shards = _served_mesh(monkeypatch, keep=False)
    q = "sum by (grp) (rate(m[2m]))"
    for shift in (0, 20_000):
        r = (RANGE[0] + shift, RANGE[1] + shift, RANGE[2])
        got, spans = _ask(eng, q, r)
        _same_bits(got, host.query_range(q, *r))
        assert _leaf(spans).tags["gids"] == "bypass"
    assert len(eng._mesh_memo) == 0
