"""Compressed-resident histogram stores: i8/i16 2D-delta bucket blocks as the
ONLY resident copy (ref: the reference keeps in-memory histograms compressed —
doc/compression.md "Histograms", HistogramVector.scala 2D-delta sections; its
1.5M-series/GB claim leans on exactly this), plus the residency config knob,
mesh eligibility of narrow-resident stores, and the peer-wire/metadata
satellite fixes that ride with universal compressed residency."""

import numpy as np
import pytest

from filodb_tpu.config import Config
from filodb_tpu.core.chunkstore import DeferredDecodeHist
from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import PROM_HISTOGRAM
from filodb_tpu.query.engine import QueryEngine

START = 1_000_000
INTERVAL = 10_000
N = 96
B = 8
LES = np.concatenate([2.0 ** np.arange(B - 1), [np.inf]])


def _cfg(**kw):
    return StoreConfig(max_series_per_shard=16, samples_per_series=128,
                       flush_batch_size=10**9, dtype="float32", **kw)


def _build(mode: str, mixed: bool = False, n_series: int = 10, bursty=False,
           frac: float = 0.3):
    """Integer cumulative bucket counts (compress exactly); ``mixed`` scales
    some rows to non-integer values that must take the raw-f32 cohort pool;
    ``bursty`` makes increments too wide for i8 (i16 tier)."""
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", PROM_HISTOGRAM, 0,
                  _cfg(compressed_residency=mode))
    rng = np.random.default_rng(7)
    for s in range(n_series):
        b = RecordBuilder(PROM_HISTOGRAM, bucket_les=LES)
        lam = 200.0 if bursty else 0.4
        c = np.cumsum(np.cumsum(rng.poisson(lam, (N, B)), axis=0),
                      axis=1).astype(np.float64)
        if bursty:
            # oscillating per-scrape rates: delta-of-deltas escapes i8
            c += np.cumsum((np.arange(N) % 2) * 300, dtype=np.int64)[:, None]
        if mixed and s % 4 == 3:
            c = c * frac                      # non-integer: cohort pool
        for t in range(N):
            b.add({"_metric_": "h", "host": f"x{s}"}, START + t * INTERVAL,
                  c[t])
        ms.ingest("prometheus", 0, b.build())
    sh.flush()
    return ms, sh


def test_hist_resident_frees_blocks_and_meets_retention():
    ms_r, sh_r = _build("off")
    ms_c, sh_c = _build("all")
    st = sh_c.store
    assert st.is_narrow_resident
    assert st.val is None and st.ts is None
    assert isinstance(st.column_array(), DeferredDecodeHist)
    assert st._nhist[0].dtype == np.int8      # quiet series: i8 tier
    # acceptance bar: >= 3x retention at fixed HBM vs the raw f32 store
    raw = sh_r.store.resident_sample_bytes()
    assert raw / st.resident_sample_bytes() >= 3.0
    # decode + ts derivation are bit-exact against the raw store
    dec = np.asarray(st.value_block())
    np.testing.assert_array_equal(dec[:10, :N], np.asarray(sh_r.store.val)[:10, :N])
    np.testing.assert_array_equal(np.asarray(st.ts_block())[:10, :N],
                                  np.asarray(sh_r.store.ts)[:10, :N])


def test_hist_bursty_rows_take_the_i16_tier():
    ms, sh = _build("all", bursty=True)
    st = sh.store
    assert st.is_narrow_resident
    assert st._nhist[0].dtype == np.int16
    ms_r, sh_r = _build("off", bursty=True)
    dec = np.asarray(st.value_block())
    np.testing.assert_array_equal(dec[:10, :N], np.asarray(sh_r.store.val)[:10, :N])


def _build_with_reset(mode: str):
    """Cumulative counters with a mid-stream RESET (process restart) on some
    rows — integer data that round-trips bit-exactly but whose negative
    increments the raw rate kernel clamps (counter correction)."""
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", PROM_HISTOGRAM, 0,
                  _cfg(compressed_residency=mode))
    rng = np.random.default_rng(21)
    for s in range(8):
        b = RecordBuilder(PROM_HISTOGRAM, bucket_les=LES)
        c = np.cumsum(np.cumsum(rng.poisson(0.5, (N, B)), axis=0),
                      axis=1).astype(np.float64)
        if s % 4 == 0:
            c[N // 2:] -= c[N // 2][None, :]   # restart: counts drop to ~0
        for t in range(N):
            b.add({"_metric_": "h", "host": f"x{s}"}, START + t * INTERVAL,
                  c[t])
        ms.ingest("prometheus", 0, b.build())
    sh.flush()
    return ms, sh


def test_hist_counter_reset_rows_take_the_pool():
    """The raw rate/increase kernels clamp negative increments (counter-reset
    correction, RateFunctions.scala) — a nonlinear step the narrow kernel's
    telescoped matmuls cannot reproduce. Reset rows must therefore fail the
    encoder's ok contract, land in the cohort pool, and answer through the
    raw path — parity holds across residencies."""
    ms_a, _ = _build_with_reset("off")
    ms_b, sh_b = _build_with_reset("all")
    st = sh_b.store
    assert st.is_narrow_resident
    _dd, _fd, ok = st.hist_operands()
    assert (~ok[:8:4]).all(), "reset rows must be pooled"
    assert ok[1:8:4].all() and ok[2:8:4].all(), "monotone rows must stream"
    ea = QueryEngine(ms_a, "prometheus")
    eb = QueryEngine(ms_b, "prometheus")
    start, end, step = START + 300_000, START + 800_000, 30_000
    for q in ("sum(rate(h[2m]))", "sum(increase(h[2m]))",
              "histogram_quantile(0.9, sum(rate(h[2m])))"):
        a = np.asarray(ea.query_range(q, start, end, step).matrix.values)
        b = np.asarray(eb.query_range(q, start, end, step).matrix.values)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6, equal_nan=True)
        assert np.nanmin(a) >= 0.0          # clamped rates are non-negative


@pytest.mark.parametrize("frac", [0.3, 0.5])
def test_hist_mixed_rows_take_the_pool_bit_exact(frac):
    """0.3-scaled rows do not round-trip in f32; 0.5-scaled rows DO (every
    partial sum is a multiple of 0.5 far below 2^23) and would still be
    truncated by the integer cast — both must be pooled (or stored
    losslessly), whatever the backend's cumsum association."""
    ms, sh = _build("all", mixed=True, frac=frac)
    st = sh.store
    assert st.is_narrow_resident
    dd, first_d, ok = st.hist_operands()
    assert (~ok[:10]).sum() >= 2              # scaled rows are in the pool
    dec = np.asarray(st.value_block())
    ms_r, sh_r = _build("off", mixed=True, frac=frac)
    np.testing.assert_array_equal(dec[:10, :N], np.asarray(sh_r.store.val)[:10, :N])


@pytest.mark.parametrize("mixed", [False, True])
def test_hist_query_parity_resident_vs_f32(mixed):
    """quantile-of-sum-of-rate (the fused path) and every hist grid function
    answer identically whether the store is raw-f32 or hist-resident —
    bit-exactly for integer data (but where each store's own tiled tier
    serves: to an f32 ulp); pool rows recompute through the general
    kernels (different f32 summation order, so the aggregate rounds)."""
    ms_a, _ = _build("off", mixed)
    ms_b, sh_b = _build("all", mixed)
    assert sh_b.store.is_narrow_resident
    ea = QueryEngine(ms_a, "prometheus")
    eb = QueryEngine(ms_b, "prometheus")
    start, end, step = START + 300_000, START + 800_000, 30_000
    for q in ("histogram_quantile(0.9, sum(rate(h[2m])))",
              "histogram_quantile(0.5, sum(rate(h[2m])))",
              "sum(rate(h[2m]))", "sum(increase(h[3m]))",
              "sum_over_time(h[2m])", "sum(delta(h[2m]))",
              "last_over_time(h[2m])", "h",
              'histogram_quantile(0.9, sum(rate(h{host="x1"}[2m])))'):
        ra = ea.query_range(q, start, end, step)
        rb = eb.query_range(q, start, end, step)
        # each engine reports the fused variant it served with: the raw
        # store "fused-hist[pallas|xla]" (the tiled raw tier), the resident
        # one "fused-hist-narrow[...]"; routes otherwise match
        assert (rb.exec_path == ra.exec_path
                or (ra.exec_path.startswith("fused-hist[")
                    and rb.exec_path
                    == ra.exec_path.replace("fused-hist[",
                                            "fused-hist-narrow["))), \
            (q, ra.exec_path, rb.exec_path)
        a, b = np.asarray(ra.matrix.values), np.asarray(rb.matrix.values)
        assert a.shape == b.shape, q
        if mixed:
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                       equal_nan=True)
        elif ra.exec_path.startswith("fused-hist["):
            # two tiled tiers, two folds: the raw tier sums bucket steps in
            # compensated pairs, the narrow tier cumulative buckets in plain
            # f32 — they agree to an f32 ulp or two of the answer
            np.testing.assert_allclose(a, b, rtol=1e-6, equal_nan=True)
        else:
            np.testing.assert_array_equal(a, b)
    assert sh_b.store.is_narrow_resident    # read-only queries don't rehydrate


def test_hist_fused_path_never_materializes():
    """The flagship hist query on a resident store streams the dd block —
    no transient f32 decode of the whole [S, C, B] block, no ts derivation."""
    ms, sh = _build("all")
    st = sh.store
    calls = {"v": 0, "t": 0}
    orig_v, orig_t = st.value_block, st.ts_block
    st.value_block = lambda: calls.__setitem__("v", calls["v"] + 1) or orig_v()
    st.ts_block = lambda: calls.__setitem__("t", calls["t"] + 1) or orig_t()
    eng = QueryEngine(ms, "prometheus")
    r = eng.query_range("histogram_quantile(0.9, sum(rate(h[2m])))",
                        START + 300_000, START + 800_000, 30_000)
    # on the CPU the kernel is interpreted, and the route says so
    assert r.exec_path == "fused-hist-narrow[pallas-interpret]", r.exec_path
    assert r.matrix.num_series == 1
    r2 = eng.query_range("sum(rate(h[2m]))", START + 300_000, START + 800_000,
                         30_000)
    assert r2.matrix.num_series == 1
    assert calls == {"v": 0, "t": 0}, calls
    st.value_block, st.ts_block = orig_v, orig_t


def test_empty_selection_never_materializes():
    """A selection matching nothing (typo'd metric) must return synthetic pad
    arrays, not slice the deferred view — that slice decodes the FULL block
    (~GBs at production scale) for an empty answer."""
    ms, sh = _build("all")
    st = sh.store
    calls = {"v": 0}
    orig_v = st.value_block
    st.value_block = lambda: calls.__setitem__("v", calls["v"] + 1) or orig_v()
    eng = QueryEngine(ms, "prometheus")
    r = eng.query_range("sum(rate(no_such_metric[2m]))",
                        START + 300_000, START + 800_000, 30_000)
    assert r.matrix.num_series == 0
    assert calls == {"v": 0}, calls
    st.value_block = orig_v


def test_hist_append_rehydrates_and_recompresses():
    ms, sh = _build("all")
    st = sh.store
    assert st.is_narrow_resident
    rng = np.random.default_rng(3)
    b = RecordBuilder(PROM_HISTOGRAM, bucket_les=LES)
    tail = np.cumsum(rng.poisson(0.4, (8, B)), axis=1).astype(np.float64) + 500
    for t in range(8):
        b.add({"_metric_": "h", "host": "x0"},
              START + (N + t) * INTERVAL, np.maximum.accumulate(tail[t]))
    ms.ingest("prometheus", 0, b.build())
    sh.flush()
    assert st.is_narrow_resident              # re-compressed at flush
    eng = QueryEngine(ms, "prometheus")
    r = eng.query_range('sum_over_time(h{host="x0"}[1m])',
                        START + (N + 7) * INTERVAL,
                        START + (N + 7) * INTERVAL, 1)
    assert r.matrix.num_series == 1


def test_config_residency_roundtrip():
    cfg = Config({"store": {"compressed_residency": "all"}})
    sc = cfg.store_config()
    assert sc.compressed_residency == "all"
    assert sc.residency_mode() == "all"
    assert Config().store_config().residency_mode() == "off"
    assert StoreConfig(narrow_resident=True).residency_mode() == "gauge"
    assert StoreConfig(compressed_residency="gauge").residency_mode() == "gauge"
    with pytest.raises(ValueError):
        StoreConfig(compressed_residency="everything")
    with pytest.raises(ValueError):
        Config({"store": {"compressed_residency": "bogus"}}).store_config()


def test_gauge_mode_leaves_hist_stores_raw():
    ms, sh = _build("gauge")
    assert not sh.store.is_narrow_resident
    assert sh.store.val is not None


def test_hist_gather_rows_matches_full_materialization():
    import jax.numpy as jnp

    from filodb_tpu.core.chunkstore import DeferredTs

    ms, sh = _build("all", mixed=True)
    st = sh.store
    rid = jnp.asarray(np.array([0, 3, 7, 9], np.int32))
    dv = st.column_array()
    assert isinstance(dv, DeferredDecodeHist)
    rows = np.asarray(dv.gather_rows(rid))
    full = np.asarray(st.value_block())
    np.testing.assert_array_equal(rows, full[np.asarray(rid)])
    trows = np.asarray(DeferredTs(st).gather_rows(rid))
    np.testing.assert_array_equal(trows, np.asarray(st.ts_block())[np.asarray(rid)])


# -- mesh eligibility of narrow-resident gauge stores -------------------------

def _build_mesh_stores(narrow: bool):
    import jax

    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.parallel.shardmapper import ShardMapper
    devs = jax.devices()
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=16, samples_per_series=128,
                      flush_batch_size=10**9, dtype="float32",
                      narrow_resident=narrow)
    shards = []
    rng = np.random.default_rng(5)
    for i, dev in enumerate(devs):
        shards.append(ms.setup("prometheus", GAUGE, i, cfg, device=dev))
    for i in range(24):
        b = RecordBuilder(GAUGE)
        vals = np.cumsum(rng.integers(1, 50, N)).astype(np.float64)
        for t in range(N):
            b.add({"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 3}"},
                  START + t * INTERVAL, float(vals[t]))
        ms.ingest("prometheus", i % len(devs), b.build())
    ms.flush_all()
    return ms, shards, ShardMapper(len(devs))


@pytest.mark.parametrize("q", ["sum(rate(m[2m]))",
                               "sum by (grp) (rate(m[2m]))",
                               "max(m)", "topk(2, rate(m[2m]))",
                               "quantile(0.5, m)"])
def test_mesh_accepts_narrow_resident_stores(q):
    """_mesh_executor no longer bails on is_narrow_resident: the fused route
    streams the i16 state (or transiently decodes), and every mesh answer
    matches the host path on the identical data."""
    from filodb_tpu.parallel.distributed import make_mesh
    ms, shards, mapper = _build_mesh_stores(True)
    assert all(s.store.is_narrow_resident for s in shards)
    em = QueryEngine(ms, "prometheus", mapper, mesh=make_mesh())
    eh = QueryEngine(ms, "prometheus", mapper)          # host path oracle
    start, end, step = START + 300_000, START + 800_000, 30_000
    rm = em.query_range(q, start, end, step)
    assert rm.exec_path.startswith("mesh[pjit]-"), rm.exec_path
    rh = eh.query_range(q, start, end, step)
    a = {k: v for k, _t, v in rh.matrix.iter_series()}
    b = {k: v for k, _t, v in rm.matrix.iter_series()}
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-6, atol=1e-9,
                                   equal_nan=True)
    assert all(s.store.is_narrow_resident for s in shards)


def test_mesh_narrow_fused_streams_i16():
    """With every shard narrow-resident and pool-free, the fused mesh route
    streams the quantized state (no per-shard value_block decode)."""
    from filodb_tpu.parallel.distributed import make_mesh
    ms, shards, mapper = _build_mesh_stores(True)
    counts = {"v": 0}
    origs = []
    for s in shards:
        orig = s.store.value_block
        origs.append((s.store, orig))
        s.store.value_block = (lambda o=orig:
                               counts.__setitem__("v", counts["v"] + 1) or o())
    em = QueryEngine(ms, "prometheus", mapper, mesh=make_mesh())
    rn = em.query_range("sum(rate(m[2m]))", START + 300_000,
                        START + 800_000, 30_000)
    assert rn.exec_path == "mesh[pjit]-fused-narrow", rn.exec_path
    assert counts["v"] == 0
    for st, orig in origs:
        st.value_block = orig


# -- peer-wire + metadata satellites ------------------------------------------

def test_corrupt_remote_result_raises_query_error():
    from filodb_tpu.query.rangevector import (QueryError, RangeVectorKey,
                                              ResultMatrix)
    from filodb_tpu.query.wire import deserialize_result, serialize_result
    good = serialize_result(ResultMatrix(
        np.arange(3, dtype=np.int64), np.ones((2, 3)),
        [RangeVectorKey((("host", "a"),)), RangeVectorKey((("host", "b"),))]))
    for bad in (good[: len(good) // 2], b"A\x00\x00", b"A\xff\xff\xff\xff",
                b"Z" + good[1:], b""):
        with pytest.raises(QueryError):
            deserialize_result(bad)


def test_remote_leaf_classifies_torn_payload(monkeypatch):
    import urllib.request

    from filodb_tpu.query.exec import SelectRawPartitionsExec
    from filodb_tpu.query.wire import RemoteLeafExec, RemotePeerError

    class FakeResp:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def read(self):
            return b"A\x10\x00\x00\x00{\"truncated"   # torn mid-meta

    monkeypatch.setattr(urllib.request, "urlopen",
                        lambda *a, **k: FakeResp())
    leaf = RemoteLeafExec(endpoint="peer:1", dataset="ds",
                          inner=SelectRawPartitionsExec(shard=3))
    with pytest.raises(RemotePeerError) as ei:
        leaf.execute(None)
    assert ei.value.endpoint == "peer:1" and ei.value.shard == 3
    assert ei.value.shards == (3,)
    assert "shards [3]" in str(ei.value)


def test_label_values_topk_cross_node_ranking(monkeypatch):
    """top_k forwards on the peer fan-out and the limit re-applies AFTER the
    count-merge: a value barely in the local top-k can dominate cluster-wide."""
    from filodb_tpu.core.schemas import GAUGE
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", GAUGE, 0, _cfg())
    b = RecordBuilder(GAUGE)
    # local counts: a=3 series, b=2, c=1
    for i, host in enumerate(["a"] * 3 + ["b"] * 2 + ["c"]):
        b.add({"_metric_": "m", "host": host, "u": str(i)}, START, 1.0)
    ms.ingest("prometheus", 0, b.build())
    sh.flush()
    eng = QueryEngine(ms, "prometheus")
    seen_paths = []

    def fake_peer(path):
        seen_paths.append(path)
        return [["c", 10], ["b", 1]]     # peer: c dominates cluster-wide

    monkeypatch.setattr(eng, "_peer_metadata", fake_peer)
    monkeypatch.setattr(eng, "_has_remote_shards", lambda: True)
    out = eng.label_values("host", top_k=2)
    assert out == ["c", "a"]             # c=11, a=3, b=3 (a wins the tie)
    assert seen_paths and "top_k=2" in seen_paths[0] \
        and "counts=1" in seen_paths[0]
    # local_only keeps the local ranking and respects k
    assert eng.label_values("host", top_k=2, local_only=True) == ["a", "b"]


def test_http_local_marker_is_strict(monkeypatch):
    """``local=0`` (or garbage) must NOT silently enable local-only mode —
    only the exact peer-leg marker ``local=1`` does."""
    import json as _json
    import urllib.request

    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.http.api import FiloHttpServer
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", GAUGE, 0, _cfg())
    b = RecordBuilder(GAUGE)
    b.add({"_metric_": "m", "host": "h0"}, START, 1.0)
    ms.ingest("prometheus", 0, b.build())
    sh.flush()
    eng = QueryEngine(ms, "prometheus")
    seen = []
    orig = eng.label_names

    def spy(filters=None, local_only=False):
        seen.append(local_only)
        return orig(filters, local_only=True)   # never fan out in the test

    eng.label_names = spy
    srv = FiloHttpServer({"prometheus": eng}, port=0).start()
    try:
        base = f"http://127.0.0.1:{srv.port}/promql/prometheus/api/v1/labels"
        for suffix, want in (("", False), ("?local=0", False),
                             ("?local=yes", False), ("?local=1", True)):
            with urllib.request.urlopen(base + suffix, timeout=10) as r:
                assert _json.load(r)["status"] == "success"
        assert seen == [False, False, False, True]
    finally:
        srv.stop()
