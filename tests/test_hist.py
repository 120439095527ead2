"""First-class histogram tests: codec (incl. the reference's ~50x wire-size
claim), quantile math, and the end-to-end histogram_quantile(sum(rate(...)))
query (ref analogs: memory HistogramTest/HistogramVectorTest,
query HistogramQuantileMapper specs)."""

import numpy as np
import pytest

from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import PROM_HISTOGRAM
from filodb_tpu.memory import hist as H
from filodb_tpu.query.engine import QueryEngine

BASE = 1_700_000_000_000
IV = 10_000


def make_hist_series(n=100, B=64, rng=None, rate=0.3):
    """Cumulative bucket counts for an increasing histogram (counter-like)."""
    rng = rng or np.random.default_rng(5)
    per_bucket_incr = rng.poisson(rate, (n, B)).cumsum(axis=0)   # over time
    return np.cumsum(per_bucket_incr, axis=1)                     # cumulative in le


def test_codec_roundtrip():
    c = make_hist_series(50, 16)
    buf = H.encode_hist_series(c)
    back = H.decode_hist_series(buf)
    np.testing.assert_array_equal(back, c)


def test_codec_50x_compression_claim():
    """doc/compression.md: 'For 64 buckets ... this format saves 50x space
    compared to the traditional Prometheus data model' (one f64 sample+ts per
    bucket per scrape = 16 bytes/bucket)."""
    # realistic quiet-ish latency histogram: a few observations per scrape
    # spread over 64 buckets
    c = make_hist_series(120, 64, rate=0.05)
    buf = H.encode_hist_series(c)
    prom_model_bytes = 120 * 64 * 16
    ratio = prom_model_bytes / len(buf)
    assert ratio > 50, f"compression ratio only {ratio:.1f}x"


def test_geometric_buckets():
    b = H.GeometricBuckets(2.0, 2.0, 8)
    np.testing.assert_allclose(b.les(), [2, 4, 8, 16, 32, 64, 128, 256])


def test_quantile_host_math():
    les = np.array([1.0, 2.0, 4.0, 8.0, np.inf])
    counts = np.array([0, 10, 30, 40, 40], dtype=float)
    # rank 20 => inside (2,4] bucket, halfway: 2 + 2*(20-10)/(30-10) = 3
    assert H.histogram_quantile(0.5, les, counts) == 3.0
    # q hitting the +Inf bucket returns the last finite bound
    assert H.histogram_quantile(1.0, les, counts) == 4.0 or \
        H.histogram_quantile(1.0, les, counts) == 8.0
    assert np.isnan(H.histogram_quantile(0.5, les, np.zeros(5)))


def test_device_quantile_matches_host():
    import jax.numpy as jnp
    from filodb_tpu.ops.gridfns import histogram_quantile
    rng = np.random.default_rng(8)
    les = np.array([0.5, 1, 2, 4, 8, 16, np.inf])
    counts = np.sort(rng.integers(0, 100, (5, 9, 7)), axis=-1).astype(np.float64)
    got = np.asarray(histogram_quantile(jnp.float64(0.9), jnp.asarray(les),
                                        jnp.asarray(counts)))
    for i in range(5):
        for t in range(9):
            want = H.histogram_quantile(0.9, les, counts[i, t])
            np.testing.assert_allclose(got[i, t], want, equal_nan=True,
                                       err_msg=f"{i},{t}")


@pytest.fixture(scope="module")
def hist_engine():
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=8, samples_per_series=128,
                      flush_batch_size=10**9, dtype="float64")
    shard = ms.setup("histds", PROM_HISTOGRAM, 0, cfg)
    les = np.array([1.0, 2.0, 4.0, 8.0, 16.0, np.inf])
    data = {}
    for s in range(3):
        b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
        counts = make_hist_series(100, 6, np.random.default_rng(s))
        for t in range(100):
            b.add({"_metric_": "req_latency", "pod": f"p{s}"},
                  BASE + t * IV, counts[t].astype(np.float64))
        shard.ingest(b.build())
        data[s] = counts
    shard.flush()
    return QueryEngine(ms, "histds"), les, data


def test_hist_rate_and_quantile_e2e(hist_engine):
    eng, les, data = hist_engine
    start, end, step = BASE + 600_000, BASE + 900_000, 60_000
    r = eng.query_range("histogram_quantile(0.9, sum(rate(req_latency[2m])))",
                        start, end, step)
    series = list(r.matrix.iter_series())
    assert len(series) == 1
    key, ts, vals = series[0]
    assert np.isfinite(vals).all()
    # golden: per-bucket prometheus rate summed across pods, then quantile
    out_ts = np.arange(start, end + 1, step)
    from .prom_reference import eval_range_fn
    tgrid = BASE + np.arange(100) * IV
    summed = np.zeros((len(out_ts), 6))
    for s, counts in data.items():
        for b in range(6):
            summed[:, b] += eval_range_fn("rate", tgrid, counts[:, b].astype(float),
                                          out_ts, 120_000)
    want = np.array([H.histogram_quantile(0.9, les, summed[t]) for t in range(len(out_ts))])
    np.testing.assert_allclose(vals, want, rtol=1e-9)


def test_hist_sum_over_time_and_bucket(hist_engine):
    eng, les, data = hist_engine
    start = BASE + 600_000
    r = eng.query_range('histogram_bucket(4.0, req_latency{pod="p0"})',
                        start, start + 120_000, 60_000)
    (key, ts, vals), = list(r.matrix.iter_series())
    # value of the le=4 bucket (index 2) at those instants
    cell = (ts - BASE) // IV
    want = data[0][cell.astype(int), 2]
    np.testing.assert_allclose(vals, want)


def test_hist_off_grid_rate_matches_golden():
    """Histogram queries on an off-grid shard (irregular timestamps) take the
    general searchsorted hist path and must match the per-bucket golden model
    (previously: QueryError; ref HistogramVector read through chunked range
    functions for arbitrary layouts)."""
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=8, samples_per_series=128,
                      flush_batch_size=10**9, dtype="float64")
    shard = ms.setup("histds", PROM_HISTOGRAM, 0, cfg)
    les = np.array([1.0, 2.0, 4.0, np.inf])
    rng = np.random.default_rng(17)
    # irregular scrape times (jittered): defeats the grid tracker
    tgrid = BASE + np.cumsum(rng.integers(7_000, 14_000, 60))
    data = {}
    for s in range(2):
        counts = make_hist_series(60, 4, np.random.default_rng(40 + s))
        b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
        for t in range(60):
            b.add({"_metric_": "lat", "pod": f"p{s}"}, int(tgrid[t]),
                  counts[t].astype(np.float64))
        shard.ingest(b.build())
        data[s] = counts
    shard.flush()
    assert shard.store.grid_info() is None   # truly off-grid
    eng = QueryEngine(ms, "histds")
    start, end, step = BASE + 300_000, BASE + 500_000, 45_000
    r = eng.query_range("histogram_quantile(0.9, sum(rate(lat[2m])))",
                        start, end, step)
    (key, ts, vals), = list(r.matrix.iter_series())
    out_ts = np.arange(start, end + 1, step)
    from .prom_reference import eval_range_fn
    summed = np.zeros((len(out_ts), 4))
    for s, counts in data.items():
        for bk in range(4):
            summed[:, bk] += eval_range_fn("rate", tgrid,
                                           counts[:, bk].astype(float),
                                           out_ts, 120_000)
    want = np.array([H.histogram_quantile(0.9, les, summed[t])
                     for t in range(len(out_ts))])
    np.testing.assert_allclose(vals, want, rtol=1e-9, equal_nan=True)


def test_hist_churned_cohort_matches_general():
    """A late-joining histogram series keeps the shard on the grid path; its
    rows are corrected via the general hist kernels bit-for-bit."""
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=8, samples_per_series=128,
                      flush_batch_size=10**9, dtype="float64")
    shard = ms.setup("histds", PROM_HISTOGRAM, 0, cfg)
    les = np.array([1.0, 4.0, np.inf])
    b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
    series = {s: make_hist_series(80, 3, np.random.default_rng(60 + s))
              for s in range(4)}
    for t in range(80):
        for s in range(4):
            if s == 3 and t < 30:
                continue   # churned pod
            b.add({"_metric_": "lat", "pod": f"p{s}"}, BASE + t * IV,
                  series[s][t].astype(np.float64))
    shard.ingest(b.build())
    shard.flush()
    assert shard.store.grid_info() is not None
    eng = QueryEngine(ms, "histds")
    q = ("histogram_quantile(0.9, rate(lat[2m]))",
         BASE + 400_000, BASE + 700_000, 60_000)
    r1 = eng.query_range(*q)
    shard.store.grid_ok = False
    r2 = eng.query_range(*q)
    shard.store.grid_ok = True
    g1 = {k.as_dict()["pod"]: np.asarray(v) for k, _, v in r1.matrix.iter_series()}
    g2 = {k.as_dict()["pod"]: np.asarray(v) for k, _, v in r2.matrix.iter_series()}
    assert set(g1) == {"p0", "p1", "p2", "p3"}
    for p in g1:
        np.testing.assert_array_equal(g1[p], g2[p], err_msg=p)


def test_hist_batch_downsample_and_query(tmp_path):
    """hSum batch downsampling of a native-histogram dataset: per-bucket sums
    per resolution bucket, persisted with the bucket scheme, loadable and
    queryable (histogram_quantile works on the downsampled dataset)."""
    from filodb_tpu.core.store import FileColumnStore
    from filodb_tpu.jobs.batch_downsampler import (load_downsampled,
                                                   run_batch_downsample)
    sink = FileColumnStore(str(tmp_path))
    cfg = StoreConfig(max_series_per_shard=4, samples_per_series=128,
                      flush_batch_size=10**9, groups_per_shard=1, dtype="float64")
    ms = TimeSeriesMemStore()
    shard = ms.setup("histds", PROM_HISTOGRAM, 0, cfg, sink=sink)
    les = np.array([1.0, 2.0, np.inf])
    counts = make_hist_series(30, 3, np.random.default_rng(9))
    b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
    for t in range(30):
        b.add({"_metric_": "lat", "pod": "p0"}, BASE + t * IV,
              counts[t].astype(np.float64))
    shard.ingest(b.build(), offset=0)
    shard.flush_all_groups()
    RES = 60_000   # 1m buckets over 10s samples: 6 samples per bucket
    written = run_batch_downsample(sink, "histds", 0, RES)
    assert written == {"hSum": 1}
    ms2 = TimeSeriesMemStore()
    ds = load_downsampled(sink, "histds", 0, RES, "hSum", ms2,
                          StoreConfig(max_series_per_shard=4,
                                      samples_per_series=64,
                                      flush_batch_size=10**9, dtype="float64"))
    np.testing.assert_allclose(ds.bucket_les, les)
    ts0, v0 = ds.store.series_snapshot(0)
    assert v0.shape[1] == 3
    # golden: per-bucket sums grouped by each sample's 1m time bucket
    tgrid = BASE + np.arange(30) * IV
    want = np.stack([counts[tgrid // RES == bk].sum(axis=0)
                     for bk in np.unique(tgrid // RES)])
    np.testing.assert_allclose(v0, want)
    # the downsampled dataset answers quantile queries
    eng = QueryEngine(ms2, "histds:ds_1m:hSum")
    r = eng.query_range("histogram_quantile(0.5, lat)",
                        int(ts0[1]), int(ts0[3]), RES)
    (_k, _t, vals), = list(r.matrix.iter_series())
    assert np.isfinite(vals).all()


def test_hist_unsupported_fn_raises(hist_engine):
    eng, _, _ = hist_engine
    from filodb_tpu.query.rangevector import QueryError
    with pytest.raises(QueryError):
        eng.query_range("stddev_over_time(req_latency[2m])",
                        BASE + 600_000, BASE + 700_000, 60_000)


def test_hist_persistence_roundtrip(tmp_path):
    from filodb_tpu.core.store import FileColumnStore
    sink = FileColumnStore(str(tmp_path))
    cfg = StoreConfig(max_series_per_shard=4, samples_per_series=64,
                      flush_batch_size=10**9, groups_per_shard=2, dtype="float64")
    ms = TimeSeriesMemStore()
    shard = ms.setup("histds", PROM_HISTOGRAM, 0, cfg, sink=sink)
    les = np.array([1.0, 2.0, np.inf])
    b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
    counts = make_hist_series(20, 3)
    for t in range(20):
        b.add({"_metric_": "h"}, BASE + t * IV, counts[t].astype(np.float64))
    shard.ingest(b.build(), offset=0)
    shard.flush_all_groups()
    # recover into a fresh store
    ms2 = TimeSeriesMemStore()
    shard2 = ms2.setup("histds", PROM_HISTOGRAM, 0, cfg, sink=sink)
    shard2.recover()
    assert shard2.store is not None and shard2.store.nbuckets == 3
    np.testing.assert_allclose(shard2.bucket_les, les)
    ts0, v0 = shard2.store.series_snapshot(0)
    assert len(ts0) == 20


def test_raw_hist_result_expands_to_le_series(hist_engine):
    """rate(hist[2m]) without a quantile mapper serializes as classic
    Prometheus le-labeled bucket series."""
    eng, les, data = hist_engine
    r = eng.query_range("rate(req_latency[2m])",
                        BASE + 600_000, BASE + 660_000, 30_000)
    series = list(r.matrix.iter_series())
    # 3 pods x 6 buckets
    assert len(series) == 18
    les_seen = {k.as_dict()["le"] for k, _, _ in series}
    assert les_seen == {"1", "2", "4", "8", "16", "+Inf"}
    # cumulative within a pod at each step: monotone in le
    pod0 = {k.as_dict()["le"]: np.asarray(v) for k, _, v in series
            if k.as_dict()["pod"] == "p0"}
    np.testing.assert_array_equal(
        np.maximum(pod0["1"], pod0["2"]), pod0["2"])
    np.testing.assert_array_equal(
        np.maximum(pod0["16"], pod0["+Inf"]), pod0["+Inf"])


# ---- classic le-labeled histogram_quantile (HistogramQuantileMapper parity) --

def _classic_gauge_engine(les, data):
    """The same bucket counters ingested as classic scalar ``_bucket`` series
    with le labels (what remote-write / the Influx gateway produce)."""
    from filodb_tpu.core.schemas import GAUGE
    from filodb_tpu.query.rangevector import fmt_value
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=32, samples_per_series=128,
                      flush_batch_size=10**9, dtype="float64")
    shard = ms.setup("prometheus", GAUGE, 0, cfg)
    for s, counts in data.items():
        for bi, le in enumerate(les):
            le_s = "+Inf" if np.isinf(le) else fmt_value(le)
            b = RecordBuilder(GAUGE)
            for t in range(counts.shape[0]):
                b.add({"_metric_": "req_latency_bucket", "pod": f"p{s}",
                       "le": le_s}, BASE + t * IV, float(counts[t, bi]))
            shard.ingest(b.build())
    shard.flush()
    return QueryEngine(ms, "prometheus")


def test_classic_le_quantile_matches_native(hist_engine):
    """Golden parity (ref: HistogramQuantileMapper.scala:23-90): the same
    histogram ingested natively and as classic le-labeled bucket series
    answers histogram_quantile identically, per-histogram and summed."""
    eng, les, data = hist_engine
    ceng = _classic_gauge_engine(les, data)
    start, end, step = BASE + 600_000, BASE + 900_000, 60_000

    rn = eng.query_range("histogram_quantile(0.9, rate(req_latency[2m]))",
                         start, end, step)
    rc = ceng.query_range(
        "histogram_quantile(0.9, rate(req_latency_bucket[2m]))",
        start, end, step)
    native = {k.without(("_metric_",)): np.asarray(v)
              for k, _t, v in rn.matrix.iter_series()}
    classic = {k.without(("_metric_",)): np.asarray(v)
               for k, _t, v in rc.matrix.iter_series()}
    assert set(native) == set(classic) and len(native) == 3
    for k in native:
        np.testing.assert_allclose(classic[k], native[k], rtol=1e-9)

    # the canonical dashboard form: quantile of sum-of-rates
    rn2 = eng.query_range(
        "histogram_quantile(0.9, sum(rate(req_latency[2m])))",
        start, end, step)
    rc2 = ceng.query_range(
        "histogram_quantile(0.9, sum by (le) (rate(req_latency_bucket[2m])))",
        start, end, step)
    (_k, _t, vn), = list(rn2.matrix.iter_series())
    (_k, _t, vc), = list(rc2.matrix.iter_series())
    np.testing.assert_allclose(vc, vn, rtol=1e-9)


def test_classic_le_quantile_semantics():
    """Unit semantics (ref: HistogramQuantileMapper.makeMonotonic +
    histogramQuantile): monotonic repair, missing +Inf bucket, missing le
    label, and out-of-range q."""
    from filodb_tpu.query.exec import (InstantVectorFunctionMapper,
                                       _classic_le_quantile)
    from filodb_tpu.query.rangevector import QueryError, RangeVectorKey, \
        ResultMatrix
    out_ts = np.array([0, 1000], np.int64)

    def mat(rows):
        keys = [RangeVectorKey.of(d) for d, _ in rows]
        vals = np.array([v for _, v in rows], np.float64)
        return ResultMatrix(out_ts, vals, keys)

    # NaN and regressing bucket rates take the running max before quantile
    m = mat([({"le": "1"}, [10.0, 10.0]),
             ({"le": "2"}, [np.nan, 8.0]),        # NaN -> repaired to 10
             ({"le": "4"}, [30.0, 30.0]),
             ({"le": "+Inf"}, [40.0, 40.0])])
    r = _classic_le_quantile(m, 0.5)
    # rank 20: first step interpolates in (2,4]: 2 + 2*(20-10)/(30-10) = 3
    np.testing.assert_allclose(np.asarray(r.values)[0], [3.0, 3.0])

    # without a +Inf bucket the quantile is undefined
    m = mat([({"le": "1"}, [10.0, 10.0]), ({"le": "4"}, [30.0, 30.0])])
    assert np.isnan(np.asarray(_classic_le_quantile(m, 0.5).values)).all()

    # q outside [0, 1]
    m = mat([({"le": "1"}, [10.0, 10.0]), ({"le": "+Inf"}, [30.0, 30.0])])
    assert np.isposinf(np.asarray(_classic_le_quantile(m, 1.5).values)).all()
    assert np.isneginf(np.asarray(_classic_le_quantile(m, -0.5).values)).all()

    # a series without an le tag is an error (reference throws)
    m = mat([({"le": "1"}, [10.0, 10.0]), ({"pod": "p0"}, [30.0, 30.0])])
    try:
        _classic_le_quantile(m, 0.5)
        assert False, "expected QueryError"
    except QueryError:
        pass

    # the mapper routes scalar (non-native-histogram) input to the classic path
    out = InstantVectorFunctionMapper("histogram_quantile", (0.9,)).apply(
        mat([({"le": "1"}, [10.0, 10.0]), ({"le": "+Inf"}, [10.0, 10.0])]),
        None)
    assert np.asarray(out.values).shape == (1, 2)


def test_fused_hist_quantile_route_and_parity(hist_engine):
    """histogram_quantile(q, sum(rate)) takes the single-dispatch fused
    device program; result matches the general ExecPlan path exactly (same
    algebra, same partial layout)."""
    eng, les, data = hist_engine
    start, end, step = BASE + 600_000, BASE + 900_000, 60_000
    q = "histogram_quantile(0.9, sum(rate(req_latency[2m])))"
    r1 = eng.query_range(q, start, end, step)
    # 6 buckets are no whole sublane tile: outside the tiled raw tier's
    # gate (fusedresident.raw_hist_fusable), so the untiled one-program
    # composition serves — the bare route name
    assert r1.exec_path == "fused-hist"
    # grouping by an absent label still routes fused and must equal the
    # global sum (one group)
    r2 = eng.query_range(
        "histogram_quantile(0.9, sum by (__absent__) (rate(req_latency[2m])))",
        start, end, step)
    assert r2.exec_path == "fused-hist"
    (_k, _t, v1), = list(r1.matrix.iter_series())
    (_k, _t, v2), = list(r2.matrix.iter_series())
    np.testing.assert_allclose(v1, v2, rtol=1e-12, equal_nan=True)
    # general-path oracle: identical engine with the fused route disabled
    eng2 = QueryEngine(eng.memstore, eng.dataset)
    eng2._try_fused_hist = lambda plan, ctx=None: None
    r3 = eng2.query_range(q, start, end, step)
    assert r3.exec_path == "local"
    (_k, _t, v3), = list(r3.matrix.iter_series())
    np.testing.assert_allclose(v1, v3, rtol=1e-12, equal_nan=True)


def test_fused_bail_after_leaf_does_not_double_count_stats(hist_engine):
    """PR-7 regression: a fused-hist attempt that bails AFTER its leaf
    select (here: evaluation window too far from the grid base) re-runs
    the leaf on the general path — the probe's stats must be discarded,
    not added on top of the general path's (stats equal a fused-disabled
    oracle's exactly)."""
    eng, _les, _data = hist_engine
    q = "histogram_quantile(0.9, sum(rate(req_latency[2m])))"
    # >= 2**31 ms from the grid base: the fused route bails post-leaf
    start = BASE + 2**31 + 600_000
    end, step = start + 300_000, 60_000
    res = eng.query_range(q, start, end, step)
    assert res.exec_path == "local"
    oracle = QueryEngine(eng.memstore, eng.dataset)
    oracle._try_fused_hist = lambda plan, ctx=None: None
    want = oracle.query_range(q, start, end, step)
    got_d, want_d = res.stats.to_dict(), want.stats.to_dict()
    for field in ("series_matched", "blocks_raw", "blocks_narrow",
                  "rows_paged_in"):
        assert got_d[field] == want_d[field], field
