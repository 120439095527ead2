"""Narrow (u16 quantized) resident form: bit-exact fast path for
integer-valued series, raw-f32 fallback for incompressible rows
(ops/narrow.py; ref: the reference's compressed chunk read path,
NibblePack.scala / doc/compression.md — bytes-per-sample as the bandwidth
lever)."""

import numpy as np

from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE
from filodb_tpu.query.engine import QueryEngine

BASE = 1_700_000_000_000
IV = 10_000
NSERIES = 520          # store pads to S=1024 (>=512: narrow-eligible)
NSAMP = 64


def _build(narrow: bool, values_of):
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=1024, samples_per_series=NSAMP + 8,
                      flush_batch_size=10**9, dtype="float32",
                      narrow_resident=narrow)
    shard = ms.setup("prometheus", GAUGE, 0, cfg)
    ts = BASE + np.arange(NSAMP, dtype=np.int64) * IV
    b = RecordBuilder(GAUGE)
    for s in range(NSERIES):
        b.add_batch({"_metric_": "m", "host": f"h{s}", "grp": f"g{s % 4}"},
                    ts, values_of(s))
    shard.ingest(b.build())
    shard.flush()
    return ms, shard


def _query(ms, q="sum(rate(m[2m]))"):
    eng = QueryEngine(ms, "prometheus")
    r = eng.query_range(q, BASE + 200_000, BASE + (NSAMP - 1) * IV, 30_000)
    return {k: np.asarray(v) for k, _t, v in r.matrix.iter_series()}


def _wide_counter(rng):
    # integer increments past i8 (delta8 declines) over a span inside the
    # u16 range: the ladder lands on quant16 with scale 1
    return np.cumsum(rng.integers(0, 1000, NSAMP)).astype(np.float64)


def test_integer_counters_use_quant16_bit_exactly():
    rng, rng2 = np.random.default_rng(7), np.random.default_rng(7)
    ms_n, shard_n = _build(True, lambda s: _wide_counter(rng))
    ms_r, _ = _build(False, lambda s: _wide_counter(rng2))
    got_n = _query(ms_n)
    # the narrow form IS the store and every live row round-trips exactly
    assert shard_n.store.is_narrow_resident
    kind, _ops, ok = shard_n.store.narrow_operands()
    assert kind == "quant16"
    assert np.asarray(ok)[:NSERIES].all(), "integer counters must encode exactly"
    got_r = _query(ms_r)
    for k in got_r:
        np.testing.assert_array_equal(got_n[k], got_r[k])


def test_incompressible_floats_fall_back_to_raw():
    rng = np.random.default_rng(8)
    ms_n, shard_n = _build(True, lambda s: np.cumsum(rng.exponential(5.0, NSAMP)))
    got = _query(ms_n)
    (v,) = got.values()
    assert np.isfinite(v).all()
    # every encoding was tried once, found inexact, and the store kept raw
    # f32 (no narrow operands reach the leaf)
    st = shard_n.store
    assert not st.is_narrow_resident and st.narrow_operands() is None
    assert st.residency_decline == "non-integer"


def test_mixed_rows_correct_inexact_minority():
    def vals_of(rng):
        def vals(s):
            if s % 10 == 0:       # 10% of rows are incompressible
                return np.cumsum(rng.exponential(5.0, NSAMP))
            return _wide_counter(rng)
        return vals

    ms_n, shard_n = _build(True, vals_of(np.random.default_rng(9)))
    ms_r, _ = _build(False, vals_of(np.random.default_rng(9)))
    got_n = _query(ms_n, "sum by (grp) (rate(m[2m]))")
    got_r = _query(ms_r, "sum by (grp) (rate(m[2m]))")
    kind, _ops, ok = shard_n.store.narrow_operands()
    assert kind == "quant16"
    ok = np.asarray(ok)[:NSERIES]
    assert 0 < (~ok).sum() <= NSERIES // 8
    assert set(got_n) == set(got_r)
    for k in got_r:
        # inexact rows ride the general kernel: tolerance, not bit equality
        np.testing.assert_allclose(got_n[k], got_r[k], rtol=2e-4, atol=1e-4)
