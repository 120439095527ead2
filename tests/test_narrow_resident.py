"""Narrow-resident store: the i16 quantized form as the ONLY resident value
copy (ref: the reference's read path keeps values only compressed —
memory/.../format/vectors/DoubleVector.scala:1-60, doc/compression.md — and
write buffers raw: TimeSeriesPartition write buffers -> frozen chunks)."""

import jax.numpy as jnp
import numpy as np
import pytest

from filodb_tpu.core.chunkstore import DeferredDecode
from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE
from filodb_tpu.query.engine import QueryEngine

START = 1_000_000
INTERVAL = 10_000
N = 96


def _cfg(**kw):
    return StoreConfig(max_series_per_shard=32, samples_per_series=128,
                       flush_batch_size=10**9, dtype="float32", **kw)


def _build(narrow_resident: bool, mixed: bool = False, n_series: int = 12):
    """Integer-valued counters (quantize exactly); ``mixed`` adds continuous
    rows that must take the raw-f32 cohort pool."""
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", GAUGE, 0, _cfg(narrow_resident=narrow_resident))
    rng = np.random.default_rng(9)
    for i in range(n_series):
        b = RecordBuilder(GAUGE)
        if mixed and i % 4 == 3:
            vals = np.cumsum(rng.exponential(5.0, N))        # continuous
        else:
            vals = np.cumsum(rng.integers(1, 50, N)).astype(np.float64)
        for t in range(N):
            b.add({"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 3}"},
                  START + t * INTERVAL, float(vals[t]))
        ms.ingest("prometheus", 0, b.build())
    sh.flush()
    return ms, sh


def test_compress_resident_frees_f32_and_halves_bytes():
    ms, sh = _build(True)
    st = sh.store
    assert st.is_narrow_resident
    assert st.val is None or isinstance(st.column_array(), DeferredDecode)
    raw_bytes = st.S * st.C * 4
    assert st.resident_value_bytes() < 0.6 * raw_bytes   # i16 + tiny pool
    # grid-contiguous: the 8B/sample timestamp block is elided too — total
    # resident sample state lands near 2B/sample (>= 2x retention per byte,
    # vs 12B/sample raw; the bar is 2x, this is ~5x)
    assert st.ts is None
    raw_sample_bytes = st.S * st.C * 12
    assert st.resident_sample_bytes() < 0.25 * raw_sample_bytes
    # the f32 view decodes bit-exactly, the ts view derives bit-exactly
    dec = np.asarray(st.value_block())
    tss = np.asarray(st.ts_block())
    ms2, sh2 = _build(False)
    ref = np.asarray(sh2.store.val)
    np.testing.assert_array_equal(dec[:12, :N], ref[:12, :N])
    np.testing.assert_array_equal(tss[:12, :N], np.asarray(sh2.store.ts)[:12, :N])


def test_fused_path_never_materializes():
    """The flagship query on a compressed-resident store streams the i16
    state — no transient f32 decode, no ts derivation."""
    ms, sh = _build(True)
    st = sh.store
    calls = {"v": 0, "t": 0}
    orig_v, orig_t = st.value_block, st.ts_block
    st.value_block = lambda: calls.__setitem__("v", calls["v"] + 1) or orig_v()
    st.ts_block = lambda: calls.__setitem__("t", calls["t"] + 1) or orig_t()
    eng = QueryEngine(ms, "prometheus")
    r = eng.query_range("sum(rate(m[2m]))", START + 300_000, START + 800_000,
                        30_000)
    assert r.matrix.num_series == 1
    assert calls == {"v": 0, "t": 0}, calls
    st.value_block, st.ts_block = orig_v, orig_t


def test_mixed_rows_take_the_pool_bit_exact():
    ms, sh = _build(True, mixed=True)
    st = sh.store
    assert st.is_narrow_resident
    _kind, _ops, ok = st.narrow_operands()
    assert (~ok[:12]).sum() >= 3          # the continuous rows are in the pool
    dec = np.asarray(st.value_block())
    ms2, sh2 = _build(False, mixed=True)
    np.testing.assert_array_equal(dec[:12, :N], np.asarray(sh2.store.val)[:12, :N])


@pytest.mark.parametrize("mixed", [False, True])
def test_query_parity_narrow_resident_vs_f32(mixed):
    """Every query route answers identically whether the store is f32- or
    narrow-resident: fused aggregates stream the i16 state, minority/pool
    rows recompute exactly, general paths decode a transient."""
    ms_a, _ = _build(False, mixed)
    ms_b, sh_b = _build(True, mixed)
    assert sh_b.store.is_narrow_resident
    ea = QueryEngine(ms_a, "prometheus")
    eb = QueryEngine(ms_b, "prometheus")
    start, end, step = START + 300_000, START + 800_000, 30_000
    for q in ("sum(rate(m[2m]))", "sum by (grp) (rate(m[2m]))",
              "max(m)", "avg_over_time(m[2m])", "topk(3, m)",
              'sum(rate(m{grp="g1"}[2m]))', "quantile(0.5, m)",
              "stddev(rate(m[2m]))"):
        ra = {k: (t.tolist(), v)
              for k, t, v in ea.query_range(q, start, end, step).matrix.iter_series()}
        rb = {k: (t.tolist(), v)
              for k, t, v in eb.query_range(q, start, end, step).matrix.iter_series()}
        assert set(ra) == set(rb), f"{q}: different series"
        for k in ra:
            assert ra[k][0] == rb[k][0], f"{q}: {k} timestamps diverge"
            if mixed:
                # pool rows recompute through the general kernels (different
                # f32 summation order than the one-pass fused kernel) — the
                # DATA is bit-exact (asserted above), the aggregate rounds
                np.testing.assert_allclose(ra[k][1], rb[k][1], rtol=1e-5,
                                           atol=1e-6)
            else:
                np.testing.assert_array_equal(ra[k][1], rb[k][1])
    # still narrow-resident after the read-only queries
    assert sh_b.store.is_narrow_resident


def test_append_rehydrates_and_recompresses():
    ms, sh = _build(True)
    st = sh.store
    assert st.is_narrow_resident
    b = RecordBuilder(GAUGE)
    for t in range(N, N + 8):
        b.add({"_metric_": "m", "host": "h0", "grp": "g0"},
              START + t * INTERVAL, float(1000 + t))
    ms.ingest("prometheus", 0, b.build())
    sh.flush()
    assert st.is_narrow_resident           # re-compressed at flush
    eng = QueryEngine(ms, "prometheus")
    r = eng.query_instant('m{host="h0"}', START + (N + 7) * INTERVAL)
    assert float(np.asarray(r.matrix.values)[0, -1]) == 1000.0 + N + 7


def test_continuous_data_declines_compression():
    """Mostly non-quantizable rows: raw f32 stays resident (the encoder's
    25% pool gate), and queries behave as before."""
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", GAUGE, 0, _cfg(narrow_resident=True))
    rng = np.random.default_rng(2)
    for i in range(8):
        b = RecordBuilder(GAUGE)
        vals = np.cumsum(rng.exponential(5.0, N))
        for t in range(N):
            b.add({"_metric_": "m", "host": f"h{i}"}, START + t * INTERVAL,
                  float(vals[t]))
        ms.ingest("prometheus", 0, b.build())
    sh.flush()
    assert not sh.store.is_narrow_resident
    assert sh.store.val is not None


def test_narrow_resident_compact_and_odp(tmp_path):
    """Compaction shifts the delta form in place; ODP reads decode once per
    batch."""
    from filodb_tpu.core.store import FileColumnStore
    ms = TimeSeriesMemStore()
    sink = FileColumnStore(str(tmp_path))
    cfg = StoreConfig(max_series_per_shard=16, samples_per_series=64,
                      flush_batch_size=10**9, groups_per_shard=1,
                      dtype="float32", narrow_resident=True)
    sh = ms.setup("prometheus", GAUGE, 0, cfg, sink=sink)
    for i in range(4):
        b = RecordBuilder(GAUGE)
        for t in range(40):
            b.add({"_metric_": "m", "host": f"h{i}"}, START + t * INTERVAL,
                  float(t))
        ms.ingest("prometheus", 0, b.build())
    sh.flush_all_groups()
    assert sh.store.is_narrow_resident
    sh.store.compact(START + 20 * INTERVAL)
    # the delta form ages out as it is: anchors move on, nothing is decoded
    assert sh.store.is_narrow_resident and sh.store.rehydrates == 0
    sh.flush()          # nothing staged — and nothing to re-compress
    assert sh.store.is_narrow_resident
    pids = sh.part_ids_from_filters([], START, START + 40 * INTERVAL)
    assert sh.needs_paging(pids, START)
    ts_a, val_a, n_a = sh.read_with_paging(pids, START, START + 40 * INTERVAL)
    assert (n_a == 40).all()
    for i in range(len(pids)):
        np.testing.assert_allclose(val_a[i, :40], np.arange(40.0))


def test_two_phase_compress_aborts_on_racing_mutation():
    """A mutation landing between the (unlocked) build and the swap must
    abort the commit — the stale compressed state would drop the race's
    samples. The next flush re-attempts on the new epoch."""
    # a store born RAW that residency is then asked of: the rebuild is the
    # only way it becomes narrow (a store born narrow never rebuilds)
    import dataclasses
    ms, sh = _build(False)
    sh.config = dataclasses.replace(sh.config, narrow_resident=True)
    st = sh.store
    assert not st.is_narrow_resident
    # an append, then race the compression
    b = RecordBuilder(GAUGE)
    b.add({"_metric_": "m", "host": "h0", "grp": "g0"},
          START + (N + 1) * INTERVAL, 7.0)
    ms.ingest("prometheus", 0, b.build())
    orig_prepare = st.compress_prepare

    def racing_prepare(hist=True):
        prep = orig_prepare(hist=hist)
        # a concurrent append mutates AFTER the build snapshot
        rb = RecordBuilder(GAUGE)
        rb.add({"_metric_": "m", "host": "h0", "grp": "g0"},
               START + (N + 2) * INTERVAL, 9.0)
        ms.ingest("prometheus", 0, rb.build())
        with sh.lock:
            sh._flush_staged_locked()
        return prep

    st.compress_prepare = racing_prepare
    sh.flush()
    st.compress_prepare = orig_prepare
    assert not st.is_narrow_resident, "stale build must not commit"
    # the racing sample survived and the next quiet flush re-compresses
    sh.flush()
    assert st.is_narrow_resident
    eng = QueryEngine(ms, "prometheus")
    r = eng.query_instant('m{host="h0"}', START + (N + 2) * INTERVAL)
    assert float(np.asarray(r.matrix.values)[0, -1]) == 9.0


def test_gather_rows_matches_full_materialization():
    """Row-wise decode/derivation (minority fixes) must agree bit-for-bit
    with the full block materialization."""
    import jax.numpy as jnp

    from filodb_tpu.core.chunkstore import DeferredTs

    ms, sh = _build(True, mixed=True)
    st = sh.store
    assert st.is_narrow_resident
    rid = jnp.asarray(np.array([0, 3, 7, 11], np.int32))
    dv = st.column_array()
    assert isinstance(dv, DeferredDecode)
    rows = np.asarray(dv.gather_rows(rid))
    full = np.asarray(st.value_block())
    np.testing.assert_array_equal(rows, full[np.asarray(rid)])
    dt = DeferredTs(st)
    trows = np.asarray(dt.gather_rows(rid))
    tfull = np.asarray(st.ts_block())
    np.testing.assert_array_equal(trows, tfull[np.asarray(rid)])
