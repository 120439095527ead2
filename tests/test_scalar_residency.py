"""Scalar compressed residency v2 (ISSUE 17): kind-tagged narrow stores.

The flush encoder now picks the NARROWEST scalar decode variant that
round-trips bit-exactly — delta8 (1B/sample) over quant16 (2B) over delta16
(2B, survives spans past the u16 range) — and every consumer (fused kernels
in both backends, row-wise decodes, the mesh narrow stream, warmup) carries
the kind through the shared registry (ops/decodereg.py). Stores that refuse
every variant tick ``filodb_store_residency_fallback`` with the dominant
decline reason."""

import numpy as np
import pytest

from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE, PROM_HISTOGRAM
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.utils.metrics import FILODB_STORE_RESIDENCY_FALLBACK, registry

START = 1_000_000
INTERVAL = 10_000
N = 96


def _cfg(**kw):
    kw.setdefault("max_series_per_shard", 32)
    kw.setdefault("samples_per_series", 128)
    return StoreConfig(flush_batch_size=10**9, dtype="float32", **kw)


def _rows(kind: str, n_series: int = 12, seed: int = 9):
    """Per-series value rows that the encoder must land on ``kind``."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_series):
        if kind == "delta8":               # counter: small integer increments
            vals = np.cumsum(rng.integers(1, 50, N)).astype(np.float64)
        elif kind == "delta16":            # odd increments, span >> u16 range
            vals = np.cumsum(rng.integers(100, 3000, N) * 2 + 1) \
                .astype(np.float64)
        elif kind == "quant16":            # half-integer steps: deltas are
            vals = 1000.0 + 0.5 * np.arange(N)   # non-integer, pow2 scale
        elif kind == "raw":                # continuous: declines everything
            vals = np.cumsum(rng.exponential(5.0, N))
        elif kind == "range":              # integral but past every width
            vals = np.cumsum(rng.integers(10**6, 11 * 10**5, N) * 2 + 1) \
                .astype(np.float64)
        else:
            raise AssertionError(kind)
        out.append(vals)
    return out


def _store(kind: str, n_series: int = 12, **cfg_kw):
    ms = TimeSeriesMemStore()
    sh = ms.setup("scalres", GAUGE, 0, _cfg(narrow_resident=True, **cfg_kw))
    for i, vals in enumerate(_rows(kind, n_series)):
        b = RecordBuilder(GAUGE)
        for t in range(N):
            b.add({"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 3}"},
                  START + t * INTERVAL, float(vals[t]))
        ms.ingest("scalres", 0, b.build())
    sh.flush()
    return ms, sh


# -- preference ladder --------------------------------------------------------

@pytest.mark.parametrize("kind,bytes_per_sample", [
    ("delta8", 1), ("delta16", 2), ("quant16", 2)])
def test_encoder_lands_on_the_narrowest_variant(kind, bytes_per_sample):
    ms, sh = _store(kind)
    st = sh.store
    assert st.is_narrow_resident
    got_kind, ops, ok = st.narrow_operands()
    assert got_kind == kind
    assert np.asarray(ok)[:12].all()
    assert ops[0].dtype == (np.int8 if bytes_per_sample == 1 else np.int16)
    # the decoded view is bit-equal to a raw store over the same ingest
    ms2 = TimeSeriesMemStore()
    sh2 = ms2.setup("scalraw", GAUGE, 0, _cfg())
    for i, vals in enumerate(_rows(kind)):
        b = RecordBuilder(GAUGE)
        for t in range(N):
            b.add({"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 3}"},
                  START + t * INTERVAL, float(vals[t]))
        ms2.ingest("scalraw", 0, b.build())
    sh2.flush()
    np.testing.assert_array_equal(
        np.asarray(st.value_block())[:12, :N],
        np.asarray(sh2.store.val)[:12, :N])


def test_delta8_retention_beats_raw_by_3x():
    """ISSUE 17 acceptance floor: counter-shaped data at 1B/sample with the
    ts block elided holds >= 3x the samples of raw f32+i64 in the same HBM."""
    ms, sh = _store("delta8")
    st = sh.store
    raw_sample_bytes = st.S * st.C * 12            # f32 value + i64 ts
    assert st.resident_sample_bytes() * 3 <= raw_sample_bytes


def test_query_parity_every_kind_vs_raw_oracle():
    """Every route (fused both backends, general, instant) answers a
    kind-tagged store bit-identically to the raw store."""
    from filodb_tpu.ops import fusedresident
    start, end, step = START + 300_000, START + 800_000, 30_000
    for kind in ("delta8", "delta16", "quant16"):
        ms_n, sh_n = _store(kind)
        assert sh_n.store.narrow_operands()[0] == kind
        ms_r = TimeSeriesMemStore()
        sh_r = ms_r.setup("scalraw2", GAUGE, 0, _cfg())
        for i, vals in enumerate(_rows(kind)):
            b = RecordBuilder(GAUGE)
            for t in range(N):
                b.add({"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 3}"},
                      START + t * INTERVAL, float(vals[t]))
            ms_r.ingest("scalraw2", 0, b.build())
        sh_r.flush()
        en = QueryEngine(ms_n, "scalres")
        er = QueryEngine(ms_r, "scalraw2")
        old = fusedresident.mode()
        try:
            for mode in ("pallas", "xla"):
                fusedresident.set_mode(mode)
                for q in ("sum(rate(m[2m]))", "sum by (grp) (rate(m[2m]))",
                          "max(m)", "stddev(rate(m[2m]))",
                          "avg_over_time(m[2m])"):
                    rn = en.query_range(q, start, end, step)
                    rr = er.query_range(q, start, end, step)
                    np.testing.assert_array_equal(
                        np.asarray(rn.matrix.values),
                        np.asarray(rr.matrix.values), err_msg=(kind, mode, q))
                    if "rate(" in q and q != "rate(m[2m])":
                        # aggregated windowed shapes serve through the
                        # fused tier; instant selectors and per-series
                        # range functions take the general kernels
                        assert rn.stats.fused_kernels >= 1, (kind, mode, q)
        finally:
            fusedresident.set_mode(old)


# -- residency-fallback metric (satellite) ------------------------------------

def _fallback_count(reason: str) -> float:
    return registry.counter(FILODB_STORE_RESIDENCY_FALLBACK,
                            {"reason": reason}).value


def test_fallback_metric_reason_non_integer():
    before = _fallback_count("non-integer")
    ms, sh = _store("raw", n_series=8)
    assert not sh.store.is_narrow_resident
    assert _fallback_count("non-integer") == before + 1
    # idempotent per compress epoch: a quiet re-flush must not re-count
    sh.flush()
    assert _fallback_count("non-integer") == before + 1


def test_fallback_metric_reason_range():
    before = _fallback_count("range")
    ms, sh = _store("range", n_series=8)
    assert not sh.store.is_narrow_resident
    assert _fallback_count("range") == before + 1


def test_fallback_metric_reason_resets():
    before = _fallback_count("resets")
    ms = TimeSeriesMemStore()
    B = 8
    les = np.concatenate([2.0 ** np.arange(B - 1), [np.inf]])
    sh = ms.setup("histres", PROM_HISTOGRAM, 0,
                  _cfg(compressed_residency="all"))
    rng = np.random.default_rng(11)
    for i in range(8):
        b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
        # counts that DROP over time: the monotonicity leg of the hist
        # ok-contract fails -> decline, reason "resets"
        c = np.cumsum(np.cumsum(rng.poisson(2.0, (N, B)), axis=0), axis=1)
        c = c[::-1].astype(np.float64)
        for t in range(N):
            b.add({"_metric_": "h", "host": f"x{i}"},
                  START + t * INTERVAL, c[t])
        ms.ingest("histres", 0, b.build())
    sh.flush()
    assert not sh.store.is_narrow_resident
    assert _fallback_count("resets") == before + 1


def test_compressing_store_does_not_tick_fallback():
    reasons = ("resets", "non-integer", "range")
    before = sum(_fallback_count(r) for r in reasons)
    ms, sh = _store("delta8")
    assert sh.store.is_narrow_resident
    assert sum(_fallback_count(r) for r in reasons) == before


# -- cohort gate config -------------------------------------------------------

def test_narrow_cohort_gate_is_config_driven():
    # 5 of 12 rows continuous: past the default 0.25 gate (declines), but a
    # 0.5 gate pools them and keeps the store narrow-resident
    def fill(ms, name):
        for i in range(12):
            b = RecordBuilder(GAUGE)
            if i % 3 != 0:
                vals = np.cumsum(
                    np.random.default_rng(i).integers(1, 50, N))
            else:
                vals = np.cumsum(
                    np.random.default_rng(i).exponential(5.0, N))
            for t in range(N):
                b.add({"_metric_": "m", "host": f"h{i}"},
                      START + t * INTERVAL, float(vals[t]))
            ms.ingest(name, 0, b.build())

    ms_a = TimeSeriesMemStore()
    sh_a = ms_a.setup("gate25", GAUGE, 0, _cfg(narrow_resident=True))
    fill(ms_a, "gate25")
    sh_a.flush()
    assert not sh_a.store.is_narrow_resident

    ms_b = TimeSeriesMemStore()
    sh_b = ms_b.setup("gate50", GAUGE, 0,
                      _cfg(narrow_resident=True, narrow_cohort_gate=0.5))
    fill(ms_b, "gate50")
    sh_b.flush()
    assert sh_b.store.is_narrow_resident
    _kind, _ops, ok = sh_b.store.narrow_operands()
    assert 1 <= (~np.asarray(ok)[:12]).sum() <= 6


def test_cohort_gate_validated():
    with pytest.raises(ValueError):
        _cfg(narrow_cohort_gate=1.5)


# -- mixed residency through the engine (satellite) ---------------------------

def _mixed_fill(ms, name, nshards):
    """Shard 0 gets clean counters (adopts delta8 when narrow), shard 1 gets
    a blend with continuous rows (pool rows when narrow)."""
    rng = np.random.default_rng(4)
    for i in range(16):
        b = RecordBuilder(GAUGE)
        if i % nshards == 1 and i % 4 == 1:
            vals = np.cumsum(rng.exponential(5.0, N))
        else:
            vals = np.cumsum(rng.integers(1, 50, N)).astype(np.float64)
        for t in range(N):
            b.add({"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 3}"},
                  START + t * INTERVAL, float(vals[t]))
        ms.ingest(name, i % nshards, b.build())
    ms.flush_all()


def test_mixed_residency_shards_query_at_parity():
    """Narrow shard + raw shard + cohort-pool rows in ONE selection: fused,
    composed and general routes all match the all-raw oracle (pool rows
    recompute through the general kernels — allclose there, bit-equal on
    the pool-free queries)."""
    NSHARDS = 2
    ms_m = TimeSeriesMemStore()
    ms_m.setup("mixed", GAUGE, 0, _cfg(narrow_resident=True))
    ms_m.setup("mixed", GAUGE, 1, _cfg())        # raw shard
    _mixed_fill(ms_m, "mixed", NSHARDS)
    shards = list(ms_m.shards_of("mixed"))
    assert shards[0].store.is_narrow_resident
    assert shards[0].store.narrow_operands()[0] == "delta8"
    assert not shards[1].store.is_narrow_resident

    ms_o = TimeSeriesMemStore()
    for s in range(NSHARDS):
        ms_o.setup("mixedraw", GAUGE, s, _cfg())
    _mixed_fill(ms_o, "mixedraw", NSHARDS)

    em = QueryEngine(ms_m, "mixed")
    eo = QueryEngine(ms_o, "mixedraw")
    start, end, step = START + 300_000, START + 800_000, 30_000
    for q in ("sum(rate(m[2m]))", "sum by (grp) (rate(m[2m]))",
              "max(m)", "avg_over_time(m[2m])", "topk(3, m)",
              "quantile(0.5, m)", "stddev(rate(m[2m]))"):
        rm = {k: (t.tolist(), v) for k, t, v in
              em.query_range(q, start, end, step).matrix.iter_series()}
        ro = {k: (t.tolist(), v) for k, t, v in
              eo.query_range(q, start, end, step).matrix.iter_series()}
        assert set(rm) == set(ro), q
        for k in rm:
            assert rm[k][0] == ro[k][0], (q, k)
            np.testing.assert_array_equal(rm[k][1], ro[k][1],
                                          err_msg=f"{q}: {k}")


def test_mixed_residency_mesh_serves_with_parity():
    """A mesh fleet where one shard is narrow and another raw (or where
    kinds differ) cannot stream one narrow program — narrow_arrays() must
    return None and the fused route streams transient f32 decodes, still
    bit-equal to a no-mesh oracle."""
    from filodb_tpu.parallel.distributed import make_mesh

    mesh = make_mesh()
    ndev = mesh.devices.size
    if ndev < 2:
        pytest.skip("needs >= 2 devices")

    def build(device_mesh, narrow_shards):
        ms = TimeSeriesMemStore()
        devs = (list(device_mesh.devices.ravel())
                if device_mesh is not None else [None] * ndev)
        for s in range(ndev):
            ms.setup("mixmesh", GAUGE, s,
                     _cfg(max_series_per_shard=16, samples_per_series=N,
                          narrow_resident=(s in narrow_shards)),
                     device=devs[s])
        rng = np.random.default_rng(6)
        for i in range(2 * ndev):
            b = RecordBuilder(GAUGE)
            vals = np.cumsum(rng.integers(1, 50, N)).astype(np.float64)
            for t in range(N):
                b.add({"_metric_": "m", "host": f"h{i}", "grp": f"g{i % 3}"},
                      START + t * INTERVAL, float(vals[t]))
            ms.ingest("mixmesh", i % ndev, b.build())
        ms.flush_all()
        return ms

    half = set(range(ndev // 2))
    ms_mesh = build(mesh, half)
    ms_host = build(None, set())
    em = QueryEngine(ms_mesh, "mixmesh", mesh=mesh)
    eo = QueryEngine(ms_host, "mixmesh")
    start, end, step = START + 300_000, START + 800_000, 30_000
    for q in ("sum(rate(m[2m]))", "sum by (grp) (rate(m[2m]))"):
        rm = em.query_range(q, start, end, step)
        assert rm.exec_path == "mesh[pjit]-fused", rm.exec_path
        np.testing.assert_array_equal(
            np.asarray(rm.matrix.values),
            np.asarray(eo.query_range(q, start, end, step).matrix.values),
            err_msg=q)


# -- warmup coverage ----------------------------------------------------------

def test_warmup_residency_field_pretraces_the_narrow_program():
    """A warmup spec naming ``residency`` covers the kind-tagged fused
    program: the first dashboard query on a delta8-resident store of the
    warmed shape compiles nothing."""
    from filodb_tpu.query.plancache import plan_cache, warmup
    from filodb_tpu.utils.tracing import SPAN_QUERY_COMPILE, tracer

    ms, sh = _store("delta8", n_series=32, max_series_per_shard=32,
                    samples_per_series=128)
    assert sh.store.narrow_operands()[0] == "delta8"
    eng = QueryEngine(ms, "scalres")
    plan_cache.clear()
    info = warmup([{"fn": "rate", "op": "sum", "series": 32, "samples": 128,
                    "steps": 18, "step_ms": 30_000, "window_ms": 120_000,
                    "interval_ms": INTERVAL, "residency": "delta8"}])
    assert info["programs"] > 0
    tracer.drain()
    t0 = plan_cache.traces
    r = eng.query_range("sum(rate(m[2m]))", START + 300_000, START + 810_000,
                        30_000)
    assert r.stats.fused_kernels >= 1
    assert plan_cache.traces == t0, \
        "warmed narrow residency shape must not compile at serve time"
    assert [s for s in tracer.snapshot()
            if s.name == SPAN_QUERY_COMPILE] == []
