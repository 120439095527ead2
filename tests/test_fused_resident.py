"""Bit-parity property grid for the fused compressed-resident kernel tier
(ISSUE 9, ops/fusedresident.py).

Every registry shape x every ``query.fused_kernels`` mode x every residency
form runs against the general-path oracle (mode=off on a raw-f32 store —
the composed grid-kernel + segment-reduce chain):

  * rate_sum / window_reduce over gauge f32 — the Pallas-interpret kernel
    and the XLA-fused scan twin share the tiling plan and tile math, so
    both are asserted EXACTLY equal to each other AND to the oracle.
  * hist_quantile over i8- and i16-resident 2D-delta blocks — integer
    bucket counts round-trip bit-exactly through the narrow encoding
    (PR 1 rules), so all three paths agree exactly.
  * counter-reset rows fail the narrow ok-contract, land in the cohort
    pool, and are folded back via the general kernels — a different f32
    summation order, so THAT cell of the grid documents the PR 1 rounding
    tolerance (allclose 1e-5) instead of exact equality; everything else
    is exact.
"""

import contextlib

import numpy as np
import pytest

from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import PROM_COUNTER, PROM_HISTOGRAM
from filodb_tpu.ops import fusedgrid, fusedresident
from filodb_tpu.query.engine import QueryEngine

START = 1_000_000
IV = 10_000
N = 96
B = 8
LES = np.concatenate([2.0 ** np.arange(B - 1), [np.inf]])

MODES = ("off", "xla", "pallas")


@contextlib.contextmanager
def fused_mode(m: str):
    old = fusedresident.mode()
    fusedresident.set_mode(m)
    try:
        yield
    finally:
        fusedresident.set_mode(old)


def _range(eng, q):
    start, end, step = START + 300_000, START + 800_000, 30_000
    return eng.query_range(q, start, end, step)


# ---------------------------------------------------------------- scalar ---

def _gauge_store(n_series=24):
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=32, samples_per_series=128,
                      flush_batch_size=10**9, dtype="float32")
    ms.setup("fusedres", PROM_COUNTER, 0, cfg)
    rng = np.random.default_rng(11)
    for s in range(n_series):
        b = RecordBuilder(PROM_COUNTER)
        vals = np.cumsum(rng.exponential(5.0, N))
        for t in range(N):
            b.add({"_metric_": "rt", "job": f"J{s % 3}", "inst": f"i{s}"},
                  START + t * IV, float(vals[t]))
        ms.ingest("fusedres", 0, b.build())
    ms.flush_all()
    return ms


SCALAR_QUERIES = (
    # rate_sum: rate/increase/delta into every partial-state op family
    "sum(rate(rt[2m]))",
    "avg(increase(rt[2m]))",
    "sum by(job) (rate(rt[2m]))",
    "stddev(delta(rt[2m]))",
    # window_reduce: *_over_time into reduce — the new fused shape
    "sum(avg_over_time(rt[2m]))",
    "sum by(job) (sum_over_time(rt[2m]))",
    "count(count_over_time(rt[2m]))",
)


def test_scalar_grid_all_modes_exact_vs_oracle():
    ms = _gauge_store()
    eng = QueryEngine(ms, "fusedres")
    for q in SCALAR_QUERIES:
        res = {}
        for m in MODES:
            with fused_mode(m):
                r = _range(eng, q)
            res[m] = np.asarray(r.matrix.values)
            if m != "off":
                # the fused map phase actually served (per-query stats)
                assert r.stats.fused_kernels >= 1, (q, m)
        # the backends equal each other exactly: same tile math, same fold
        # contraction — parity by construction. The composed-path oracle
        # sums the same exact products in another order (its contractions
        # are the default's six passes, the fused tier's its own three):
        # a few f32 ulps of a sum (a stddev's cancellation makes them some
        # 1e-6 of the answer; the deployments state 2e-4)
        np.testing.assert_array_equal(res["xla"], res["pallas"], err_msg=q)
        np.testing.assert_allclose(res["pallas"], res["off"], rtol=1e-5,
                                   atol=0, err_msg=q)


def test_scalar_off_mode_disables_the_fused_tier():
    ms = _gauge_store(n_series=8)
    eng = QueryEngine(ms, "fusedres")
    with fused_mode("off"):
        r = _range(eng, "sum(rate(rt[2m]))")
    assert r.stats.fused_kernels == 0
    assert r.matrix.num_series == 1


# ------------------------------------------------------------------ hist ---

def _hist_store(residency: str, bursty=False, reset=False, n_series=10):
    """Integer cumulative bucket counts: quiet rows fit the i8 tier,
    ``bursty`` escapes to i16, ``reset`` rows violate monotonicity and
    must take the cohort pool (general-path recompute)."""
    ms = TimeSeriesMemStore()
    sh = ms.setup("fusedhist", PROM_HISTOGRAM, 0,
                  StoreConfig(max_series_per_shard=16, samples_per_series=128,
                              flush_batch_size=10**9, dtype="float32",
                              compressed_residency=residency))
    rng = np.random.default_rng(17)
    for s in range(n_series):
        b = RecordBuilder(PROM_HISTOGRAM, bucket_les=LES)
        lam = 200.0 if bursty else 0.4
        c = np.cumsum(np.cumsum(rng.poisson(lam, (N, B)), axis=0),
                      axis=1).astype(np.float64)
        if bursty:
            c += np.cumsum((np.arange(N) % 2) * 300, dtype=np.int64)[:, None]
        if reset and s % 4 == 0:
            c[N // 2:] -= c[N // 2][None, :]
        for t in range(N):
            b.add({"_metric_": "h", "host": f"x{s}"}, START + t * IV, c[t])
        ms.ingest("fusedhist", 0, b.build())
    sh.flush()
    return ms, sh


HIST_QUERIES = (
    "histogram_quantile(0.9, sum(rate(h[2m])))",
    "histogram_quantile(0.5, sum(increase(h[2m])))",
    "histogram_quantile(0.9, sum by(host) (rate(h[2m])))",
)


@pytest.mark.parametrize("tier,bursty", [("int8", False), ("int16", True)])
def test_hist_grid_all_modes_exact_vs_oracle(tier, bursty):
    ms_raw, _ = _hist_store("off", bursty=bursty)
    ms_nar, sh = _hist_store("all", bursty=bursty)
    assert str(sh.store._nhist[0].dtype) == tier   # the residency under test
    oracle_eng = QueryEngine(ms_raw, "fusedhist")
    eng = QueryEngine(ms_nar, "fusedhist")
    for q in HIST_QUERIES:
        with fused_mode("off"):
            oracle = _range(oracle_eng, q)
            off = _range(eng, q)
            assert off.exec_path == "local"       # composed chain, by config
        np.testing.assert_array_equal(np.asarray(off.matrix.values),
                                      np.asarray(oracle.matrix.values),
                                      err_msg=q)
        res = {}
        for m in ("xla", "pallas"):
            with fused_mode(m):
                r = _range(eng, q)
            assert r.exec_path == f"fused-hist-narrow[{fusedgrid.kernel_tag(m)}]", (q, r.exec_path)
            assert r.stats.fused_kernels >= 1
            res[m] = np.asarray(r.matrix.values)
        np.testing.assert_array_equal(res["xla"], res["pallas"], err_msg=q)
        # integer bucket counts: the narrow encoding round-trips bit-exactly
        # (PR 1 rules), and the fused fold matches the composed contraction
        np.testing.assert_array_equal(res["pallas"],
                                      np.asarray(oracle.matrix.values),
                                      err_msg=q)


def test_hist_counter_reset_rows_fold_through_the_pool():
    """Rows violating the monotonicity contract are excluded from the fused
    stream and recomputed via the general kernels (cohort-pool correction):
    results match the oracle within the PR 1 tolerance — the pool rows'
    partials sum in a different f32 order, the ONE documented non-exact
    cell of this grid."""
    ms_raw, _ = _hist_store("off", reset=True, n_series=8)
    ms_nar, sh = _hist_store("all", reset=True, n_series=8)
    _dd, _fd, ok = sh.store.hist_operands()
    assert (~ok[:8:4]).all(), "reset rows must be pooled"
    oracle_eng = QueryEngine(ms_raw, "fusedhist")
    eng = QueryEngine(ms_nar, "fusedhist")
    for q in HIST_QUERIES[:2]:
        with fused_mode("off"):
            want = np.asarray(_range(oracle_eng, q).matrix.values)
        for m in ("xla", "pallas"):
            with fused_mode(m):
                r = _range(eng, q)
            assert r.exec_path == f"fused-hist-narrow[{fusedgrid.kernel_tag(m)}]"
            np.testing.assert_allclose(np.asarray(r.matrix.values), want,
                                       rtol=1e-5, atol=1e-6, equal_nan=True,
                                       err_msg=(q, m))


def test_mode_validation_and_registry_surface():
    with pytest.raises(ValueError):
        fusedresident.set_mode("vulkan")
    assert set(fusedresident.FUSED_SHAPES) == {"rate_sum", "window_reduce",
                                               "hist_quantile"}
    for fns, ops in fusedresident.FUSED_SHAPES.values():
        assert fns and ops
    assert fusedresident.scalar_shape_of("rate") == "rate_sum"
    assert fusedresident.scalar_shape_of("avg_over_time") == "window_reduce"
    assert fusedresident.scalar_shape_of("last_sample") is None
