"""The ``hist`` data module (``benchmark/data/hist/``): native histograms.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` (tier-1
collects these too: ``tests/test_benchmark_data.py``).

- golden parity: the generator's columns, the reference's answers, the
  probes and the byte count as the tree that added the module gave them
  (``golden_hist.json``): a later change to any of them is a change of the
  yardstick and shows here;
- the generator in numpy and in ``jax.numpy``: the same integers; every
  stored number below 2**24; no counter falls; growth 0..31 a scrape;
- the plain reference against hand values, and its range function tied to
  the repo's golden model (``tests/prom_reference.py``) bucket by bucket;
- ``fill`` against the same scrapes sent through the write path: the two
  stores are bit-equal, mirrors and bounds included;
- probes, byte count and the configuration and mix files against what
  ISSUE 29 names.
"""

import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import data, traffic  # noqa: E402

with open(os.path.join(HERE, "golden_hist.json")) as f:
    GOLD = json.load(f)
DEPLOY = {"metric": "h", "buckets": 64, "labels": {"groups": 8, "per_rack": 4},
          "scrape_interval_ms": 10000, "fill_columns": 720}
BASE = 1_700_000_000_000


@pytest.fixture(scope="module")
def hist():
    return data.load("hist")


# ---- golden ---------------------------------------------------------------

@pytest.mark.parametrize("seed", sorted(GOLD["values"], key=int))
def test_hist_columns_are_the_pinned_ones(hist, seed):
    sids, cols = GOLD["sids"], GOLD["cols"]
    want = {k: np.asarray(v, np.float64) for k, v in GOLD["values"][seed].items()}
    got = hist.raw_values(int(seed), sids, cols, DEPLOY)
    assert got.dtype == np.float64 and (got == want["h"]).all()
    assert hist.datagen.fold_seed(int(seed)) == GOLD["fold_seed"][seed]
    layout = hist.schema().col_layout(64)
    assert [(nm, off, w) for nm, off, w, _h in layout] == [
        ("sum", 0, 1), ("count", 1, 1), ("h", 2, 64)]
    for j, k in enumerate(cols):
        sc = hist.scrape(int(seed), np.asarray(sids), k, DEPLOY)
        assert sc["values"].shape == (len(sids), 66)
        assert (sc["values"][:, 0] == want["sum"][:, j]).all()
        assert (sc["values"][:, 1] == want["count"][:, j]).all()
        assert (sc["values"][:, 2:] == want["h"][:, j]).all()
        assert (sc["values"][:, 1] == sc["values"][:, -1]).all()   # count = top
        assert (sc["ts"] == BASE + k * 10000).all()
        assert hist.scrape_ms(k, DEPLOY) == sc["ts"][0]
        np.testing.assert_array_equal(sc["bucket_les"],
                                      hist.datagen.bucket_les(64))


@pytest.mark.parametrize("i", range(len(GOLD["evaluate"]["answers"])))
def test_hist_answers_are_the_pinned_ones(hist, i):
    ev = GOLD["evaluate"]
    a = ev["answers"][i]
    mix = traffic.load("adhoc_hist")
    assert mix["queries"][a["qi"]]["promql"] == a["promql"]
    ref = mix["queries"][a["qi"]]["ref"]
    out_ts = np.arange(a["start_ms"], a["end_ms"] + 1, a["step_ms"])
    sids = np.arange(ev["sid_lo"], ev["sid_lo"] + ev["sid_n"])
    got = hist.evaluate(ev["seed"], sids, ref, out_ts, DEPLOY, ev["head_col"])
    want = {tuple(map(tuple, k)): np.array(
        [np.nan if x is None else x for x in v]) for k, v in a["rows"]}
    assert set(got) == set(want) and len(want) == (8 if ref["by"] else 1)
    for k, v in want.items():
        np.testing.assert_allclose(got[k], v, rtol=1e-12)
        # a range that starts at cell 0 has no two samples in its first window
        assert np.isfinite(v[1:]).all() and np.nanmin(v) > 0.001 \
            and np.nanmax(v) < 81.2
    assert hist.query_bytes(32768, ref, out_ts, DEPLOY, ev["head_col"],
                            768) == a["query_bytes"]


@pytest.mark.parametrize("p", GOLD["probes"], ids=lambda p: str(p["seed"]))
def test_hist_probes_are_the_pinned_ones(hist, p):
    got = hist.probes(p["seed"], np.arange(p["lo"], p["hi"]), p["col"],
                      DEPLOY, 2)
    assert [g["promql"] for g in got] == p["promql"]
    assert got[0]["promql"].startswith("histogram_bucket(") \
        and '__col__="count"' in got[1]["promql"]
    for g, want in zip(got, p["want"]):
        assert [lb for lb, _ in g["want"]] == [lb for lb, _ in want]
        assert len(g["want"]) == 4                  # a rack's four hosts
        assert (np.stack([v for _, v in g["want"]])
                == np.asarray([v for _, v in want])).all()
        assert (g["start_ms"], g["end_ms"], g["step_ms"]) == (
            BASE + (p["col"] - 3) * 10000, BASE + p["col"] * 10000, 10000)
    # the bucket probe reads the very bucket its bound names
    les = hist.datagen.bucket_les(64)
    le = float(got[0]["promql"].split("(")[1].split(",")[0])
    b = int(np.argmin(np.abs(les - le)))
    assert les[b] == le and 8 <= b < 56
    rack = int(got[0]["promql"].split('"r')[1].split('"')[0])
    h = hist.raw_values(p["seed"], np.arange(rack * 4, rack * 4 + 4),
                        np.arange(p["col"] - 3, p["col"] + 1), DEPLOY)
    assert (np.stack([v for _, v in got[0]["want"]]) == h[:, :, b]).all()


# ---- the generator ----------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**33 + 1])
def test_numpy_and_jax_numpy_give_the_same_integers(hist, seed):
    import filodb_tpu  # noqa: F401 — turns x64 on, as the server does
    import jax
    import jax.numpy as jnp
    dg = hist.datagen
    s, k = np.arange(1000, 1384), np.arange(0, 768, 5)
    host = dg.columns_np(seed, s, k, 64, np.int64)
    dev = jax.jit(lambda s, k, w: dg.columns(jnp, w, s[:, None], k[None, :],
                                             64))(
        jnp.asarray(s, jnp.uint32), jnp.asarray(k, jnp.uint32),
        jnp.uint32(dg.fold_seed(seed)))
    for d, h in zip(dev, host):
        assert (np.asarray(d).astype(np.int64) == h).all()


def test_counters_never_fall_and_fit_f32(hist):
    sids = np.arange(0, 4096, 7)
    su, cn, h = hist.datagen.columns_np(2**31 + 5, sids, np.arange(768), 64,
                                        np.int64)
    per_bucket = np.diff(h, axis=2, prepend=0)
    grow = np.diff(per_bucket, axis=1)
    assert grow.min() >= 0 and grow.max() <= 31
    assert (per_bucket >= 0).all() and (np.diff(su, axis=1) >= 0).all()
    assert max(h.max(), su.max()) < 2**24 and (cn == h[:, :, -1]).all()
    assert (h.astype(np.float32).astype(np.int64) == h).all()
    # groups differ in shape, and a group's quantile moves as it gets busy
    les = hist.datagen.bucket_les(64)
    assert len(les) == 64 and np.isinf(les[-1]) and (np.diff(les[:-1]) > 0).all()
    p90 = {}
    for k0 in (100, 148):
        d = h[:, k0 + 30] - h[:, k0]
        for g in (0, 7):
            tot = d[sids % 8 == g].sum(axis=0)
            p90[k0, g] = int(np.searchsorted(tot, 0.9 * tot[-1]))
    assert p90[100, 7] - p90[100, 0] >= 20 and p90[100, 0] != p90[148, 0]


# ---- the plain reference ----------------------------------------------------

def test_quantile_against_hand_values(hist):
    q = hist.reference.quantile
    les = np.array([1.0, 2.0, 4.0, np.inf])
    c = np.array([10.0, 30.0, 40.0, 40.0])
    assert q(0.5, les, c) == pytest.approx(1.5)        # rank 20 in (1, 2]
    assert q(0.99, les, c) == pytest.approx(3.92)      # rank 39.6 in (2, 4]
    assert q(0.1, les, c) == pytest.approx(0.4)        # rank 4 in (0, 1]
    assert q(0.9, les, np.array([1.0, 2.0, 3.0, 40.0])) == 4.0   # in +Inf
    assert np.isnan(q(0.5, les, np.zeros(4)))
    assert q(1.5, les, c) == np.inf and q(-0.5, les, c) == -np.inf


def test_bucket_rates_against_hand_values(hist):
    r = hist.reference
    # one series, two buckets: 1 and 3 a second, samples every 10 s from 0
    k = np.arange(12)
    v = np.stack([10.0 * k + 100, 30.0 * k + 300], axis=1)[None]    # [1, 12, 2]
    n = np.array([12])
    on_grid = np.array([BASE + 100_000])            # window cells 5..10
    got = r.bucket_rates("rate", v, k, n, on_grid, 50_000, 10_000)
    np.testing.assert_allclose(got[0, 0], [1.0, 3.0], rtol=1e-15)
    got = r.bucket_rates("increase", v, k, n, on_grid, 50_000, 10_000)
    np.testing.assert_allclose(got[0, 0], [50.0, 150.0], rtol=1e-15)
    # 3 s past a sample, window 50 s: cells 6..10 (40 s sampled), 7 s before
    # the first and 3 s after the last, both under 1.1 intervals: the slope
    # is stretched over all 50 s
    off = np.array([BASE + 103_000])
    got = r.bucket_rates("increase", v, k, n, off, 50_000, 10_000)
    np.testing.assert_allclose(got[0, 0], [50.0, 150.0], rtol=1e-15)
    # a series with one sample in the window has no value; a fall adds 0
    assert np.isnan(r.bucket_rates("rate", v, k, np.array([6]), on_grid,
                                   50_000, 10_000)).all()
    w = v.copy()
    w[0, 8:, 0] -= w[0, 8, 0]                       # bucket 0 falls at cell 8
    got = r.bucket_rates("increase", w, k, n, on_grid, 50_000, 10_000)
    np.testing.assert_allclose(got[0, 0], [40.0, 150.0], rtol=1e-15)
    got = r.bucket_rates("delta", w, k, n, on_grid, 50_000, 10_000)
    np.testing.assert_allclose(got[0, 0], [20.0 - 150.0, 150.0], rtol=1e-15)


def test_reference_is_the_golden_models_bucket_by_bucket(hist):
    from tests import prom_reference as pr
    r = hist.reference
    cols = np.arange(200)
    _su, _cn, h = hist.datagen.columns_np(5, np.arange(40, 44), cols, 64)
    h[1, 120:, 9] -= h[1, 120, 9]                   # one fall
    ts = BASE + cols * 10_000
    n = np.array([200, 200, 150, 200])
    out_ts = BASE + 310_000 + 7_000 + np.arange(20) * 75_000
    for fn in ("rate", "increase", "delta"):
        mine = r.bucket_rates(fn, h, cols, n, out_ts, 300_000, 10_000)
        for i in range(4):
            for b in (0, 9, 33, 63):
                gold = pr.eval_range_fn(fn, ts[:n[i]], h[i, :n[i], b], out_ts,
                                        300_000)
                np.testing.assert_allclose(mine[i, :, b], gold, rtol=1e-12,
                                           err_msg=f"{fn} series {i} bucket {b}")
    # what a deployment evaluates — two cells a window — is the dense answer
    # wherever no counter fell
    ends = r.needed_columns(out_ts, 300_000, 10_000, 199)
    full = np.full(4, 200)
    dense = r.bucket_rates("rate", h[[0, 3]], cols, full[:2], out_ts, 300_000,
                           10_000)
    sparse = r.bucket_rates("rate", h[[0, 3]][:, ends], ends, full[:2], out_ts,
                            300_000, 10_000)
    np.testing.assert_allclose(sparse, dense, rtol=1e-12)


def test_evaluate_sums_groups_by_series_modulo(hist):
    r = hist.reference
    sids = np.arange(64)
    out_ts = BASE + 3_000_000 + np.arange(5) * 60_000
    spec = {"q": 0.9, "fn": "rate", "window_s": 300, "by": ["g"]}
    got = hist.evaluate(11, sids, spec, out_ts, DEPLOY, 720)
    assert sorted(got) == [(("g", f"g{k}"),) for k in range(8)]
    cols = r.needed_columns(out_ts, 300_000, 10_000, 720)
    h = hist.raw_values(11, sids, cols, DEPLOY)
    rates = r.bucket_rates("rate", h, cols, np.full(64, 721), out_ts, 300_000,
                           10_000)
    les = hist.datagen.bucket_les(64)
    for k in range(8):
        want = r.quantile(0.9, les, rates[sids % 8 == k].sum(axis=0))
        np.testing.assert_allclose(got[("g", f"g{k}"),], want, rtol=1e-12)
    whole = hist.evaluate(11, sids, dict(spec, by=[]), out_ts, DEPLOY, 720)
    np.testing.assert_allclose(whole[()], r.quantile(0.9, les, rates.sum(0)),
                               rtol=1e-12)
    # ``values`` replaces the generator (the control's way in)
    twice = hist.evaluate(11, sids, spec, out_ts, DEPLOY, 720,
                          values=lambda s, c: 2 * hist.raw_values(11, s, c, DEPLOY))
    for k in got:
        np.testing.assert_allclose(twice[k], got[k], rtol=1e-9)   # scale-free


def test_query_bytes_by_hand(hist):
    ref = {"window_s": 300}
    head = BASE + 720 * 10000
    out_ts = np.arange(head - 900_000, head + 1, 15_000)
    # cells 600..720 (the first window reaches back 5 min): 121 columns
    assert hist.kernelbytes.needed_columns(out_ts, 300_000, 10_000, 720,
                                           768) == 121
    assert hist.query_bytes(32768, ref, out_ts, DEPLOY, 720, 768) == \
        32768 * (121 * 64 * 4 + 8) + 2 * 121 * 61 * 4
    whole = np.arange(head - 7_200_000, head + 1, 120_000)
    assert hist.kernelbytes.needed_columns(whole, 300_000, 10_000, 720,
                                           768) == 721


# ---- fill against the write path ------------------------------------------

def _shard(series: int, capacity: int):
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    ms = TimeSeriesMemStore()
    sh = ms.setup("histfill", data.load("hist").schema(), 0, StoreConfig(
        max_series_per_shard=series, samples_per_series=capacity,
        flush_batch_size=10**9, dtype="float32"))
    return ms, sh


def test_fill_leaves_the_store_the_write_path_would(hist):
    """1,024 histograms x 24 scrapes: scrape 0 through the write path and
    the fill after it, against all 24 through the write path."""
    import dataclasses

    from filodb_tpu.core.record import RecordBuilder
    S, C, FILL, seed = 1024, 32, 24, 2**31 + 3
    deploy = dict(DEPLOY, fill_columns=FILL)
    ids = np.arange(S)
    b = RecordBuilder(hist.schema())
    b.add_series_batch(hist.series_labels(ids, deploy),
                       hist.scrape_ms(0, deploy), 0.0)
    template = b.build()
    stores = []
    for scrapes in (1, FILL):
        ms, sh = _shard(S, C)
        for k in range(scrapes):
            ms.ingest("histfill", 0, dataclasses.replace(
                template, **hist.scrape(seed, ids, k, deploy)))
            sh.flush()
        stores.append(sh)
    filled, written = stores
    sid = np.arange(S, dtype=np.int64)
    with pytest.raises(RuntimeError, match="not as the write path"):
        hist.check_filled(filled, sid, deploy)
    hist.fill(filled, sid, seed, deploy)
    assert hist.check_filled(filled, sid, deploy) == set(
        filled.store.val.devices())
    hist.check_filled(written, sid, deploy)
    a, w = filled.store, written.store
    for x, y in ((a.val, w.val), (a.ts, w.ts), (a.n, w.n),
                 (a.extra["sum"], w.extra["sum"]),
                 (a.extra["count"], w.extra["count"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(a.n_host, w.n_host)
    np.testing.assert_array_equal(a.last_ts, w.last_ts)
    np.testing.assert_array_equal(a.first_ts, w.first_ts)
    assert a.grid_info() == w.grid_info() == (BASE, 10000)
    np.testing.assert_array_equal(filled.bucket_les, written.bucket_les)
    assert filled.lead_ms == written.lead_ms
    assert hist.landed(filled, 0, FILL - 1) and not hist.landed(filled, 0, FILL)
    assert hist.landed(filled, np.arange(S), FILL - 1).all()
    su, cn, h = hist.datagen.columns_np(seed, ids, np.arange(FILL), 64)
    np.testing.assert_array_equal(np.asarray(a.val)[:, :FILL], h)
    assert not np.asarray(a.val)[:, FILL:].any()


# ---- the files ISSUE 29 names -----------------------------------------------

def test_the_configuration_and_the_mix_are_as_named():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf = {c["name"]: c for c in bench["configs"]}["histdev_raw_32k"]
    cell = {w["name"]: w for w in bench["workloads"]}["hist_adhoc"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "histdev_raw_32k", "adhoc_hist", 1)
    with open(os.path.join(ROOT, conf["file"])) as f:
        d = json.load(f)
    assert d["source"] == conf["source"] and len(d["source"]) <= 200
    assert d["reduced"] == conf["reduced"] == []
    assert d["server"] == {
        "num_shards": 1, "spread": 0, "dataset": "histdev",
        "schema": "prom-histogram",
        "store": {"max_series_per_shard": 32768, "samples_per_series": 768}}
    assert (d["series"], d["data"], d["buckets"], d["fill_columns"],
            d["scrape_interval_ms"], d["containers_per_scrape"]) == (
        32768, "hist", 64, 720, 10000, 8)
    assert d["guarantees"]["rtol"] == 2e-4 and d["guarantees"]["atol"] == 1e-4
    assert 32768 * 768 * 64 * 4 == 6_442_450_944
    mix = traffic.load("adhoc_hist")
    assert mix["clients"] == 8 and mix["tenant"] is None
    assert [q["promql"] for q in mix["queries"]] == [
        "histogram_quantile(0.9, sum(rate(h[5m])))",
        "histogram_quantile(0.99, sum by (g)(rate(h[5m])))",
        "histogram_quantile(0.5, sum(increase(h[5m])))",
        "histogram_quantile(0.9, sum by (g)(rate(h[1m])))"]
    with open(os.path.join(BENCH, "traffic", "adhoc.json")) as f:
        assert mix["ranges"] == json.load(f)["ranges"]
    gen = traffic.Generator(mix, 5, BASE + 7_200_000)
    assert len(gen.cards) == 20 and all(
        len(r.out_ts()) == 61 for r in gen.warmup())
    assert (mix["order"], mix["warmup"]) == ("shared_deck", "deck")
    # the tiled raw tier's route and no other: not the untiled composition,
    # the narrow tier, the XLA twin or the general path
    from benchmark import correct
    recs = [{"ok": True, "path": p} for p in (
        "fused-hist[pallas]", "fused-hist", "fused-hist-narrow[pallas]",
        "fused-hist[xla]", "local", "fused-hist[pallas-interpret]")]
    off, _ = correct.routes_off(recs, mix["expect_routes"], False)
    assert off == 5
    off, _ = correct.routes_off(recs, mix["expect_routes"], True)
    assert off == 4
