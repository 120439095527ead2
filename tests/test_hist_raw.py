"""First-class histograms on the raw f32 store: the row-tiled raw hist
kernel (ops/fusedresident.py, route ``fused-hist[<backend>]``), the scalar
registration sample on a histogram schema, and the served path end to end.

The plain reference is the benchmark's (``benchmark/data/hist/reference.py``:
numpy f64, imports nothing of the program); the parity reference is the
untiled composition ``gridfns.fused_hist_quantile_grid`` (``_grid_hist_kernel``
+ ``partial_aggregate`` + ``_hist_quantile``).

Tolerances, each with its reason:

- Pallas (interpreted here) against its XLA twin: rtol 1e-6, a few f32
  ulps of the answer and a two-hundredth of the tolerance. One tiling plan,
  one tile math, one fold order — but the fold is compensated, which keeps
  what a plain f32 fold rounds away, so the last bit of each series'
  contribution shows, and the two programs' compilers contract a multiply
  and the subtraction after it differently (measured: up to 2e-7, for
  ``delta``, whose factor is one a step).
- tiled against the f64 reference: the deployments' stated exactness, rtol
  2e-4 + atol 1e-4 (``histdev_raw_32k.json``), and in fact a fiftieth of
  it: the kernel's matmuls are exact on integers below 2^24, what rounds is
  each series' f32 extrapolation (1e-7 of a cumulative count), and the fold
  sums bucket STEPS in compensated pairs, so that a tail bucket keeps its
  own digits whatever the number of series. One bf16 pass over the values
  (``benchmark/control.py``'s fault) misses the same tolerance by 20 times
  and more (last test).
- tiled against the untiled composition: the same rtol 2e-4 — the
  composition is the LESS exact side (it folds cumulative buckets in f32:
  its own error against f64 reaches 0.3 of the tolerance at a p99).
- the packed weight against the sum of increments it replaces: equal, on
  integers below 2^24 (a telescoped sum of integers, plus integer
  corrections where the increments are clipped or the row ends).
"""

import dataclasses
import json
import time
import urllib.parse
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import data as benchdata
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE, PROM_HISTOGRAM
from filodb_tpu.ops import fusedgrid, fusedresident, gridfns

hist = benchdata.load("hist")
ref, datagen = hist.reference, hist.datagen
BASE, IV = datagen.BASE_TS, 10_000
RTOL, ATOL = 2e-4, 1e-4


def err_ratio(got, want):
    """Worst |got - want| in units of the tolerance; inf on a NaN mismatch."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    fin = np.isfinite(want)
    if got.shape != want.shape or (np.isfinite(got) != fin).any():
        return float("inf")
    if not fin.any():
        return 0.0
    return float(np.max(np.abs(got[fin] - want[fin])
                        / (ATOL + RTOL * np.abs(want[fin]))))


def _block(S, C, B, seed):
    """Cumulative integer buckets [S, C, B] (f64) and counts n [S]: rows
    with fewer samples than capacity (half, one, none) and a counter that
    falls once in one bucket of one series."""
    rng = np.random.default_rng(seed)
    inc = rng.integers(0, 32, (S, C, B))
    inc[:, :, B // 2:] //= 8                  # a tail: p99 lands in thin buckets
    v = np.cumsum(np.cumsum(inc, axis=1), axis=2).astype(np.float64)
    n = np.full(S, C, np.int32)
    n[::7] = C // 2
    n[3], n[5] = 1, 0
    v[2, C // 3:, 4] -= v[2, C // 3, 4]       # the fall
    assert (np.diff(v[2, :, 4]) < 0).sum() == 1
    for s in np.flatnonzero(n < C):
        v[s, n[s]:] = 0.0
    assert v.max() < 2**24
    return v, n


SHAPES = {"1024x64x8": (1024, 64, 8), "2048x128x64": (2048, 128, 64)}


@pytest.fixture(scope="module", params=sorted(SHAPES))
def block(request):
    S, C, B = SHAPES[request.param]
    v, n = _block(S, C, B, 29)
    return v, jnp.asarray(v, jnp.float32), n, datagen.bucket_les(B)


def _steps(C, T):
    """``T`` steps off the grid, the first windows reaching before cell 0,
    the last ones past the short rows' ends."""
    return BASE + 2 * IV + 3_000 + np.arange(T) * ((C - 4) * IV // T)


FNS = ["rate", "increase", "delta"]


# 24 steps: the weight's halves share 128 columns; 100: two bands wide;
# 160: three of four, the halves meeting inside a lane tile
@pytest.mark.parametrize("fn, q, G, T", [
    (fn, q, G, 24) for fn in FNS for q in (0.5, 0.9, 0.99) for G in (1, 8)
] + [(fn, 0.9, 8, 100) for fn in FNS] + [("rate", 0.9, 8, 160)])
def test_tiled_raw_kernel_against_composition_and_reference(block, fn, q, G,
                                                            T):
    v64, v32, n, les = block
    S, C, B = v64.shape
    out_ts = _steps(C, T)
    assert fusedresident.raw_hist_fusable(S, C, len(out_ts), B, 8)
    gids = (np.arange(S) % G).astype(np.int32)
    window = 120_000
    outs = {}
    for variant in ("xla", "pallas"):
        out, _falls, tags = fusedresident.fused_hist_quantile_raw(
            q, les, v32, n, gids, 8, out_ts, window, fn, BASE, IV,
            variant=variant)
        assert tags["kernel"] == fusedgrid.kernel_tag(variant)
        assert (tags["rows"], tags["buckets"]) == (S, B)
        assert tags["packed"] == (T != 100)
        outs[variant] = np.asarray(out)[:G], int(np.asarray(_falls)[0])
    # the block's one fall, and rows that end under a window in every tile
    assert outs["xla"][1] == outs["pallas"][1] == S // 16
    outs = {k: o[0] for k, o in outs.items()}
    np.testing.assert_allclose(outs["xla"], outs["pallas"], rtol=1e-6)
    want = ref.group_quantile(
        q, les, ref.bucket_rates(fn, v64, np.arange(C), n, out_ts, window,
                                 IV), gids, G)
    assert np.isfinite(want).sum() >= G * (len(out_ts) - 2)
    assert err_ratio(outs["pallas"], want) <= 0.02
    comp = np.asarray(gridfns.fused_hist_quantile_grid(
        q, les, v32, n, gids, 8, out_ts, window, fn, BASE, IV))[:G]
    assert err_ratio(outs["pallas"], comp) <= 1.0
    assert err_ratio(comp, want) <= 1.0


@pytest.mark.parametrize("short", [None, 236])
def test_a_sub_range_query_streams_only_its_columns(short):
    """The raw tier slices columns (the narrow tier cannot: its frames
    telescope from cell 0): the last 10 windows of a 256-cell store read one
    128-column block, and answer as the whole store does — also where rows
    end at cell ``short``, inside the block and under its windows: the
    packed weight reads their zeroed cells, and the correction puts the
    last sample back."""
    S, C, B = 64, 256, 8
    v, n = _block(S, C, B, 5)
    if short:
        n[1::5] = short
        v[1::5, short:] = 0.0
    les = datagen.bucket_les(B)
    out_ts = BASE + (C - 40) * IV + 1_000 + np.arange(10) * 30_000
    gids = (np.arange(S) % 8).astype(np.int32)
    v32 = jnp.asarray(v, jnp.float32)
    out, falls, tags = fusedresident.fused_hist_quantile_raw(
        0.9, les, v32, n, gids, 8, out_ts, 60_000, "rate", BASE, IV)
    assert (tags["c0"], tags["cols"]) == (128, 128)
    # the block's own short rows end at cell 128, 1 and 0: none in a window
    assert int(np.asarray(falls)[0]) == (S // 16 if short else 0)
    want = ref.group_quantile(
        0.9, les, ref.bucket_rates("rate", v, np.arange(C), n, out_ts,
                                   60_000, IV), gids, 8)
    assert err_ratio(np.asarray(out), want) <= 0.25


def _counters(S, C, B, seed, falls):
    """Monotone integer buckets ``[S, C, B]``, every row full, and a reset
    to zero at ``(series, cell)`` for each of ``falls``."""
    rng = np.random.default_rng(seed)
    v = np.cumsum(np.cumsum(rng.integers(0, 32, (S, C, B)), axis=1), axis=2)
    for s_, c_ in falls:
        v[s_, c_:] -= v[s_, c_]
    return v.astype(np.float64), np.full(S, C, np.int32)


@pytest.mark.parametrize("variant", ["xla", "pallas"])
@pytest.mark.parametrize("falls, want_tiles", [
    ([], 0),                                      # counters that only grow
    ([(3, 40), (20, 90), (37, 41), (63, 100)], 4),  # a reset in every tile
    ([(3, 40), (5, 60)], 1),                      # two in one tile
    ([(20, 8), (40, 120)], 0),                    # under no window
], ids=["none", "every", "one", "unread"])
def test_fall_tiles_counts_the_tiles_that_took_the_correction(
        falls, want_tiles, variant):
    """64 series = 4 tiles of 16; windows of 6 cells at steps of 5 cover
    cells 25..110 of 128. A tile runs the second matmul only where one of
    its series falls in a cell some window sums; the answer is the
    reference's either way."""
    S, C, B = 64, 128, 8
    v, n = _counters(S, C, B, 11, falls)
    les = datagen.bucket_les(B)
    out_ts = BASE + 30 * IV + 2_000 + np.arange(17) * 50_000
    gids = (np.arange(S) % 8).astype(np.int32)
    out, got, _tags = fusedresident.fused_hist_quantile_raw(
        0.9, les, jnp.asarray(v, jnp.float32), n, gids, 8, out_ts, 60_000,
        "rate", BASE, IV, variant=variant)
    assert int(np.asarray(got)[0]) == want_tiles
    want = ref.group_quantile(
        0.9, les, ref.bucket_rates("rate", v, np.arange(C), n, out_ts,
                                   60_000, IV), gids, 8)
    assert err_ratio(np.asarray(out), want) <= 0.25


@pytest.mark.parametrize("c0_from", [0, 150])
@pytest.mark.parametrize("T", [24, 100])
@pytest.mark.parametrize("fn", FNS)
def test_the_packed_weight_telescopes_the_sum_of_increments(fn, T, c0_from):
    """What one matmul of the values gives — ``v[hi] - v[lo]`` and the first
    sample, side by side — plus the correction's matmul equals, to the
    integer, what the two matmuls it replaces gave: the sum of clipped
    increments over the open band (``_grid_hist_kernel``'s) and the values
    at the first-sample one-hot. On the block with a fall, short and empty
    rows; from cell 0 with windows before it, and on a column sub-range."""
    S, C, B = 32, 256, 8
    v, n = _block(S, C, B, 3)
    n[4] = 200                                   # ends under a window
    v[4, 200:] = 0.0
    out_ts = BASE + c0_from * IV + 3_000 + np.arange(T) * (
        (C - 4 - c0_from) * IV // T)
    last, w, band, used, _lo, _hi, _rel, c0, Ca = \
        fusedresident.raw_hist_weights(C, out_ts, 120_000, BASE, IV)
    Tp, N = band.shape[1], w.shape[1]
    assert (c0 > 0) == (c0_from > 0) and w.shape[0] == band.shape[0] == Ca
    assert N == (128 if T <= 64 else 256) and Tp == 128
    assert set(np.unique(w)) <= {-1.0, 0.0, 1.0}
    assert (np.abs(w[:, :N // 2]).sum(0) <= 2).all() \
        and (w[:, :N // 2].sum(0) == 0).all() \
        and (w[:, N // 2:N // 2 + T].sum(0) == 1).all() \
        and not w[:, T:N // 2].any() and not w[:, N // 2 + T:].any()
    # the two products of before, in f64 on the whole store
    lo, hi = gridfns.grid_edges(out_ts, 120_000, BASE, IV)
    valid = np.arange(C)[None, :, None] < n[:, None, None]
    vz = np.where(valid, v, 0.0)
    raw = np.diff(vz, axis=1, prepend=vz[:, :1])
    inc = np.where(valid, raw if fn == "delta" else np.maximum(raw, 0), 0.0)
    want_d = np.einsum("scb,ct->sbt", inc,
                       gridfns.band_matrix(C, lo, hi, True, np.float64))
    want_f = np.einsum("scb,ct->sbt", vz, gridfns.onehot_matrix(
        C, np.maximum(lo, 0), np.float64))
    assert last[0] == min(hi.max(), C - 1) and used[0, last[0] - c0] \
        and not used[0, last[0] - c0 + 1:].any()

    def roll1(a):
        return jnp.roll(a, 1, axis=1)
    # the tile as it arrives: absent cells hold whatever was there before
    x = jnp.asarray(np.where(valid, v, 7.0)[:, c0:c0 + Ca].transpose(0, 2, 1),
                    jnp.float32)
    vs = jnp.stack([fusedresident.raw_hist_values(c0, x[s_], int(n[s_]))
                    for s_ in range(S)])
    corr = jnp.stack([fusedresident.raw_hist_corr(
        fn, c0, vs[s_], int(n[s_]), jnp.asarray(used), roll1)
        for s_ in range(S)]).reshape(S * B, Ca)
    # every cell that needs a correction shows as a drop (the pass each
    # tile takes looks for no more), or is the cell after a row's end
    drops = np.asarray(jnp.stack([fusedresident.raw_hist_drops(vs[s_], roll1)
                                  for s_ in range(S)])) > 0
    ends = np.arange(c0, c0 + Ca)[None, None, :] == n[:, None, None]
    need = np.asarray(corr).reshape(S, B, Ca) != 0
    assert need.any() and not (need & ~(drops | ends)).any()
    vs = vs.reshape(S * B, Ca)
    both = np.asarray(fusedgrid.dot_exact01(
        vs, jnp.asarray(w, jnp.bfloat16))).reshape(S, B, N)
    fix = np.asarray(fusedgrid.dot_exact01(
        corr, jnp.asarray(band, jnp.bfloat16))).reshape(S, B, Tp)
    np.testing.assert_array_equal(both[:, :, :T] + fix[:, :, :T], want_d)
    np.testing.assert_array_equal(both[:, :, N // 2:N // 2 + T], want_f)
    delta, f_v = fusedresident.unpack_halves(
        jnp.asarray(both[0]), Tp, lambda a, k: jnp.roll(a, k, axis=1))
    np.testing.assert_array_equal(np.asarray(delta)[:, :T], both[0, :, :T])
    np.testing.assert_array_equal(np.asarray(f_v)[:, :T], want_f[0])


@pytest.mark.parametrize("S, C, T, B, G, ok", [
    (32768, 768, 64, 64, 8, True),       # histdev_raw_32k
    (32768, 768, 64, 64, 64, True),      # the group cap
    (32768, 768, 64, 64, 128, False),    # past it: accumulators too large
    (1024, 64, 24, 6, 8, False),         # buckets no whole sublane tile
    (1000, 64, 24, 8, 8, False),         # rows no whole tiles
    (1024, 2048, 24, 8, 8, False),       # capacity past the band's room
])
def test_the_raw_tiers_gate(S, C, T, B, G, ok):
    assert fusedresident.raw_hist_fusable(S, C, T, B, G) is ok


def test_dot_exact01_is_exact_where_one_bf16_pass_is_not():
    rng = np.random.default_rng(1)
    x = rng.integers(0, 2**19, (64, 256)).astype(np.float32)
    w = ((rng.random((256, 128)) < 0.1).astype(np.float32)
         - (rng.random((256, 128)) < 0.1))        # -1, 0 and 1
    assert set(np.unique(w)) == {-1.0, 0.0, 1.0}
    want = x.astype(np.float64) @ w.astype(np.float64)
    got = np.asarray(fusedgrid.dot_exact01(
        jnp.asarray(x), jnp.asarray(w, jnp.bfloat16)))
    keep = np.abs(want) < 2**24              # the sum itself must fit f32
    assert keep.mean() > 0.9
    np.testing.assert_array_equal(got[keep], want[keep])
    one_pass = np.asarray(jnp.dot(
        jnp.asarray(x, jnp.bfloat16), jnp.asarray(w, jnp.bfloat16),
        preferred_element_type=jnp.float32))
    assert (one_pass[keep] != want[keep]).mean() > 0.5


# ---- the scalar registration sample --------------------------------------

@pytest.mark.parametrize("nb", [0, 4])
def test_a_scalar_registers_series_on_a_histogram_schema(nb):
    """``add_series_batch(labels, ts, 0.0)`` is how a producer registers
    (benchmark/served.py builds every template so): on prom-histogram every
    value column takes the scalar — it was ``TypeError: len() of unsized
    object`` — with or without bucket bounds."""
    les = np.array([1.0, 2.0, 4.0, np.inf])[:nb] if nb else None
    b = RecordBuilder(PROM_HISTOGRAM, bucket_les=les)
    b.add_series_batch({"_metric_": "h", "host": [f"h{i}" for i in range(5)]},
                       BASE, 0.0)
    b.add({"_metric_": "h", "host": "solo"}, BASE, 3.0)
    rc = b.build()
    assert rc.values.shape == (6, 2 + nb)
    assert (rc.values[:5] == 0.0).all() and (rc.values[5] == 3.0).all()
    # the other forms still mean what they meant
    if nb:
        b.add({"_metric_": "h", "host": "x"}, BASE, np.array([1., 2., 3., 7.]))
        row = b.build().values[0]
        assert np.isnan(row[0]) and row[1] == 7.0 and (row[2:] == [1, 2, 3, 7]).all()
    g = RecordBuilder(GAUGE)
    g.add_series_batch({"_metric_": "m", "host": ["a", "b"]}, BASE, 1.5)
    assert (g.build().values == 1.5).all()


# ---- the served path ------------------------------------------------------

DEPLOY = {"metric": "h", "buckets": 64, "labels": {"groups": 8, "per_rack": 4},
          "scrape_interval_ms": IV, "fill_columns": 48}
N_SERIES, SEED = 256, 2**31 + 17


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A FiloServer with ``schema: prom-histogram`` and shipped defaults
    else; 256 histograms x 48 scrapes published as containers ``[n, 2 + B]``
    with ``bucket_les`` to the shard's bus, consumed and flushed."""
    from filodb_tpu.config import Config
    from filodb_tpu.ingest.bus import FileBus
    from filodb_tpu.standalone import FiloServer
    from filodb_tpu.utils.tracing import tracer
    tmp = tmp_path_factory.mktemp("histsrv")
    srv = FiloServer(Config({
        "num_shards": 1, "spread": 0, "dataset": "hists",
        "schema": "prom-histogram", "bus_dir": str(tmp / "bus"),
        "http": {"port": 0},
        "store": {"max_series_per_shard": N_SERIES,
                  "samples_per_series": 64}})).start()
    was = tracer.enabled, tracer.sample_rate
    tracer.enabled, tracer.sample_rate = True, 1.0
    try:
        assert srv.config["store.compressed_residency"] == "off"
        assert srv.config["query.fused_kernels"] == "pallas"
        ids = np.arange(N_SERIES)
        b = RecordBuilder(hist.schema())
        b.add_series_batch(hist.series_labels(ids, DEPLOY),
                           hist.scrape_ms(0, DEPLOY), 0.0)
        template = b.build()
        bus = FileBus(str(tmp / "bus" / "shard0.log"))
        for k in range(DEPLOY["fill_columns"]):
            bus.publish(dataclasses.replace(
                template, **hist.scrape(SEED, ids, k, DEPLOY)))
        bus.close()
        sh = srv.memstore.shard("hists", 0)
        want_rows = N_SERIES * DEPLOY["fill_columns"]
        deadline = time.monotonic() + 60
        while sh.stats.rows_ingested < want_rows:
            assert time.monotonic() < deadline, sh.stats.rows_ingested
            time.sleep(0.02)
        sh.flush()

        def get(promql, start_ms, end_ms, step_ms):
            q = urllib.parse.urlencode({
                "query": promql, "start": start_ms / 1000,
                "end": end_ms / 1000, "step": step_ms / 1000})
            url = (f"http://127.0.0.1:{srv.http.port}/promql/hists/api/v1/"
                   f"query_range?{q}")
            with urllib.request.urlopen(url, timeout=120) as r:
                return json.load(r)
        yield srv, sh, get
    finally:
        tracer.enabled, tracer.sample_rate = was
        srv.shutdown()


def _rows(body, out_ts, step_ms):
    got = {}
    for s in body["data"]["result"]:
        row = np.full(len(out_ts), np.nan)
        for ts, v in s["values"]:
            row[int(round((ts * 1000 - int(out_ts[0])) / step_ms))] = float(v)
        got[tuple(sorted(s["metric"].items()))] = row
    return got


HEAD = DEPLOY["fill_columns"] - 1


def test_the_write_path_lands_every_column_exactly(served):
    _srv, sh, _get = served
    st = sh.store
    assert st.val.shape == (N_SERIES, 64, 64) and sorted(st.extra) == [
        "count", "sum"]
    assert (st.n_host == HEAD + 1).all() and st.grid_info() == (BASE, IV)
    np.testing.assert_array_equal(sh.bucket_les, datagen.bucket_les(64))
    su, cn, h = datagen.columns_np(SEED, np.arange(N_SERIES),
                                   np.arange(HEAD + 1), 64)
    np.testing.assert_array_equal(np.asarray(st.val)[:, :HEAD + 1], h)
    np.testing.assert_array_equal(np.asarray(st.extra["sum"])[:, :HEAD + 1], su)
    np.testing.assert_array_equal(
        np.asarray(st.extra["count"])[:, :HEAD + 1], cn)


@pytest.mark.parametrize("promql, spec", [
    ("histogram_quantile(0.9, sum(rate(h[5m])))",
     {"q": 0.9, "fn": "rate", "window_s": 300, "by": []}),
    ("histogram_quantile(0.99, sum by (g)(rate(h[5m])))",
     {"q": 0.99, "fn": "rate", "window_s": 300, "by": ["g"]}),
    ("histogram_quantile(0.5, sum(increase(h[5m])))",
     {"q": 0.5, "fn": "increase", "window_s": 300, "by": []}),
    ("histogram_quantile(0.9, sum by (g)(rate(h[1m])))",
     {"q": 0.9, "fn": "rate", "window_s": 60, "by": ["g"]}),
    ("histogram_quantile(0.9, sum by (g)(delta(h[2m])))",
     {"q": 0.9, "fn": "delta", "window_s": 120, "by": ["g"]}),
])
def test_served_quantiles_answer_on_the_tiled_route(served, promql, spec):
    _srv, _sh, get = served
    end = hist.scrape_ms(HEAD, DEPLOY) - 1_009
    start, step = end - 180_000, 15_000
    body = get(promql, start, end, step)
    assert body["status"] == "success"
    assert body["stats"]["exec_path"] == \
        f"fused-hist[{fusedresident.tag()}]"
    out_ts = np.arange(start, end + 1, step)
    got = _rows(body, out_ts, step)
    want = hist.evaluate(SEED, np.arange(N_SERIES), spec, out_ts, DEPLOY, HEAD)
    assert set(got) == set(want) and len(want) == (8 if spec["by"] else 1)
    assert max(err_ratio(got[k], want[k]) for k in want) <= 1.0


def _counter(text, name, **labels):
    for line in text.splitlines():
        if line.startswith(name + "{") and all(
                f'{k}="{v}"' in line for k, v in labels.items()):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def test_served_and_fallback_are_counted_on_the_raw_route(served):
    srv, _sh, get = served
    url = f"http://127.0.0.1:{srv.http.port}/metrics"

    def read():
        with urllib.request.urlopen(url, timeout=30) as r:
            text = r.read().decode()
        return (_counter(text, "filodb_query_fused_served_total",
                         shape="hist_quantile", mode="pallas"),
                _counter(text, "filodb_query_fused_fallback_total",
                         shape="hist_quantile"))
    end = hist.scrape_ms(HEAD, DEPLOY) - 3_027
    s0, f0 = read()
    get("histogram_quantile(0.9, sum(rate(h[1m])))", end - 60_000, end, 15_000)
    s1, f1 = read()
    assert (s1 - s0, f1 - f0) == (1, 0)
    # 256 groups are past the tiled tier's accumulators: the untiled
    # composition serves and the fallback counts
    body = get("histogram_quantile(0.9, sum by (host)(rate(h[1m])))",
               end - 60_000, end, 15_000)
    assert body["stats"]["exec_path"] == "fused-hist"
    s2, f2 = read()
    assert (s2 - s1, f2 - f1) == (0, 1)


def test_windows_past_the_rows_end_are_served_through_the_correction(served):
    """A range that ends after the newest scrape: every row's last sample
    lies under a window, which the packed weight cannot know (it reads the
    row's zeroed cell at ``hi``), so all 256 / 16 tiles take the correction
    matmul — counted in /metrics — and the answer is the reference's."""
    srv, _sh, get = served
    url = f"http://127.0.0.1:{srv.http.port}/metrics"

    def fall_tiles():
        with urllib.request.urlopen(url, timeout=30) as r:
            return _counter(r.read().decode(),
                            "filodb_query_fused_fall_tiles_total",
                            mode="pallas")
    spec = {"q": 0.9, "fn": "rate", "window_s": 60, "by": ["g"]}
    promql = "histogram_quantile(0.9, sum by (g)(rate(h[1m])))"
    step = 15_000
    for past_ms, want_tiles in ((-5_011, 0), (40_007, N_SERIES // 16)):
        end = hist.scrape_ms(HEAD, DEPLOY) + past_ms
        start = end - 120_000
        before = fall_tiles()
        body = get(promql, start, end, step)
        assert body["stats"]["exec_path"] == \
            f"fused-hist[{fusedresident.tag()}]"
        assert fall_tiles() - before == want_tiles
        out_ts = np.arange(start, end + 1, step)
        got = _rows(body, out_ts, step)
        want = hist.evaluate(SEED, np.arange(N_SERIES), spec, out_ts, DEPLOY,
                             HEAD)
        assert set(got) == set(want) and len(want) == 8
        assert max(err_ratio(got[k], want[k]) for k in want) <= 1.0


def test_one_bf16_pass_misses_the_tolerance():
    """The control's fault at a size a test holds: the reference fed values
    rounded to bf16 misses rtol 2e-4 by far on every text of the mix — the
    comparison is tight enough to see one pass."""
    import ml_dtypes
    sids = np.arange(512)

    def low(s, c):
        return (hist.raw_values(SEED, s, c, DEPLOY).astype(np.float32)
                .astype(ml_dtypes.bfloat16).astype(np.float64))
    out_ts = hist.scrape_ms(HEAD, DEPLOY) - 500 - np.arange(8)[::-1] * 15_000
    for spec in ({"q": 0.9, "fn": "rate", "window_s": 300, "by": []},
                 {"q": 0.99, "fn": "rate", "window_s": 60, "by": ["g"]}):
        want = hist.evaluate(SEED, sids, spec, out_ts, DEPLOY, HEAD)
        got = hist.evaluate(SEED, sids, spec, out_ts, DEPLOY, HEAD, values=low)
        assert max(err_ratio(got[k], want[k]) for k in want) > 20
