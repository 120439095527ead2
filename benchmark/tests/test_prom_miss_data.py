"""The ``prom_miss`` data module (``benchmark/data/prom_miss/``): the scrapes
a real scraper misses.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` (tier-1
collects these too: ``tests/test_benchmark_data.py``).

- golden parity: which scrapes fail, the rows they carry, the reference's
  answers and the probes as the tree that added the module gave them
  (``golden_prom_miss.json``): a later change to any of them is a change of
  the yardstick and shows here;
- the miss law: its rate, its cap on a run, the same booleans from numpy
  and ``jax.numpy``, the fast path over a run of scrapes against the law
  spelled out; every other scrape is ``prom``'s;
- the plain reference against brute force over the samples that exist, and
  tied to the repo's golden model (``tests/prom_reference.py``) series by
  series given the same holes;
- ``fill`` of a hole store against the same scrapes sent through the write
  path, cell by cell, mirrors included; a store without a hole form is
  refused at once;
- probes meet holes on every one of 16 seeds; the reader of
  ``hole_cells_pct``; the control that clears the marks in the kernel's
  view; the files against what ISSUE 35 names.
"""

import dataclasses
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
from benchmark.run import load_layer  # noqa: E402

with open(os.path.join(HERE, "golden_prom_miss.json")) as f:
    GOLD = json.load(f)
DEPLOY = {"metric": "m", "series": 1 << 20,
          "labels": {"groups": 8, "per_rack": 4},
          "scrape_interval_ms": 10000, "fill_columns": 720}
BASE, IV = 1_700_000_000_000, 10_000
STALE_BITS = 0x7FF0000000000002


@pytest.fixture(scope="module")
def pm():
    return data.load("prom_miss")


def nan_rows(rows):
    return np.array([[np.nan if x is None else x for x in r] for r in rows])


# ---- golden ---------------------------------------------------------------

@pytest.mark.parametrize("seed", sorted(GOLD["miss"], key=int))
def test_missed_scrapes_and_their_rows_are_the_pinned_ones(pm, seed):
    sids, cols = GOLD["sids"], GOLD["cols"]
    missed = np.asarray(GOLD["miss"][seed], bool)
    want_t = np.asarray(GOLD["stamps"][seed], np.int64)
    assert (pm.datagen.miss_np(int(seed), sids, cols) == missed).all()
    assert (pm.datagen.stamps_np(int(seed), sids, cols, IV) == want_t).all()
    np.testing.assert_array_equal(pm.raw_values(int(seed), sids, cols, DEPLOY),
                                  nan_rows(GOLD["values"][seed]))
    for j, k in enumerate(cols):
        sc = pm.scrape(int(seed), np.asarray(sids), k, DEPLOY)
        assert sc["ts"].dtype == np.int64 and (sc["ts"] == want_t[:, j]).all()
        assert sc["values"].dtype == np.float64
        stale = sc["values"].view(np.uint64) == STALE_BITS
        assert (stale == missed[:, j]).all()        # StaleNaN, no other NaN
        assert np.isfinite(sc["values"][~stale]).all()
        assert pm.scrape_ms(k, DEPLOY) == BASE + k * IV


@pytest.mark.parametrize("i", range(len(GOLD["evaluate"]["answers"])))
def test_prom_miss_answers_are_the_pinned_ones(pm, i):
    ev = GOLD["evaluate"]
    a = ev["answers"][i]
    mix = traffic.load("adhoc")
    assert mix["queries"][a["qi"]]["promql"] == a["promql"]
    ref = mix["queries"][a["qi"]]["ref"]
    out_ts = np.arange(a["start_ms"], a["end_ms"] + 1, a["step_ms"])
    sids = np.arange(ev["sid_lo"], ev["sid_lo"] + ev["sid_n"])
    got = pm.evaluate(ev["seed"], sids, ref, out_ts, DEPLOY, ev["head_col"])
    want = {tuple(map(tuple, k)): np.array(
        [np.nan if x is None else x for x in v]) for k, v in a["rows"]}
    assert set(got) == set(want)
    for k, v in want.items():
        np.testing.assert_array_equal(got[k], v)
    prom = data.load("prom")
    assert pm.query_bytes is prom.query_bytes       # the marks cost no byte


@pytest.mark.parametrize("p", GOLD["probes"], ids=lambda p: str(p["seed"]))
def test_prom_miss_probes_are_the_pinned_ones(pm, p):
    got = pm.probes(p["seed"], np.arange(p["lo"], p["hi"]), p["col"],
                    DEPLOY, 2)
    assert [g["promql"] for g in got] == p["promql"]
    for g, span, want in zip(got, p["spans"], p["want"]):
        assert [g["start_ms"], g["end_ms"], g["step_ms"]] == span
        assert [[lb, v.tolist()] for lb, v in g["want"]] == want


# ---- the miss law ----------------------------------------------------------

@pytest.mark.parametrize("seed", (0, 7, 2**31 + 12345, 2**33 + 1))
def test_the_miss_law_in_numpy_and_jax_and_its_shape(pm, seed):
    import filodb_tpu  # noqa: F401 — turns x64 on, as the server does
    import jax
    import jax.numpy as jnp
    g = pm.datagen
    s, k = np.arange(8192), np.arange(768)
    word = g.fold_seed(seed)
    with np.errstate(over="ignore"):
        host = g.miss(np, word, s.astype(np.uint32)[:, None],
                      k.astype(np.uint32)[None, :])
        raw = g.raw(np, word, s.astype(np.uint32)[:, None],
                    k.astype(np.uint32)[None, :])
    dev = jax.jit(lambda s, k, w: g.miss(jnp, w, s[:, None], k[None, :]))(
        jnp.asarray(s, jnp.uint32), jnp.asarray(k, jnp.uint32),
        jnp.uint32(word))
    assert (np.asarray(dev) == host).all()
    assert (g.miss_np(seed, s, k) == host).all()           # the run's path
    assert (g.miss_np(seed, s, k[5::7]) == host[:, 5::7]).all()  # the law's
    assert (g.miss_np(seed, s, [-2, -1, 0, 1])[:, :3] == 0).all()
    # 1 in 128, never the registration scrape, a run of three at most
    assert abs(host.mean() - 1 / 128) < 4e-4 and not host[:, 0].any()
    run4 = host[:, 3:] & host[:, 2:-1] & host[:, 1:-2] & host[:, :-3]
    assert not run4.any()
    # independent a series, and what the cap took away is raw's fourth
    assert (host <= raw).all() and (raw & ~host)[:, 1:].sum() <= 4
    assert 0.95 < (host.any(axis=1)).mean() <= 1.0         # 99.6 % at 720
    # every other scrape is prom's, stamp and value
    prom = data.load("prom").datagen
    T, P = g.stamps_np(seed, s[:512], k, IV), prom.stamps_np(seed, s[:512], k,
                                                             IV)
    m = host[:512]
    assert (T[~m] == P[~m]).all() and g.counter is prom.counter
    with np.errstate(over="ignore"):
        ph = prom.phase(np, word, s[:512].astype(np.uint32), IV)
    assert (T[m] == (BASE + k[None, :] * IV + ph[:, None])[m]).all()


def test_runs_of_one_two_and_three_all_occur(pm):
    host = pm.datagen.miss_np(5, np.arange(1 << 16), np.arange(720))
    pad = np.zeros((len(host), 1), bool)
    edge = np.diff(np.concatenate([pad, host, pad], axis=1).astype(np.int8),
                   axis=1)
    lens = (np.argwhere(edge == -1)[:, 1] - np.argwhere(edge == 1)[:, 1])
    counts = np.bincount(lens, minlength=5)
    assert counts[4:].sum() == 0 and (counts[1:4] > 0).all()
    assert counts[1] > 100 * counts[2] > 0 and counts[2] > 50 * counts[3]
    # a series whole after 2 h is 1 in 280: independent a scrape
    assert abs(1 - (1 - host.any(axis=1).mean()) / (127 / 128) ** 719) < 0.25


# ---- the reference ---------------------------------------------------------

def brute(fn, t, v, out_ts, w):
    """One series, the slow obvious way, over the samples handed in."""
    out = np.full(len(out_ts), np.nan)
    for j, x in enumerate(out_ts):
        m = (t >= x - w) & (t <= x)
        tt, vv = t[m], v[m]
        if fn == "count_over_time":
            out[j] = len(tt) if len(tt) else np.nan
        elif fn == "sum_over_time":
            out[j] = vv.sum() if len(tt) else np.nan
        elif fn == "avg_over_time":
            out[j] = vv.mean() if len(tt) else np.nan
        elif len(tt) >= 2:
            d, samp = vv[-1] - vv[0], (tt[-1] - tt[0]) / 1000.0
            avg = samp / (len(tt) - 1)
            ds, de = (tt[0] - (x - w)) / 1000.0, (x - tt[-1]) / 1000.0
            if d > 0 and vv[0] >= 0 and samp * (vv[0] / d) < ds:
                ds = samp * (vv[0] / d)
            ext = samp + (ds if ds < avg * 1.1 else avg / 2) \
                + (de if de < avg * 1.1 else avg / 2)
            out[j] = d * (ext / samp) / ((w / 1000.0) if fn == "rate" else 1)
    return out


def test_reference_against_brute_force_and_the_golden_model(pm):
    from tests import prom_reference as pr
    seed, head, S = 5, 719, 40
    sids = np.arange(300, 300 + S)
    cols = np.arange(head + 1)
    T = pm.datagen.stamps_np(seed, sids, cols, IV)
    V = pm.datagen.counter_np(seed, sids, cols).astype(float)
    there = ~pm.datagen.miss_np(seed, sids, cols)
    lost = np.argwhere(~there)
    n = 0
    for start, step in ((BASE - 7000, 120_000), (T[3, 300] + 3000, 15_000),
                        (BASE + head * IV - 3_600_000, 60_000)):
        out_ts = start + np.arange(61) * step
        # edges ON the stamp of a missed scrape's neighbours, and beside
        (i, k) = lost[len(lost) // 2]
        out_ts[5:11] = (T[i, k - 1], T[i, k - 1] - 1, T[i, k + 1] + 300_000,
                        T[i, k + 1] + 300_001, T[i, k], T[i, k] + 300_000)
        out_ts = np.sort(out_ts[out_ts <= T.max()])
        for fn in ("rate", "increase", "sum_over_time", "avg_over_time",
                   "count_over_time"):
            mine = pm.reference.per_series(fn, T, V, there, 0, out_ts, 300_000,
                                           IV, head)
            for r in range(S):
                t, v = T[r][there[r]], V[r][there[r]]
                np.testing.assert_allclose(
                    mine[r], brute(fn, t, v, out_ts, 300_000), rtol=1e-12)
                np.testing.assert_allclose(
                    mine[r], pr.eval_range_fn(fn, t, v, out_ts, 300_000),
                    rtol=1e-12, err_msg=f"{fn} series {sids[r]}")
                n += len(out_ts)
    assert n > 30_000 and len(lost) > 100


def test_prom_miss_evaluate_is_the_per_series_answers_aggregated(pm):
    seed, head = 11, 720
    sids = np.arange(4096)
    out_ts = np.arange(BASE + 3_000_017, BASE + 3_900_018, 15_000)
    cols = np.arange(head + 1)
    T = pm.datagen.stamps_np(seed, sids, cols, IV)
    V = pm.datagen.counter_np(seed, sids, cols).astype(float)
    there = ~pm.datagen.miss_np(seed, sids, cols)
    x = pm.reference.per_series("rate", T, V, there, 0, out_ts, 300_000, IV,
                                head)
    got = pm.evaluate(seed, sids, {"agg": "stddev", "fn": "rate",
                                   "window_s": 300, "by": ["g"]},
                      out_ts, DEPLOY, head)
    assert sorted(got) == [(("g", f"g{k}"),) for k in range(8)]
    for k in range(8):
        np.testing.assert_allclose(got[(("g", f"g{k}"),)],
                                   x[sids % 8 == k].std(axis=0), rtol=1e-9)
    spec = {"agg": "sum", "fn": "count_over_time", "window_s": 300, "by": []}
    c = pm.reference.evaluate(seed, sids, spec, out_ts, IV, head, 8)[()]
    whole = pm.reference.evaluate(seed, sids, spec, out_ts, IV, head, 8,
                                  holes=False)[()]
    assert (c < whole).all() and (whole - c < 0.012 * whole).all()


# ---- fill against the write path ------------------------------------------

def _shard(series: int, capacity: int):
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    ms = TimeSeriesMemStore()
    sh = ms.setup("missfill", data.load("prom_miss").schema(), 0, StoreConfig(
        max_series_per_shard=series, samples_per_series=capacity,
        flush_batch_size=10**9, dtype="float32"))
    return ms, sh


def test_fill_of_a_hole_store_against_the_write_path_cell_by_cell(pm):
    """2,048 series x 40 scrapes: scrape 0 through the write path and the
    fill after it, against all 40 through the write path."""
    from filodb_tpu.core.chunkstore import RES_HOLE, TS_PAD
    from filodb_tpu.core.record import RecordBuilder
    S, C, FILL, seed = 2048, 64, 40, 2**31 + 3
    deploy = dict(DEPLOY, fill_columns=FILL)
    ids = np.arange(S)
    b = RecordBuilder(pm.schema())
    b.add_series_batch(pm.series_labels(ids, deploy),
                       pm.scrape_ms(0, deploy), 0.0)
    template = b.build()
    stores = []
    for scrapes in (1, FILL):
        ms, sh = _shard(S, C)
        for k in range(scrapes):
            ms.ingest("missfill", 0, dataclasses.replace(
                template, **pm.scrape(seed, ids, k, deploy)))
            sh.flush()
        stores.append(sh)
    filled, written = stores
    sid = np.arange(S, dtype=np.int64)
    with pytest.raises(RuntimeError, match="not as the write path"):
        pm.check_filled(filled, sid, deploy)
    pm.fill(filled, sid, seed, deploy)
    assert pm.check_filled(filled, sid, deploy) == set(
        filled.store.val.devices())
    pm.check_filled(written, sid, deploy)
    a, w = filled.store, written.store
    assert a.stamp_form == w.stamp_form == "line" and a.ts is None is w.ts
    for x, y in ((a.val, w.val), (a.res, w.res), (a.n, w.n),
                 (a.ts_block(), w.ts_block())):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in ((a.n_host, w.n_host), (a.holes_host, w.holes_host),
                 (a.tail_holes, w.tail_holes), (a.last_ts, w.last_ts), (a.first_ts, w.first_ts),
                 (a.line0, w.line0), (a.off_line, w.off_line)):
        np.testing.assert_array_equal(x, y)
    missed = pm.datagen.miss_np(seed, ids, np.arange(FILL))
    assert a.hole_cells == w.hole_cells == missed.sum() > 300
    assert a.stats.stale_markers == w.stats.stale_markers == missed.sum()
    assert a.stats.samples_appended == w.stats.samples_appended == S * FILL
    assert a.demoted == w.demoted == dict.fromkeys(a.demoted, 0)
    assert a.line_info().holes and len(a.line_info().minority) == 0
    res = np.asarray(a.res)
    assert ((res[:, :FILL] == RES_HOLE) == missed).all()
    assert not res[:, FILL:].any()
    # a sample under its own stamp; a hole past TS_PAD, under its line's,
    # and the marker's own stamp less that in its value cell
    ts = np.asarray(a.ts_block())[:, :FILL]
    stamps = pm.datagen.stamps_np(seed, ids, np.arange(FILL), IV)
    line = stamps[:, :1] + np.arange(FILL)[None, :] * IV
    assert (ts == np.where(missed, TS_PAD + line, stamps)).all()
    assert (np.asarray(a.val)[:, :FILL][missed]
            == (stamps - line)[missed]).all()
    assert 0 < np.abs(line - stamps)[missed].max() <= 63
    assert a.tail_holes.max() >= 1 and (
        a.tail_holes > 0).sum() == missed[:, FILL - 1].sum()
    assert filled.lead_ms == written.lead_ms
    assert pm.landed(filled, 0, FILL - 1) and not pm.landed(filled, 0, FILL)
    # a row whose newest scrape was missed has landed too: the cell is used
    ended = np.flatnonzero(missed[:, FILL - 1])
    assert len(ended) and pm.landed(filled, ended, FILL - 1).all()


def test_a_store_that_keeps_no_holes_is_refused_at_once(pm):
    """The parent commit's store: the fill stops before it writes."""
    class Old:
        C, n_host, line0 = 32, np.ones(4, np.int32), np.zeros(4, np.int64)

    class Shard:
        store, shard_num = Old(), 0

    with pytest.raises(RuntimeError, match="keeps no holes"):
        pm.fill(Shard(), np.arange(4), 1, dict(DEPLOY, fill_columns=8))
    with pytest.raises(RuntimeError, match="keeps no holes"):
        pm.check_filled(Shard(), np.arange(4), dict(DEPLOY, fill_columns=8))


# ---- probes, the reader, the control ---------------------------------------

@pytest.mark.parametrize("seed", [3 * i * i + (i % 3) * 2**31 + i
                                  for i in range(16)])
def test_probes_meet_holes_on_every_seed(pm, seed):
    """Of every run's probes: a sample read right after a run of missed
    scrapes, values and stamps its own; a step at which a probed series'
    newest scrape was missed, so the rack's count falls — a step of its
    own, between the marker's stamp and the stamp the row's line gives the
    marker's cell; exact counts of a group that a store without holes
    would not give."""
    ids = np.arange(131072, 262144)
    for col in (720, 733):
        got = pm.probes(seed, ids, col, DEPLOY, 2)
        assert len(got) == 4 and len({g["promql"] for g in got}) == 4
        vals, stamps, count, fused = got
        steps = np.arange(vals["start_ms"], vals["end_ms"] + 1, IV)
        assert len(steps) == 4 and vals["end_ms"] == BASE + col * IV + IV + 62
        assert stamps["promql"] == f'timestamp({vals["promql"]})'
        behind = 0
        for (lb, v), (_, s) in zip(vals["want"], stamps["want"]):
            sid = int(lb["host"][1:])
            cols = np.arange(col + 1)
            T = pm.datagen.stamps_np(seed, [sid], cols, IV)[0]
            lost = pm.datagen.miss_np(seed, [sid], cols)[0]
            k = [int(np.flatnonzero(T <= t)[-1]) for t in steps]
            assert not lost[k].any() and k[-1] == col
            assert v.tolist() == pm.datagen.counter_np(seed, [sid], k)[0] \
                .tolist()
            assert s.tolist() == (T[k] / 1000.0).tolist()
            behind += int(lost[np.array(k) - 1].sum())
        assert behind >= 1
        (lb, c), = count["want"]
        rack = int(lb["rack"][1:])
        assert count["promql"] == f'count by (rack)(m{{rack="r{rack}"}})'
        members = np.arange(4 * rack, 4 * rack + 4)
        assert np.isin(members, ids).all()
        mine = np.arange(count["start_ms"], count["end_ms"] + 1, IV)
        assert len(mine) == 4 and count["step_ms"] == IV
        assert vals["end_ms"] - IV < count["end_ms"] <= vals["end_ms"]
        held = pm.reference.last_scrape(seed, members, mine, IV, col)
        lost = np.stack([pm.datagen.miss_np(seed, [i], held[j])[0]
                         for j, i in enumerate(members)])
        assert c.tolist() == (~lost).sum(axis=0).tolist()
        assert c.min() >= 1 and c.min() < 4
        gaps = 0
        for j, i in enumerate(members):
            line0 = pm.datagen.stamps_np(seed, [i], [0], IV)[0, 0]
            for x, k in zip(mine[lost[j]], held[j][lost[j]]):
                marker = pm.datagen.stamps_np(seed, [i], [k], IV)[0, 0]
                gaps += marker <= x < line0 + k * IV
        assert gaps >= 1
        assert fused["promql"].startswith("sum(count_over_time(m{g=")
        assert fused["end_ms"] < pm.scrape_ms(720, DEPLOY)


@pytest.mark.parametrize("series", (4096, 1 << 17))
def test_the_count_probe_counts_the_samples_that_exist(pm, series):
    deploy = dict(DEPLOY, series=series)
    p = pm.probes(9, np.arange(series // 8), 725, deploy, 2)[-1]
    g = int(p["promql"].split('g="g')[1].split('"')[0])
    steps = np.arange(p["start_ms"], p["end_ms"] + 1, p["step_ms"])
    sids = np.arange(g, series, 8)
    cols = np.arange(721)
    T = pm.datagen.stamps_np(9, sids, cols, IV)
    there = ~pm.datagen.miss_np(9, sids, cols)

    def counts(ok):
        return [int((ok & (T >= t - 300_000) & (T <= t)).sum())
                for t in steps]

    (labels, want), = p["want"]
    assert labels == {} and want.tolist() == counts(there)
    assert all(a < b for a, b in zip(counts(there), counts(there | True)))
    assert max(want) < 2**24                      # exact in f32


def sp(name, trace, **tags):
    return {"name": name, "trace_id": trace, "t0": 1.0, "dur_s": 0.01,
            "tags": tags}


def test_hole_cells_pct_reads_the_selects_of_fused_queries():
    read = load_layer("hole_cells_pct").read
    spans = [sp("query.exec.select", "a", hole_cells=78, used_cells=10_000),
             sp("query.exec.kernel", "a", phase="dispatch", rows=1000,
                stamps="line", holes=1),
             sp("query.exec.kernel", "a", phase="fetch"),
             sp("query.exec.select", "b", hole_cells=0, used_cells=30_000),
             sp("query.exec.kernel", "b", phase="dispatch", rows=3000),
             # the general path's answer: no fused kernel read these cells
             sp("query.exec.select", "c", hole_cells=500, used_cells=1000)]
    assert read({"spans": spans}) == pytest.approx(100 * 78 / 40_000)
    assert read({"spans": [s for s in spans if s["trace_id"] == "b"]}) == 0.0
    assert read({"spans": [s for s in spans if s["trace_id"] == "c"]}) is None
    # the parent commit's select span carries no such tags
    old = [sp("query.exec.select", "a", demoted=0),
           sp("query.exec.kernel", "a", phase="dispatch", rows=1000)]
    assert read({"spans": old}) is None and read({"spans": []}) is None


def test_the_absent_step_lies_before_the_line_and_the_store_serves_it(pm):
    """The store answers probe (b) — one of its steps between a marker's
    stamp and the stamp the row's line gives the marker's cell — and a
    store that kept the hole but not the marker's own stamp (its value
    cell cleared) serves the scrape before there: one too many."""
    import jax.numpy as jnp
    from filodb_tpu.core.chunkstore import RES_HOLE
    from filodb_tpu.core.record import RecordBuilder
    from filodb_tpu.query.engine import QueryEngine
    S, C, FILL, seed = 8192, 64, 40, 2**32 + 11
    deploy = dict(DEPLOY, series=S, fill_columns=FILL)
    ids = np.arange(S)
    b = RecordBuilder(pm.schema())
    b.add_series_batch(pm.series_labels(ids, deploy),
                       pm.scrape_ms(0, deploy), 0.0)
    ms, sh = _shard(S, C)
    ms.ingest("missfill", 0, dataclasses.replace(
        b.build(), **pm.scrape(seed, ids, 0, deploy)))
    sh.flush()
    pm.fill(sh, np.arange(S, dtype=np.int64), seed, deploy)
    probe = pm.probes(seed, ids, FILL - 1, deploy, 2)[2]
    assert probe["promql"].startswith("count by (rack)(")
    (_labels, want), = probe["want"]
    eng = QueryEngine(ms, "missfill")

    def served():
        r = eng.query_range(probe["promql"], probe["start_ms"],
                            probe["end_ms"], probe["step_ms"])
        return np.asarray(r.matrix.values)[0].tolist()

    assert served() == want.tolist() and want.min() < 4
    st = sh.store
    with sh.lock:
        st._pre_donate("test")
        st.val = jnp.where(st.res == RES_HOLE, 0, st.val)
    assert sum(served()) == want.sum() + 1


def test_the_control_clears_the_marks_in_the_kernels_view_alone(pm):
    """benchmark/control_holes.py: the store keeps its holes and returns
    its stamps; what the fused tier is handed has no mark and says so."""
    from benchmark import control_holes
    from filodb_tpu.core.chunkstore import RES_HOLE, TS_PAD, SeriesStore
    S, K, seed = 64, 40, 5
    ids = np.arange(S) + 77
    st = SeriesStore(S, 64)
    for k in range(K):
        sc = pm.scrape(seed, ids, k, DEPLOY)
        st.append(np.arange(S), sc["ts"], sc["values"])
    missed = pm.datagen.miss_np(seed, ids, np.arange(K))
    sound = st.line_info()
    line_info = SeriesStore.line_info
    try:
        control_holes.blind_kernel()
        blind = st.line_info()
        assert st.line_info().res is blind.res       # one block a state
    finally:
        SeriesStore.line_info = line_info
    assert sound.holes and sound.res is st.res and missed.sum() > 5
    assert ((np.asarray(sound.res)[:, :K] == RES_HOLE) == missed).all()
    assert not blind.holes and blind.start is sound.start
    got = np.asarray(blind.res)
    assert not (got == RES_HOLE).any()
    assert (got[:, :K][~missed] == np.asarray(sound.res)[:, :K][~missed]).all()
    ts = np.asarray(st.ts_block())[:, :K]
    assert ((ts >= TS_PAD) == missed).all()          # the store is intact


# ---- the files ISSUE 35 names -----------------------------------------------

def test_prom_miss_configuration_and_cell_are_as_named():
    """``promdev_prom_miss_1m`` x ``adhoc``, appended after
    ``promdev_prom_1m``'s entries, which stay as they were."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    confs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    conf, cell = confs["promdev_prom_miss_1m"], cells["adhoc_prom_miss"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "promdev_prom_miss_1m", "adhoc", 1)
    assert bench["configs"][-1] is conf and bench["workloads"][-1] is cell
    with open(os.path.join(ROOT, conf["file"])) as f:
        d = json.load(f)
    with open(os.path.join(ROOT, confs["promdev_prom_1m"]["file"])) as f:
        prom = json.load(f)
    assert d["source"] == conf["source"] and len(d["source"]) <= 200
    assert "scrape.go" in d["source"] and "StaleNaN" in d["source"] \
        and "timeseries-dev-source.conf" in d["source"]
    assert d["source"] != prom["source"]
    assert d["reduced"] == conf["reduced"] == [] and d["architecture"] is None
    for key in ("server", "series", "metric", "labels", "scrape_interval_ms",
                "fill_columns", "containers_per_scrape"):
        assert d[key] == prom[key], key
    assert d["data"] == "prom_miss" and "GB" in conf["why"]
    stated = dict(d["guarantees"])
    assert stated.pop("holes").startswith("a missed scrape is not a sample")
    assert stated == prom["guarantees"]
    assumed = dict(d["assumed"])
    for key in ("stream", "markers", "hole_runs"):
        assert key in assumed
    assert "0 mod 128" in assumed["stream"] and "k = 0" in assumed["stream"]
    assert "departure" in assumed["markers"]
    for key in ("stamp_law", "samples_per_series", "targets", "values",
                "scrape_ms", "fill_columns"):
        assert assumed[key] == prom["assumed"][key], key
    metrics = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("query_p50_ms", "kernel_roofline_pct", "leaf_ms",
                 "demoted_rows_pct"):
        assert metrics[name]["workloads"][-2:] == ["adhoc_prom",
                                                   "adhoc_prom_miss"], name
    assert metrics["hole_cells_pct"] == {
        "name": "hole_cells_pct", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "fused kernel",
        "moves": "query_rate", "workloads": ["adhoc_prom_miss"]}
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "demoted_rows_pct", "hole_cells_pct"]
    # promdev_prom_1m's own, as its file's case has them
    pc, pw = confs["promdev_prom_1m"], cells["adhoc_prom"]
    assert (pw["config"], pw["traffic"], pw["chips"]) == (
        "promdev_prom_1m", "adhoc", 1)
    assert prom["source"] == pc["source"] and prom["data"] == "prom"
    assert prom["reduced"] == pc["reduced"] == []
    assert "no missed scrape" in prom["assumed"]["stream"]
    mix = traffic.load("adhoc")
    assert mix["expect_routes"] == ["fused"]
    gen = traffic.Generator(mix, 5, BASE + 720 * IV)
    assert all(r.end_ms <= BASE + 720 * IV for r in gen.warmup())
    for f in ("data/prom_miss/__init__.py", "data/prom_miss/datagen.py",
              "data/prom_miss/fill.py", "data/prom_miss/reference.py",
              "layers/hole_cells_pct.py", "control_holes.py",
              "configs/promdev_prom_miss_1m.json"):
        assert os.path.isfile(os.path.join(BENCH, f)), f
