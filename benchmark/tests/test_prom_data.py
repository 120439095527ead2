"""The ``prom`` data module (``benchmark/data/prom/``): a scraper's stamps.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` (tier-1
collects these too: ``tests/test_benchmark_data.py``).

- golden parity: the generator's stamps and values, the reference's
  answers, the probes and the byte count as the tree that added the module
  gave them (``golden_prom.json``): a later change to any of them is a
  change of the yardstick and shows here;
- the stamp law in numpy and in ``jax.numpy``: the same integers; every
  phase in [0, interval), one scrape in sixteen late by 3..63 ms, none
  early; the values are ``counter``'s;
- the plain reference tied to the repo's golden model
  (``tests/prom_reference.py``) series by series, window edges on stamps
  among them, and its self-check;
- ``fill`` against the same scrapes sent through the write path: the two
  stores are bit-equal, residuals, lines and mirrors included;
- probes, byte count, the reader of ``demoted_rows_pct``, the control that
  leaves the residual out, and the files against what ISSUE 33 names.
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

with open(os.path.join(HERE, "golden_prom.json")) as f:
    GOLD = json.load(f)
DEPLOY = {"metric": "m", "series": 1 << 20,
          "labels": {"groups": 8, "per_rack": 4},
          "scrape_interval_ms": 10000, "fill_columns": 720}
BASE, IV = 1_700_000_000_000, 10_000


@pytest.fixture(scope="module")
def prom():
    return data.load("prom")


# ---- golden ---------------------------------------------------------------

@pytest.mark.parametrize("seed", sorted(GOLD["stamps"], key=int))
def test_prom_scrapes_are_the_pinned_ones(prom, seed):
    sids, cols = GOLD["sids"], GOLD["cols"]
    want_t = np.asarray(GOLD["stamps"][seed], np.int64)
    want_v = np.asarray(GOLD["values"][seed], np.float64)
    assert (prom.datagen.stamps_np(int(seed), sids, cols, IV) == want_t).all()
    assert (prom.raw_values(int(seed), sids, cols, DEPLOY) == want_v).all()
    for j, k in enumerate(cols):
        sc = prom.scrape(int(seed), np.asarray(sids), k, DEPLOY)
        assert sc["ts"].dtype == np.int64 and (sc["ts"] == want_t[:, j]).all()
        assert (sc["values"] == want_v[:, j]).all()
        assert prom.scrape_ms(k, DEPLOY) == BASE + k * IV
        assert ((sc["ts"] >= BASE + k * IV)
                & (sc["ts"] < BASE + k * IV + IV + 63)).all()


@pytest.mark.parametrize("i", range(len(GOLD["evaluate"]["answers"])))
def test_prom_answers_are_the_pinned_ones(prom, i):
    ev = GOLD["evaluate"]
    a = ev["answers"][i]
    mix = traffic.load("adhoc")
    assert mix["queries"][a["qi"]]["promql"] == a["promql"]
    ref = mix["queries"][a["qi"]]["ref"]
    out_ts = np.arange(a["start_ms"], a["end_ms"] + 1, a["step_ms"])
    sids = np.arange(ev["sid_lo"], ev["sid_lo"] + ev["sid_n"])
    got = prom.evaluate(ev["seed"], sids, ref, out_ts, DEPLOY, ev["head_col"])
    want = {tuple(map(tuple, k)): np.array(
        [np.nan if x is None else x for x in v]) for k, v in a["rows"]}
    assert set(got) == set(want)
    for k, v in want.items():
        np.testing.assert_array_equal(got[k], v)
    assert prom.query_bytes(1048576, ref, out_ts, DEPLOY, ev["head_col"],
                            768) == a["query_bytes"]


@pytest.mark.parametrize("p", GOLD["probes"], ids=lambda p: str(p["seed"]))
def test_prom_probes_are_the_pinned_ones(prom, p):
    got = prom.probes(p["seed"], np.arange(p["lo"], p["hi"]), p["col"],
                      DEPLOY, 2)
    sel = [f'm{{rack="r{r}"}}' for r in p["racks"]]
    *got, count = got
    assert [g["promql"] for g in got] == [
        q for s in sel for q in (s, f"timestamp({s})")]
    assert count["promql"] == p["count"]["promql"]
    assert (count["start_ms"], count["end_ms"], count["step_ms"]) == (
        p["count"]["start_ms"], p["count"]["end_ms"], IV)
    (labels, want), = count["want"]
    assert labels == {} and want.tolist() == p["count"]["want"]
    end = BASE + p["col"] * IV + IV + 62
    for g, want in zip(got, p["want"]):
        assert (g["start_ms"], g["end_ms"], g["step_ms"]) == (
            end - 3 * IV, end, IV)
        assert (np.stack([v for _, v in g["want"]]) == np.asarray(want)).all()
    for g, rack in zip(got[::2], p["racks"]):
        assert [lb for lb, _ in g["want"]] == [{"host": f"h{rack * 4 + j}"}
                                               for j in range(4)]


# ---- the stamp law ---------------------------------------------------------

@pytest.mark.parametrize("seed", (0, 7, 2**31 + 12345, 2**33 + 1))
def test_the_law_gives_numpy_and_jax_the_same_integers(prom, seed):
    import filodb_tpu  # noqa: F401 — turns x64 on, as the server does
    import jax
    import jax.numpy as jnp
    g = prom.datagen
    s, k = np.arange(4096), np.arange(768)
    word = g.fold_seed(seed)
    with np.errstate(over="ignore"):
        ph = g.phase(np, word, s.astype(np.uint32), IV)
        lt = g.late(np, word, s.astype(np.uint32)[:, None],
                    k.astype(np.uint32)[None, :])
    dev_ph, dev_lt = jax.jit(lambda s, k, w: (
        g.phase(jnp, w, s, IV), g.late(jnp, w, s[:, None], k[None, :])))(
            jnp.asarray(s, jnp.uint32), jnp.asarray(k, jnp.uint32),
            jnp.uint32(word))
    assert (np.asarray(dev_ph) == ph).all() and (np.asarray(dev_lt) == lt).all()
    assert ph.min() >= 0 and ph.max() < IV and len(np.unique(ph)) > 3000
    hit = lt > 0
    assert abs(hit.mean() - 1 / 16) < 0.002
    assert lt[hit].min() == 3 and lt[hit].max() == 63
    assert (g.offset_np(seed, s, k, IV) == ph[:, None].astype(np.int64)
            + lt).all()
    # counter's values, not a copy of them
    from benchmark.data.counter import datagen as counter_gen
    assert g.counter is counter_gen.counter
    assert (g.counter_np(seed, s[:64], k) ==
            counter_gen.counter_np(seed, s[:64], k)).all()


# ---- the reference ---------------------------------------------------------

def test_reference_against_the_golden_model_series_by_series(prom):
    from tests import prom_reference as pr
    seed, head, S = 5, 719, 48
    sids = np.arange(100, 100 + S)
    cols = np.arange(head + 1)
    T = prom.datagen.stamps_np(seed, sids, cols, IV)
    V = prom.datagen.counter_np(seed, sids, cols).astype(float)
    word = prom.datagen.fold_seed(seed)
    with np.errstate(over="ignore"):
        ph = prom.datagen.phase(np, word, sids.astype(np.uint32),
                                IV).astype(np.int64)
    late = np.argwhere(T - (BASE + cols * IV + ph[:, None]) > 0)
    n = 0
    for start, step in ((BASE - 7000, 120_000), (T[3, 300] + 3000, 15_000),
                        (BASE + head * IV - 3_600_000, 60_000)):
        out_ts = start + np.arange(61) * step
        # edges ON late stamps, and a millisecond to either side
        (i, k), (j, m) = late[len(late) // 3], late[2 * len(late) // 3]
        out_ts[5:11] = (T[i, k], T[i, k] - 1, T[i, k] + 1, T[j, m] + 300_000,
                        T[j, m] + 299_999, T[j, m] + 300_001)
        out_ts = np.sort(out_ts[out_ts <= T.max()])
        for fn in ("rate", "increase", "sum_over_time", "avg_over_time",
                   "count_over_time"):
            mine = prom.reference.per_series(fn, T, V, 0, out_ts, 300_000, IV,
                                             ph)
            for r in range(S):
                gold = pr.eval_range_fn(fn, T[r], V[r], out_ts, 300_000)
                np.testing.assert_allclose(mine[r], gold, rtol=1e-12,
                                           err_msg=f"{fn} series {sids[r]}")
                n += len(gold)
    assert n > 40_000


def test_the_reference_checks_its_own_brackets(prom):
    T = BASE + np.arange(40)[None, :] * IV + np.array([[0], [5000]])
    out_ts = np.array([BASE + 350_000])
    ok = prom.reference.window_run(T, 0, out_ts, 300_000, IV,
                                   np.array([0, 5000]))
    assert [x.tolist() for x in ok] == [[[5], [5]], [[35], [34]]]
    with pytest.raises(AssertionError, match="not bracketed"):
        prom.reference.window_run(T, 0, out_ts, 300_000, IV,
                                  np.array([0, 25_000]))   # a wrong phase


def test_evaluate_is_the_per_series_answers_aggregated(prom):
    seed, head = 11, 720
    sids = np.arange(4096)
    out_ts = np.arange(BASE + 3_000_017, BASE + 3_900_018, 15_000)
    cols = np.arange(head + 1)
    T = prom.datagen.stamps_np(seed, sids, cols, IV)
    V = prom.datagen.counter_np(seed, sids, cols).astype(float)
    ph = prom.datagen.offset_np(seed, sids, cols, IV).min(axis=1)
    x = prom.reference.per_series("rate", T, V, 0, out_ts, 300_000, IV, ph)
    got = prom.evaluate(seed, sids, {"agg": "stddev", "fn": "rate",
                                     "window_s": 300, "by": ["g"]},
                        out_ts, DEPLOY, head)
    assert sorted(got) == [(("g", f"g{k}"),) for k in range(8)]
    for k in range(8):
        np.testing.assert_allclose(got[(("g", f"g{k}"),)],
                                   x[sids % 8 == k].std(axis=0), rtol=1e-9)
    low = prom.evaluate(seed, sids, {"agg": "sum", "fn": "rate",
                                     "window_s": 300, "by": []},
                        out_ts, DEPLOY, head,
                        values=lambda s, c: np.round(
                            prom.datagen.counter_np(seed, s, c, np.float64),
                            -3))
    assert not np.allclose(low[()], x.sum(axis=0), rtol=1e-6)


# ---- fill against the write path ------------------------------------------

def _shard(series: int, capacity: int):
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    ms = TimeSeriesMemStore()
    sh = ms.setup("promfill", data.load("prom").schema(), 0, StoreConfig(
        max_series_per_shard=series, samples_per_series=capacity,
        flush_batch_size=10**9, dtype="float32"))
    return ms, sh


def test_fill_leaves_the_store_the_write_path_would(prom):
    """1,024 series x 24 scrapes: scrape 0 through the write path and the
    fill after it, against all 24 through the write path."""
    from filodb_tpu.core.record import RecordBuilder
    S, C, FILL, seed = 1024, 32, 24, 2**31 + 3
    deploy = dict(DEPLOY, fill_columns=FILL)
    ids = np.arange(S)
    b = RecordBuilder(prom.schema())
    b.add_series_batch(prom.series_labels(ids, deploy),
                       prom.scrape_ms(0, deploy), 0.0)
    template = b.build()
    stores = []
    for scrapes in (1, FILL):
        ms, sh = _shard(S, C)
        for k in range(scrapes):
            ms.ingest("promfill", 0, dataclasses.replace(
                template, **prom.scrape(seed, ids, k, deploy)))
            sh.flush()
        stores.append(sh)
    filled, written = stores
    sid = np.arange(S, dtype=np.int64)
    with pytest.raises(RuntimeError, match="not as the write path"):
        prom.check_filled(filled, sid, deploy)
    assert filled.store.stamp_form == "grid"       # one scrape: no step yet
    prom.fill(filled, sid, seed, deploy)
    assert prom.check_filled(filled, sid, deploy) == set(
        filled.store.val.devices())
    prom.check_filled(written, sid, deploy)
    a, w = filled.store, written.store
    assert a.stamp_form == w.stamp_form == "line" and a.ts is None is w.ts
    for x, y in ((a.val, w.val), (a.res, w.res), (a.n, w.n),
                 (a.ts_block(), w.ts_block())):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in ((a.n_host, w.n_host), (a.last_ts, w.last_ts),
                 (a.first_ts, w.first_ts), (a.line0, w.line0),
                 (a.off_line, w.off_line)):
        np.testing.assert_array_equal(x, y)
    assert (a.grid_base, a.grid_interval) == (w.grid_base, w.grid_interval)
    assert a.demoted == w.demoted == dict.fromkeys(a.demoted, 0)
    la, lw = a.line_info(), w.line_info()
    assert (la.base_ts, la.interval_ms) == (lw.base_ts, lw.interval_ms)
    assert len(la.minority) == len(lw.minority) == 0
    np.testing.assert_array_equal(np.asarray(la.start), np.asarray(lw.start))
    assert filled.lead_ms == written.lead_ms
    assert prom.landed(filled, 0, FILL - 1) and not prom.landed(filled, 0, FILL)
    assert prom.landed(filled, np.arange(S), FILL - 1).all()
    np.testing.assert_array_equal(
        np.asarray(a.ts_block())[:, :FILL],
        prom.datagen.stamps_np(seed, ids, np.arange(FILL), IV))
    res = np.asarray(a.res)[:, :FILL]
    assert res.min() < 0 < res.max() and np.abs(res).max() <= 63
    assert not np.asarray(a.res)[:, FILL:].any()


def test_a_store_without_a_line_form_is_refused_at_once(prom):
    """The parent commit's store: the fill stops before it writes."""
    class Old:
        C, n_host = 32, np.ones(4, np.int32)

    class Shard:
        store, shard_num = Old(), 0

    with pytest.raises(RuntimeError, match="no line form"):
        prom.fill(Shard(), np.arange(4), 1, dict(DEPLOY, fill_columns=8))


def test_the_control_stores_every_scrape_on_its_line(prom):
    """benchmark/control_stamps.py: the residual left out. The stamps a raw
    selector then returns are the LINE's: late scrapes come back early."""
    from benchmark import control_stamps
    from filodb_tpu.core.chunkstore import SeriesStore
    from filodb_tpu.core.record import RecordBuilder
    S, C, FILL, seed = 256, 32, 24, 77
    deploy = dict(DEPLOY, fill_columns=FILL)
    ids = np.arange(S)
    b = RecordBuilder(prom.schema())
    b.add_series_batch(prom.series_labels(ids, deploy),
                       prom.scrape_ms(0, deploy), 0.0)
    template = b.build()
    track, fill = SeriesStore._track_stamps, prom.fill
    try:
        control_stamps.drop_residuals(prom)
        ms, sh = _shard(S, C)
        ms.ingest("promfill", 0, dataclasses.replace(
            template, **prom.scrape(seed, ids, 0, deploy)))
        sh.flush()
        prom.fill(sh, ids.astype(np.int64), seed, deploy)
        ms.ingest("promfill", 0, dataclasses.replace(
            template, **prom.scrape(seed, ids, FILL, deploy)))
        sh.flush()
    finally:
        SeriesStore._track_stamps, prom.fill = track, fill
    st = sh.store
    assert st.stamp_form == "line" and not np.asarray(st.res).any()
    got = np.asarray(st.ts_block())[:, :FILL + 1]
    want = prom.datagen.stamps_np(seed, ids, np.arange(FILL + 1), IV)
    off = want - got
    assert (off != 0).any() and np.abs(off).max() <= 63
    assert (got == st.line0[:S, None] + np.arange(FILL + 1) * IV).all()


def test_the_kernel_level_control_blinds_the_fused_tier_alone(prom):
    """``--level kernel``: the store keeps its residuals and returns its
    stamps; what the fused tier is handed has none."""
    from benchmark import control_stamps
    from filodb_tpu.core.chunkstore import SeriesStore
    S, K, seed = 64, 20, 5
    ids = np.arange(S)
    st = SeriesStore(S, 32)
    for k in range(K):
        sc = prom.scrape(seed, ids, k, DEPLOY)
        st.append(ids, sc["ts"], sc["values"])
    want = prom.datagen.stamps_np(seed, ids, np.arange(K), IV)
    sound = st.line_info()
    line_info = SeriesStore.line_info
    try:
        control_stamps.blind_kernel()
        blind = st.line_info()
    finally:
        SeriesStore.line_info = line_info
    assert np.asarray(sound.res).any() and sound.res is st.res
    assert blind.res.shape == st.res.shape and blind.res.dtype == st.res.dtype
    assert not np.asarray(blind.res).any() and blind.start is sound.start
    assert (np.asarray(st.ts_block())[:, :K] == want).all()


# ---- probes, bytes, the reader ---------------------------------------------

def test_probes_read_the_sample_each_step_holds(prom):
    seed, col = 9, 700
    ids = np.arange(2048)
    for p in prom.probes(seed, ids, col, DEPLOY, 3)[:-1]:
        steps = np.arange(p["start_ms"], p["end_ms"] + 1, p["step_ms"])
        assert len(steps) == 4 and p["end_ms"] == BASE + col * IV + IV + 62
        stamps = p["promql"].startswith("timestamp(")
        for labels, want in p["want"]:
            sid = int(labels["host"][1:])
            T = prom.datagen.stamps_np(seed, [sid], np.arange(col + 1), IV)[0]
            V = prom.raw_values(seed, [sid], np.arange(col + 1), DEPLOY)[0]
            k = [int(np.flatnonzero(T <= t)[-1]) for t in steps]
            assert k[-1] == col                  # the newest scrape is read
            assert want.tolist() == [T[j] / 1000.0 if stamps else V[j]
                                     for j in k]


@pytest.mark.parametrize("seed", (0, 3, 9, 2**31 + 5, 2**33 + 77))
def test_every_probe_set_reads_a_sample_off_its_line(prom, seed):
    """The racks come from the law: of each, a probed step holds a sample
    whose stamp is not its row's line's. A store that kept the line and
    lost the residual misses the stamps of every run's probes."""
    for col in (720, 733):
        got = prom.probes(seed, np.arange(131072, 262144), col, DEPLOY, 2)
        assert len(got) == 5 and len({g["promql"] for g in got}) == 5
        for g in got[1:4:2]:
            steps = np.arange(g["start_ms"], g["end_ms"] + 1, g["step_ms"])
            off = 0
            for labels, want in g["want"]:
                sid = int(labels["host"][1:])
                line0 = prom.datagen.stamps_np(seed, [sid], [0], IV)[0, 0]
                held = prom.reference.last_scrape(seed, [sid], steps, IV,
                                                  col)[0]
                off += int((np.rint(want * 1000).astype(np.int64)
                            != line0 + held * IV).sum())
            assert off >= 1, g["promql"]


@pytest.mark.parametrize("series", (4096, 1 << 17))
@pytest.mark.parametrize("seed", (1, 2**32 + 9))
def test_the_count_probe_tells_true_stamps_from_the_lines(prom, seed, series):
    """The last probe: exact counts of one group from the TRUE stamps (by
    brute force here), inside the filled history, not the counts a reader
    of the line alone would give."""
    deploy = dict(DEPLOY, series=series)
    p = prom.probes(seed, np.arange(series // 8), 725, deploy, 1)[-1]
    g = int(p["promql"].split('g="g')[1].split('"')[0])
    assert p["promql"] == f'sum(count_over_time(m{{g="g{g}"}}[5m]))'
    steps = np.arange(p["start_ms"], p["end_ms"] + 1, p["step_ms"])
    assert len(steps) == 4 and p["end_ms"] < prom.scrape_ms(720, deploy)
    sids = np.arange(g, series, 8)
    T = prom.datagen.stamps_np(seed, sids, np.arange(721), IV)
    line = T[:, :1] + np.arange(721)[None, :] * IV

    def counts(st):
        return [int(((st >= t - 300_000) & (st <= t)).sum()) for t in steps]

    (labels, want), = p["want"]
    assert labels == {} and want.tolist() == counts(T) != counts(line)
    assert max(want) < 2**24                      # exact in f32


def test_query_bytes_counts_values_and_residuals(prom):
    head = BASE + 720 * IV
    out_ts = np.arange(head - 900_000, head + 1, 15_000)
    cols = prom.kernelbytes.needed_columns(out_ts, 300_000, IV, 720, 768)
    assert cols == 122                 # counter's 121 and one cell of phase
    ref = {"window_s": 300}
    assert prom.query_bytes(1 << 20, ref, out_ts, DEPLOY, 720, 768) == \
        (1 << 20) * (122 * (4 + 1) + 12) + 2 * 122 * 61 * 4
    whole = np.arange(head - 7_200_000, head + 1, 120_000)
    assert prom.kernelbytes.needed_columns(whole, 300_000, IV, 720,
                                           768) == 721


def sp(name, trace, **tags):
    return {"name": name, "trace_id": trace, "t0": 1.0, "dur_s": 0.01,
            "tags": tags}


def test_demoted_rows_pct_reads_the_selects_of_fused_queries():
    read = load_layer("demoted_rows_pct").read
    spans = [sp("query.exec.select", "a", demoted=10),
             sp("query.exec.kernel", "a", phase="dispatch", rows=1000,
                stamps="line"),
             sp("query.exec.kernel", "a", phase="fetch"),
             sp("query.exec.select", "b", demoted=0),
             sp("query.exec.kernel", "b", phase="dispatch", rows=3000,
                stamps="line"),
             # the general path's answer, whole: no fused answer to share
             sp("query.exec.select", "c", demoted=500)]
    assert read({"spans": spans}) == pytest.approx(100 * 10 / 4000)
    assert read({"spans": [s for s in spans if s["trace_id"] == "b"]}) == 0.0
    assert read({"spans": [s for s in spans if s["trace_id"] == "c"]}) is None
    # the parent commit's select span carries no such tag
    old = [sp("query.exec.select", "a"),
           sp("query.exec.kernel", "a", phase="dispatch", rows=1000)]
    assert read({"spans": old}) is None and read({"spans": []}) is None


# ---- the files ISSUE 33 names -----------------------------------------------

def test_the_configuration_and_the_cell_are_as_named():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    conf = {c["name"]: c for c in bench["configs"]}["promdev_prom_1m"]
    cell = {w["name"]: w for w in bench["workloads"]}["adhoc_prom"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "promdev_prom_1m", "adhoc", 1)
    with open(os.path.join(ROOT, conf["file"])) as f:
        d = json.load(f)
    with open(os.path.join(BENCH, "configs", "promdev_raw_1m.json")) as f:
        raw = json.load(f)
    assert d["source"] == conf["source"] and len(d["source"]) <= 200
    assert "scrape.go" in d["source"] and "2 ms" in d["source"] \
        and "timeseries-dev-source.conf" in d["source"]
    assert d["reduced"] == conf["reduced"] == [] and d["architecture"] is None
    for key in ("server", "series", "metric", "labels", "scrape_interval_ms",
                "fill_columns", "containers_per_scrape"):
        assert d[key] == raw[key], key
    assert d["data"] == "prom"
    stated = dict(d["guarantees"])
    assert stated.pop("stamps") == ("a sample is stored under the stamp it "
                                    "came with; a raw selector returns that "
                                    "stamp")
    assert stated == raw["guarantees"]
    assert {"stamp_law", "samples_per_series", "targets", "stream", "values",
            "scrape_ms"} <= set(d["assumed"])
    metrics = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("query_p50_ms", "kernel_roofline_pct", "leaf_ms"):
        assert metrics[name]["workloads"][-1] == "adhoc_prom"
    assert metrics["demoted_rows_pct"] == {
        "name": "demoted_rows_pct", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "fused kernel",
        "moves": "query_rate", "workloads": ["adhoc_prom"]}
    assert bench["per_layer"][-1]["name"] == "demoted_rows_pct"
    mix = traffic.load("adhoc")
    assert mix["expect_routes"] == ["fused"]
    # no query of the mix reaches past the head: the live scrapes' stamps
    # lie at or after their nominal ones
    gen = traffic.Generator(mix, 5, BASE + 720 * IV)
    assert all(r.end_ms <= BASE + 720 * IV for r in gen.warmup())
