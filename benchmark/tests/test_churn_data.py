"""The ``churn`` data module (``benchmark/data/churn/``): ``counter``'s metric
scraped off a fleet that redeploys at a public benchmark's rate.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` (tier-1
collects these too: ``tests/test_benchmark_churn_data.py``).

- the update law (births, ends, the seeded draws) from numpy, ``jax.numpy``
  and plain Python alike; its counts at the deployment's size;
- what is ``counter``'s is ``counter``'s: the same function objects;
- the plain reference tied series by series to ``tests/churn_reference.py``
  (stamps and values, brute force);
- a scrape's key fields change with a generation and are built once;
- the fill through the shard's own ingest against the same scrapes sent
  through the write path one by one, cell by cell, mirrors included; the
  probes answered by the store, and each probe ALONE missing when its fault
  is set; the controls' faults;
- the two readers; the configuration and the cell as ISSUE 49 names them (by
  membership, never a list's tail); the cell dry-added to a scratch copy
  and rehearsed there with every reader reporting.
"""

import argparse
import json
import os
import shutil
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
from tests import churn_reference as cr  # noqa: E402

CONFIG, CELL = "promdev_churn_1m", "adhoc_churn"
BASE, IV = 1_700_000_000_000, 10_000
with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
    FULL = json.load(f)


def small(slots=3328, per=64, rows=4096, **kw) -> dict:
    """The deployment cut to a size a test holds, with room for its births:
    52 targets of 64 series, 1 target an event, 768 rows to spare."""
    d = json.loads(json.dumps(FULL))
    d["series"] = slots
    d["churn"]["series_per_target"] = per
    d["server"]["store"]["max_series_per_shard"] = rows
    d.update(kw)
    return d


@pytest.fixture(scope="module")
def ch():
    return data.load("churn")


# ---- the law ------------------------------------------------------------------

def test_the_law_at_the_deployments_size(ch):
    """ISSUE 49's numbers: 896 targets x 1,024 series, 9 targets an event,
    twelve events; 1,028,096 rows, 110,592 ended, 110,592 born late, of
    them 101,376 with samples a query can reach."""
    s = ch.law.Schedule(FULL, 2**31 + 49)
    p = s.plan
    assert (p.slots, p.per_target, p.targets, p.every, p.events,
            p.per_event, p.rows) == (917_504, 1024, 896, 60, 12, 9, 1 << 20)
    assert p.registered_by_fill == 1_018_880
    assert len(s.slot) == 1_028_096
    ended = s.end < np.iinfo(np.int32).max
    assert int(ended.sum()) == 110_592 == int((s.born > 0).sum())
    assert int(((s.born > 0) & (s.born < 720)).sum()) == 101_376
    assert sorted(np.unique(s.born).tolist()) == [60 * e for e in range(13)]
    assert int((~ended).sum()) == 917_504                 # alive at the head
    for e, drawn in enumerate(s.drawn, 1):
        assert len(drawn) == len(set(drawn.tolist())) == 9
        # one of every container's 112 targets, and a ninth
        assert set((drawn // 112).tolist()) == set(range(8))
    # a revision's series never share a sample with the one before it
    order = np.lexsort((s.rev, s.slot))
    same = s.slot[order][1:] == s.slot[order][:-1]
    assert (s.end[order][:-1][same] == s.born[order][1:][same]).all()
    assert s.series_id.max() < 2**32 and len(set(s.series_id.tolist())) \
        == len(s.slot)
    assert ch.born_late_share(2**31 + 49, FULL, [0]) == pytest.approx(
        100 * 110_592 / 2**20)
    assert ch.born_late_share(2**31 + 49, FULL, [3_600_000]) == \
        pytest.approx(100 * 55_296 / 2**20)


@pytest.mark.parametrize("seed", (0, 7, 2**31 + 12345, 2**33 + 1))
def test_the_draws_in_numpy_jax_and_plain_python(ch, seed):
    import filodb_tpu  # noqa: F401 — x64 on
    import jax.numpy as jnp
    d = small()
    p = ch.law.plan(d)
    for e in (1, 5, 12):
        host = ch.law.draw(p, seed, e)
        dev = ch.law.draw(p, seed, e, jnp)
        assert host.tolist() == dev.tolist()
        # plain Python: the least (score, target) — of every container's
        # targets first where an event draws as many as there are
        # containers, else of all
        key = sorted((cr.score_py(seed, t, e), t) for t in range(p.targets))
        assert p.per_event < p.containers
        assert host.tolist() == sorted(t for _s, t in key[:p.per_event])
    full = ch.law.plan(FULL)
    host = ch.law.draw(full, seed, 3)
    per_c = 112
    first = [min(range(j * per_c, (j + 1) * per_c),
                 key=lambda t: (cr.score_py(seed, t, 3), t))
             for j in range(8)]
    rest = min((t for t in range(full.targets) if t not in first),
               key=lambda t: (cr.score_py(seed, t, 3), t))
    assert host.tolist() == sorted(first + [rest])


def test_a_deployment_without_room_revises_fewer_targets_down_to_none(ch):
    """The CPU rehearsal's ``shrink`` leaves no row to spare: the law then
    revises none, and says so."""
    assert ch.law.plan(small(4096, 1024, 4096)).per_event == 0
    assert ch.law.plan(small(3328, 64, 4096)).per_event == 1
    assert ch.law.plan(small(3328, 64, 3328 + 12 * 64 - 1)).per_event == 0
    with pytest.raises(ValueError, match="whole number of targets"):
        ch.law.plan(small(3329, 64, 8192))
    s = ch.law.Schedule(small(4096, 1024, 4096), 3)
    assert not (s.born > 0).any() and len(s.slot) == 4096


def test_values_are_counters_of_a_series_own_id_and_age(ch):
    d, seed = small(), 2**31 + 5
    s = ch.law.schedule(d, seed)
    rows = np.concatenate([np.arange(5), np.flatnonzero(s.born > 0)[:70]])
    cols = np.arange(0, 720, 37)
    got = s.values(seed, rows, cols)
    for i, r in enumerate(rows):
        for j, k in enumerate(cols):
            if s.born[r] <= k < s.end[r]:
                assert got[i, j] == cr.counter_py(
                    seed, int(s.series_id[r]), int(k - s.born[r]))
            else:
                assert np.isnan(got[i, j])
    assert np.nanmax(got) < 2**24                       # exact in f32


# ---- what is counter's is counter's -----------------------------------------------

def test_counters_functions_are_the_same_objects(ch):
    counter = data.load("counter")
    assert ch.scrape_ms is counter.scrape_ms and ch.schema is counter.schema
    assert ch.datagen is counter.datagen
    assert ch.kernelbytes is counter.kernelbytes
    assert ch.reference.base is counter.reference
    assert ch.reference.base.per_series is counter.reference.per_series
    out = BASE + np.arange(700, 720, 2) * IV
    ref = {"window_s": 300, "fn": "rate", "agg": "sum", "by": []}
    assert ch.query_bytes(1 << 20, ref, out, FULL, 720, 768) == \
        counter.query_bytes(1 << 20, ref, out, FULL, 720, 768)
    for f in ("fill", "check_filled", "landed", "evaluate", "raw_values",
              "probes", "series_labels", "scrape"):
        assert getattr(ch, f) is not getattr(counter, f), f


# ---- the plain reference ----------------------------------------------------------

@pytest.mark.parametrize("fn", ["rate", "increase", "sum_over_time",
                                "avg_over_time", "count_over_time"])
def test_reference_series_by_series_against_stamps_and_values(ch, fn):
    d, seed, head = small(), 11, 719
    s = ch.law.schedule(d, seed)
    late = np.flatnonzero(s.born > 0)
    ended = np.flatnonzero(s.end < 720)
    rows = np.unique(np.concatenate([np.arange(3), late[::97], ended[::89]]))
    out = BASE + np.arange(20, 720, 23) * IV + 4321
    mine = ch.reference.per_series(s, seed, fn, rows, out, 300_000, IV, head)
    for i, r in enumerate(rows):
        last = min(int(s.end[r]) - 1, head)
        k = np.arange(int(s.born[r]), last + 1)
        ts, v = BASE + k * IV, s.values(seed, [r], k)[0]
        gold = cr.range_fn(fn, ts, v, out, 300_000)
        np.testing.assert_allclose(mine[i], gold, rtol=1e-12,
                                   err_msg=f"{fn} row {r}")
        j = len(out) // 2
        brute = cr.window_brute(fn, ts, v, int(out[j]), 300_000)
        assert (np.isnan(brute) and np.isnan(mine[i, j])) or \
            mine[i, j] == pytest.approx(brute, rel=1e-12)


@pytest.mark.parametrize("agg,fn,by", [("sum", "rate", []),
                                       ("sum", "rate", ["g"]),
                                       ("avg", "avg_over_time", []),
                                       ("stddev", "sum_over_time", [])])
def test_evaluate_is_the_per_series_answers_aggregated(ch, agg, fn, by):
    d, seed, head = small(832, 64, 1024 + 768), 5, 720
    s = ch.law.schedule(d, seed)
    out = BASE + np.arange(660, 721, 4) * IV
    ref = {"agg": agg, "fn": fn, "window_s": 300, "by": by}
    got = ch.evaluate(seed, np.arange(832), ref, out, d, head)
    rows = np.arange(len(s.slot))
    per = ch.reference.per_series(s, seed, fn, rows, out, 300_000, IV, head)
    keys = [f"g{int(x) % 8}" if by else "" for x in s.slot]
    want = cr.aggregate(agg, list(per), keys)
    assert {k[0][1] if k else "" for k in got} == set(want)
    for k, v in got.items():
        np.testing.assert_allclose(v, want[k[0][1] if k else ""], rtol=1e-9)


# ---- a scrape -----------------------------------------------------------------

def test_a_scrape_carries_key_fields_from_the_first_event_on(ch):
    d, seed = small(), 2**31 + 7
    s = ch.law.schedule(d, seed)
    ids = np.arange(0, 416)                       # one container of eight
    plain = ch.scrape(seed, ids, 59, d)
    assert set(plain) == {"ts", "values"}
    hit = [e for e in range(1, 13) if (s.drawn[e - 1] < 416 // 64).any()]
    k = 60 * hit[0]
    sc = ch.scrape(seed, ids, k, d)
    assert {"part_hash", "part_idx", "label_sets", "part_keys",
            "set_hashes", "label_columns"} <= set(sc)
    assert (sc["ts"] == BASE + k * IV).all() and len(sc["values"]) == 416
    tgt = int(s.drawn[hit[0] - 1][s.drawn[hit[0] - 1] < 416 // 64][0])
    sets = sc["label_sets"]
    new = [sets[i] for i in sc["part_idx"][tgt * 64:(tgt + 1) * 64]]
    assert {ls["revision"] for ls in new} == {"1"}
    assert {ls["instance"] for ls in new} == {f"t{tgt}"}
    # a new series' counter starts over: its age is 0 at its birth
    rows = s.rows_at(ids, k)
    assert (sc["values"] == s.values(seed, rows, [k])[:, 0]).all()
    assert sc["values"][tgt * 64] == cr.counter_py(
        seed, int(s.series_id[rows[tgt * 64]]), 0)
    # built once a generation, kept
    again = ch.scrape(seed, ids, k + 1, d)
    assert again["part_keys"] is sc["part_keys"]
    assert again["label_sets"] is sc["label_sets"]


# ---- the fill, the checks, the probes --------------------------------------------

def _shard(d):
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    ms = TimeSeriesMemStore()
    st = d["server"]["store"]
    shard = ms.setup("prometheus", data.load("churn").schema(), 0, StoreConfig(
        max_series_per_shard=st["max_series_per_shard"],
        samples_per_series=st["samples_per_series"],
        flush_batch_size=10**9, dtype="float32"))
    return ms, shard


def _register(ch, shard, d, seed):
    import dataclasses
    from filodb_tpu.core.record import RecordBuilder
    ids = np.arange(d["series"])
    b = RecordBuilder(ch.schema())
    b.add_series_batch(ch.series_labels(ids, d), ch.scrape_ms(0, d), 0.0)
    tmpl = b.build()
    shard.ingest(dataclasses.replace(tmpl, **ch.scrape(seed, ids, 0, d)))
    shard.flush()
    sid = np.full(shard.store.S, -1, np.int64)
    sid[:len(ids)] = ids
    return tmpl, sid


@pytest.fixture(scope="module")
def filled(ch):
    """A small deployment filled as ``served.build`` fills it, the first
    live scrape sent after, and its twin: every scrape through the write
    path, one by one."""
    import dataclasses
    d, seed = small(512, 8, 512 + 8 * 12, fill_columns=720), 2**31 + 49
    d["server"]["store"]["samples_per_series"] = 768
    _ms, a = _shard(d)
    tmpl, sid = _register(ch, a, d, seed)
    ch.fill(a, sid, seed, d)
    ch.check_filled(a, sid, d)
    ids = np.arange(d["series"])
    a.ingest(dataclasses.replace(tmpl, **ch.scrape(seed, ids, 720, d)))
    a.flush()
    _ms2, w = _shard(d)
    tmpl_w, _sid = _register(ch, w, d, seed)
    for k in range(1, 721):
        w.ingest(dataclasses.replace(tmpl_w, **ch.scrape(seed, ids, k, d)))
        w.flush()
    return d, seed, a, w, sid


def test_fill_against_the_write_path_cell_by_cell(ch, filled):
    d, seed, a, w, _sid = filled
    A, W = a.store, w.store
    assert a.num_series == w.num_series == 512 + 12 * 8
    for name in ("n_host", "born", "first_ts", "last_ts", "line0"):
        np.testing.assert_array_equal(getattr(A, name), getattr(W, name),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(A.n), np.asarray(W.n))
    np.testing.assert_array_equal(np.asarray(A.born_dev),
                                  np.asarray(W.born_dev))
    np.testing.assert_array_equal(np.asarray(A.ts), np.asarray(W.ts))
    np.testing.assert_array_equal(np.asarray(A.val), np.asarray(W.val))
    assert A.born_late == W.born_late == 12 * 8
    assert A.grid_cohorts() == W.grid_cohorts() == ("uniform", 0)
    assert A.births == W.births == {"aligned": 96, "minority": 0}
    assert A.stats.samples_appended == W.stats.samples_appended
    # the index's start times are the write path's own
    s = ch.law.schedule(d, seed)
    for r in (0, 512, 512 + 8 * 5, 512 + 8 * 11 + 3):
        assert a.index.start_time(r) == BASE + int(s.born[r]) * IV
        assert a.index.labels_of(r)["revision"] == str(int(s.rev[r]))
    # landed follows a slot to the row of its current generation
    slots = np.arange(512)
    assert ch.landed(a, slots, 720).all() and not ch.landed(a, slots, 721).any()
    moved = s.rows_at(slots, 720) != slots
    assert moved.sum() >= 8 and (A.n_host[slots[moved]] <= 720).all()


def test_check_filled_names_what_is_off(ch, filled):
    d, _seed, a, _w, sid = filled
    st = a.store
    for field, word in (("born", "born on the host"), ("n_host", "n_host")):
        kept = getattr(st, field).copy()
        getattr(st, field)[520] += 1
        try:
            with pytest.raises(RuntimeError, match=word):
                ch.check_filled(a, sid, d)
        finally:
            setattr(st, field, kept)
    kept, st.aligned = st.aligned, False
    try:
        with pytest.raises(RuntimeError, match="grid form"):
            ch.check_filled(a, sid, d)
    finally:
        st.aligned = kept


def _answer(eng, p):
    """A probe's answer as correct.readback compares it: worst |got-want|,
    inf where a wanted series is not there exactly once."""
    r = eng.query_range(p["promql"], p["start_ms"], p["end_ms"],
                        p["step_ms"])
    out = np.arange(p["start_ms"], p["end_ms"] + 1, p["step_ms"])
    got = []
    for k, t, v in r.matrix.to_host().iter_series():
        row = np.full(len(out), np.nan)
        row[np.searchsorted(out, np.asarray(t, np.int64))] = v
        got.append((set(k.as_dict().items()) - {("_metric_", "m")}, row))
    worst = 0.0
    for labels, want in p["want"]:
        mine = [v for k, v in got if set(labels.items()) <= k]
        if len(mine) != 1:
            return float("inf"), r.exec_path
        dlt = np.abs(mine[0] - want)
        worst = max(worst, float(dlt.max()) if np.isfinite(dlt).all()
                    else float("inf"))
    return worst, r.exec_path


def test_probes_read_back_exactly_and_each_alone_misses_its_fault(ch, filled):
    """The sound store answers every probe with 0. Then one fault at a
    time: (born) the fused program never hears of a birth cell, (ended) it
    is handed every row as if it reached the head — the count probe (c)
    alone misses, the store's own probes (a), (b) read right; (leak) a new
    revision's first cell moved one scrape early in the STORE — (a)'s count
    before the event misses; (past) an old revision given one sample past
    its edge — (a)'s values and stamps miss."""
    from benchmark import control_births
    from filodb_tpu.ops import fusedgrid
    from filodb_tpu.query.engine import QueryEngine
    d, seed, a, _w, _sid = filled
    eng = QueryEngine(_ms_of(a), "prometheus")
    ids = np.arange(512)           # every container's slots at once
    ps = ch.probes(seed, ids, 720, d, 2)
    kinds = [p["promql"].split("(")[0] if "(" in p["promql"] else "m"
             for p in ps]
    assert kinds == ["m", "timestamp", "count", "count by ", "count by ",
                     "sum"], [p["promql"] for p in ps]
    sound = [_answer(eng, p) for p in ps]
    assert [e for e, _ in sound] == [0.0] * 6, sound
    assert sound[-1][1].startswith("local-fused")       # the FUSED kernel's
    assert sound[0][1] == "local-gather"
    assert max(ps[-1]["want"][0][1]) < 2**24
    keep = fusedgrid.fused_grid_aggregate
    for fault in ("born", "ended"):
        try:
            control_births.break_the_fused_view(fault)
            got = [_answer(eng, p)[0] for p in ps]
        finally:
            fusedgrid.fused_grid_aggregate = keep
        assert got[:5] == [0.0] * 5 and got[5] > 0, (fault, got)
    st = a.store
    s = ch.law.schedule(d, seed)
    tgt = int(ps[0]["promql"].split('"t')[1].split('"')[0])
    new = [r for r in np.flatnonzero(s.born == 720) if s.slot[r] // 8 == tgt]
    old = [r for r in np.flatnonzero(s.end == 720) if s.slot[r] // 8 == tgt]
    with a.lock:
        val, n = st.val, st.n
        born, first = st.born.copy(), st.first_ts.copy()
        try:
            # (leak) a birth one scrape early — in the store (a gathered
            # row's stamps are derived from the host's first stamp and
            # birth cell) AND in the index, whose start time is what
            # keeps a series out of a query that ends before it
            st.val = val.at[new[0], 719].set(1.0)
            st.born[new[0]], st.first_ts[new[0]] = 719, BASE + 719 * IV
            got = [_answer(eng, p)[0] for p in ps[:3]]
            assert got == [0.0] * 3, got            # the index alone holds
            a.index._start[int(new[0])] = BASE + 719 * IV
            got = [_answer(eng, p)[0] for p in ps[:3]]
            a.index._start[int(new[0])] = BASE + 720 * IV
            assert got[2] > 0, got
            st.val = val
            st.born[:], st.first_ts[:] = born, first
            # (past) a sample past an old revision's edge
            st.val = val.at[old[0], 720].set(5.0)
            st.n = n.at[old[0]].set(721)
            got = [_answer(eng, p)[0] for p in ps[:3]]
            assert got[0] > 0 and got[1] > 0 and got[2] == 0.0, got
        finally:
            st.val, st.n = val, n
            st.born[:], st.first_ts[:] = born, first
    assert [_answer(eng, p)[0] for p in ps] == [0.0] * 6


def _ms_of(shard):
    """A memstore that holds ``shard`` as its dataset's shard 0: what an
    engine over it asks for."""
    from filodb_tpu.core.memstore import TimeSeriesMemStore
    ms = TimeSeriesMemStore()
    ms._shards[("prometheus", 0)] = shard
    return ms


def test_a_program_without_birth_cells_is_refused_when_the_module_loads(
        monkeypatch):
    from filodb_tpu.core import chunkstore
    monkeypatch.delattr(chunkstore.SeriesStore, "born_dev")
    monkeypatch.delitem(sys.modules, "benchmark.data.churn", raising=False)
    with pytest.raises(SystemExit, match="SeriesStore.born_dev"):
        data.load("churn")
    monkeypatch.undo()
    sys.modules.pop("benchmark.data.churn", None)
    assert data.load("churn").law is not None


# ---- the readers ----------------------------------------------------------------

def sp(name, trace, **tags):
    return {"name": name, "trace_id": trace, "t0": 1.0, "dur_s": 0.01,
            "tags": tags}


def test_born_late_rows_pct_reads_the_dispatch_spans(ch):
    read = load_layer("born_late_rows_pct").read
    spans = [sp("query.exec.kernel", "a", phase="dispatch", rows=1 << 20,
                births=1, born_late=101_376),
             sp("query.exec.kernel", "a", phase="fetch"),
             sp("query.exec.kernel", "b", phase="dispatch", rows=1 << 20,
                births=1, born_late=55_296),
             sp("query.exec.select", "a", demoted=0)]
    assert read({"spans": spans}) == pytest.approx(
        100 * (101_376 + 55_296) / 2**21)
    # against the module's law for such a deck: a head card and one an
    # hour back
    assert read({"spans": spans}) == pytest.approx(
        ch.born_late_share(2**31 + 49, FULL, [0, 3_600_000]), abs=0.5)
    none_late = [sp("query.exec.kernel", "a", phase="dispatch", rows=4096,
                    births=0, born_late=0)]
    assert read({"spans": none_late}) == 0.0
    # the parent commit's dispatch span carries no such tag
    old = [sp("query.exec.kernel", "a", phase="dispatch", rows=1 << 20)]
    assert read({"spans": old}) is None and read({"spans": []}) is None


def test_the_laws_share_for_the_decks_own_cards(ch):
    """What ``born_late_rows_pct`` has to read in ``adhoc_churn``: the
    mix's cards end a start phase BEFORE the head or the hour mark, so a
    head card selects eleven events' births (the twelfth's series start at
    the head's own stamp) and a card an hour back five; three cards in five
    end at the head: (3 x 101,376 + 2 x 46,080) / 5 of 2^20 rows."""
    mix = traffic.load("adhoc")
    head = ch.scrape_ms(720, FULL)
    for seed in (5, 2**31 + 4001):
        gen = traffic.Generator(mix, seed, head)
        back = [head - gen.next(c % 8).end_ms for c in range(200)]
        assert min(back) > 0
        assert ch.born_late_share(seed, FULL, back) == pytest.approx(
            100 * (3 * 101_376 + 2 * 46_080) / 5 / 2**20)


def test_time_mask_selects_pct_reads_the_selects(ch):
    read = load_layer("time_mask_selects_pct").read
    spans = [sp("query.exec.select", "a", memo="miss", memo_why="time_mask"),
             sp("query.exec.select", "b", memo="bypass", memo_why="narrow"),
             sp("query.exec.select", "c", memo="hit"),
             sp("query.exec.select", "d", memo="bypass", memo_why="time_mask")]
    assert read({"spans": spans}) == 50.0
    assert read({"spans": spans[2:3]}) == 0.0
    assert read({"spans": [sp("query.exec.select", "a")]}) is None
    assert read({"spans": []}) is None


# ---- the files ISSUE 49 names -----------------------------------------------------

def test_churn_configuration_and_cell_are_as_named():
    """``promdev_churn_1m`` x ``adhoc``: entries appended after the ones
    that were there, which stay as they were — by membership and order,
    never a list's tail."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    confs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    conf, cell = confs[CONFIG], cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "adhoc", 1)
    assert len(cell["why"]) <= 200 and "ONE fused program" in cell["why"]
    for entries, first, then in (
            (bench["configs"], confs["promdev_raw_1m"], conf),
            (bench["workloads"], cells["adhoc_cold"], cell)):
        assert entries.index(first) < entries.index(then)
    d = FULL
    with open(os.path.join(BENCH, "configs", "promdev_raw_1m.json")) as f:
        raw = json.load(f)
    assert d["source"] == conf["source"] and len(d["source"]) <= 200
    for word in ("prometheus-benchmark", "scrapeInterval 10s",
                 "scrapeConfigUpdatePercent 1",
                 "scrapeConfigUpdateInterval 10m",
                 "timeseries-dev-source.conf"):
        assert word in d["source"], word
    assert d["source"] != raw["source"]
    assert d["reduced"] == conf["reduced"] == [] and d["architecture"] is None
    assert d["server"] == raw["server"]
    for key in ("metric", "labels", "scrape_interval_ms", "fill_columns",
                "containers_per_scrape"):
        assert d[key] == raw[key], key
    assert d["series"] == 896 * 1024 == 917_504 and d["data"] == "churn"
    assert d["churn"] == {"series_per_target": 1024,
                          "update_interval_ms": 600_000, "update_percent": 1}
    stated = dict(d["guarantees"])
    assert stated.pop("births").startswith(
        "a series exists from its first sample to its last")
    assert stated == raw["guarantees"]
    for key in ("series_per_target", "targets", "targets_per_event", "draw",
                "labels", "values", "stamps", "window"):
        assert key in d["assumed"], key
    assert "drawn again" in d["assumed"]["draw"]
    metrics = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("query_p50_ms", "leaf_ms", "kernel_roofline_pct",
                 "groupids_mean_ms", "kernel_host_mean_ms",
                 "device_ahead_mean", "demoted_rows_pct"):
        assert CELL in metrics[name]["workloads"], name
    assert metrics["born_late_rows_pct"] == {
        "name": "born_late_rows_pct", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "fused kernel",
        "moves": "query_rate", "workloads": [CELL]}
    assert metrics["time_mask_selects_pct"] == {
        "name": "time_mask_selects_pct", "unit": "%", "better": "lower",
        "source": "program_span", "layer": "leaf under the shard lock",
        "moves": "query_rate", "workloads": [CELL]}
    mix = traffic.load("adhoc")
    assert mix["expect_routes"] == ["fused"]
    gen = traffic.Generator(mix, 5, BASE + 720 * IV)
    assert all(r.end_ms <= BASE + 720 * IV for r in gen.warmup())
    for f in ("data/churn/__init__.py", "data/churn/law.py",
              "data/churn/fill.py", "data/churn/probes.py",
              "data/churn/reference.py", "layers/born_late_rows_pct.py",
              "layers/time_mask_selects_pct.py", "control_births.py",
              f"configs/{CONFIG}.json"):
        assert os.path.isfile(os.path.join(BENCH, f)), f


# ---- the cell, dry-added and rehearsed -------------------------------------------

def test_churn_cell_is_dry_added_to_a_scratch_copy_and_rehearsed_there(
        tmp_path, ch, monkeypatch):
    """What this PR adds, laid as new files over a copy of the by-name
    files WITHOUT them, with its entries appended to a BENCHMARK.json
    without them (``rehearse.dry_add``; it copies files, so the data
    module, a package, lies in the copy already): nothing that exists is
    edited. The configuration is cut to 52 targets of 64 series in 4,096
    rows (the rehearsal's own ``shrink`` leaves no row to spare for a
    birth). Then the whole of ``run.run`` from there, traced: correct, one
    fused program a query, every reader reporting. A selection of some
    4,000 series is narrower than the program's gather and the selection
    memo would keep none: the gather's threshold is lowered for the run, so
    that the deck's selections are kept for their spans of ranges as the
    full size's are."""
    from benchmark import rehearse
    from filodb_tpu.query import exec as qexec
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", 512)
    mine = {"configs": [f"{CONFIG}.json"], "traffic": [],
            "data": ["churn"],
            "layers": ["born_late_rows_pct.py", "time_mask_selects_pct.py"]}
    parent, add = tmp_path / "parent", tmp_path / "add"
    for dname in rehearse.BY_NAME:
        shutil.copytree(os.path.join(BENCH, dname), parent / dname,
                        ignore=shutil.ignore_patterns("__pycache__",
                                                      *mine[dname]))
        (add / dname).mkdir(parents=True)
        for f in mine[dname]:
            src = os.path.join(BENCH, dname, f)
            if os.path.isdir(src):      # dry_add copies files: lay it beside
                shutil.copytree(src, parent / dname / f,
                                ignore=shutil.ignore_patterns("__pycache__"))
            else:
                shutil.copy(src, add / dname / f)
    with open(add / "configs" / f"{CONFIG}.json", "w") as f:
        json.dump(small(), f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {"configs": [c for c in bench["configs"] if c["name"] == CONFIG],
               "workloads": [w for w in bench["workloads"]
                             if w["name"] == CELL]}
    with open(add / "entries.json", "w") as f:
        json.dump(entries, f)
    for kind in ("configs", "workloads"):
        bench[kind] = [e for e in bench[kind] if e not in entries[kind]]
    real_here, real_root = rehearse.HERE, rehearse.ROOT
    rehearse.HERE, rehearse.ROOT = str(parent), str(tmp_path / "parent_root")
    os.makedirs(rehearse.ROOT)
    with open(os.path.join(rehearse.ROOT, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    root = str(tmp_path / "root")
    try:
        assert rehearse.dry_add(str(add), root) == [CELL]
    finally:
        rehearse.HERE, rehearse.ROOT = real_here, real_root
    os.rename(os.path.join(root, "parent"), os.path.join(root, "benchmark"))
    from benchmark import run as runmod
    seed = 2**31 + 49
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=3.0, trace=1)
    stub = {"platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1}
    res = runmod.run(args, stub, allow_interpret=True, root=root)
    assert res is not None and res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 20
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["demoted_rows_pct"] == 0.0
    # the warm-up's deck paid the time-masked pass once a span of ranges;
    # the window's selects are the memo's (a card whose phase is a whole
    # step ends ON an event and opens a span of its own: one in 15,000)
    assert got["time_mask_selects_pct"] < 10.0
    # what the law gives for the deck: the head cards select every birth
    # (12 x 64 rows of 4,096), the cards an hour back six events' worth
    head = ch.born_late_share(seed, small(), [0])
    back = ch.born_late_share(seed, small(), [3_600_000])
    assert head == pytest.approx(100 * 768 / 4096)
    assert back < got["born_late_rows_pct"] <= head
    assert {"leaf_ms", "groupids_mean_ms",
            "kernel_host_mean_ms", "device_ahead_mean", "select_mean_ms",
            "lock_hold_mean_ms", "born_late_rows_pct"} <= set(got)
