"""Series that are born late and end, on a store in time-aligned cells
(core/chunkstore.py ``aligned``): ONE start cohort, ONE fused program in
its births mode, no row through the general kernels for its birth or its
end. Against ``tests/churn_reference.py`` (numpy f64 and plain Python,
nothing of the program), and against the answers of the parent commit's
tree for the same fleet (``tests/fixtures/births_parent.json``, written by
``tests/births_scenario.py`` run from that tree).

Tolerances, and why: the store is f32 and the fused kernel's arithmetic is
f32 (an extrapolated rate, a mean over 300 cells), the reference f64: the
configurations' ``rtol`` 2e-4 / ``atol`` 1e-4, the tier-1 bound
(``promdev_churn_1m.guarantees``). COUNTS are integers below 2^24, exact in
f32 in any order of the fold: compared exactly.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from filodb_tpu.core.chunkstore import TS_PAD, TS_UNBORN, SeriesStore
from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE
from filodb_tpu.ops import fusedresident
from filodb_tpu.query import exec as qexec
from filodb_tpu.query.engine import QueryConfig, QueryEngine
from filodb_tpu.utils.tracing import (SPAN_QUERY_GATHER, SPAN_QUERY_KERNEL,
                                      tracer)

from . import births_scenario as sc
from . import churn_reference as cr

RTOL, ATOL = 2e-4, 1e-4
HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def fleet():
    ms, shard, eng = sc.build()
    series = [cr.Series(i, 0, born, end, v)
              for i, ((born, end), v) in enumerate(zip(sc.LIVES, sc.values()))]
    return ms, shard, eng, series


@pytest.fixture(autouse=True)
def _tracing_on():
    was = tracer.enabled, tracer.sample_rate
    tracer.enabled, tracer.sample_rate = True, 1.0
    tracer.drain()
    yield
    tracer.enabled, tracer.sample_rate = was


def steps(name):
    start, end, step = sc.out_ts(name)
    return np.arange(start, end + 1, step, dtype=np.int64)


def want(series, agg, fn, by, out, head=sc.HEAD - 1):
    rows = [cr.range_fn(fn, *s.samples(head, sc.BASE, sc.IV), out, 300_000)
            for s in series]
    keys = [f"g{s.slot % sc.GROUPS}" if by else "" for s in series]
    return cr.aggregate(agg, rows, keys)


def close(got, ref, exact=False):
    assert set(got) == set(ref), (sorted(got), sorted(ref))
    for k in ref:
        g, w = np.asarray(got[k], np.float64), ref[k]
        assert (np.isnan(g) == np.isnan(w)).all(), k
        if exact:
            np.testing.assert_array_equal(g, w, err_msg=str(k))
        else:
            np.testing.assert_allclose(g, w, rtol=RTOL, atol=ATOL,
                                       equal_nan=True, err_msg=str(k))


def agg_of(op, rows):
    """One group, keyed as an answer without ``by`` is: ``""``."""
    return cr.aggregate(op, rows, [""] * len(rows))


# -- (a) the store, filled through append alone ------------------------------------

def test_a_store_filled_through_append_alone_keeps_every_row_in_its_cells():
    """Births at five cells, two ends, a freed and reused slot and a
    compaction: one cohort, the stamps of every row as sent, the cells
    before a birth marked, and a row's own samples out of
    ``series_snapshot``."""
    st = SeriesStore(16, 64)
    lives = {0: (0, None), 1: (0, None), 2: (0, 20), 3: (4, None),
             4: (9, 30), 5: (17, None), 6: (26, None)}
    sent = {r: [] for r in lives}

    def scrape(k, rows):
        rows = np.asarray(rows, np.int32)
        v = rows * 1000.0 + k
        st.append(rows, np.full(len(rows), sc.BASE + k * sc.IV), v)
        for r, x in zip(rows.tolist(), v.tolist()):
            sent[r].append((sc.BASE + k * sc.IV, x))

    def check(offset=0):
        assert st.grid_cohorts() == ("uniform", offset)
        assert st.grid_info() == (sc.BASE, sc.IV)
        T, V = np.asarray(st.ts), np.asarray(st.val)
        for r, rows in sent.items():
            rows = [x for x in rows if x[0] >= sc.BASE + offset * sc.IV]
            b, n = int(st.born[r]), int(st.n_host[r])
            assert n - b == len(rows), (r, b, n)
            assert (T[r, :b] == TS_UNBORN).all() and (V[r, :b] == 0).all()
            assert (T[r, n:] == TS_PAD).all()
            assert T[r, b:n].tolist() == [x[0] for x in rows]
            assert V[r, b:n].tolist() == [x[1] for x in rows]
            t, v = st.series_snapshot(r)
            assert t.tolist() == [x[0] for x in rows]
            assert v.tolist() == [x[1] for x in rows]
        assert (np.asarray(st.born_dev) == st.born).all()
        assert (np.asarray(st.n) == st.n_host).all()
        assert st.born_late == int((st.born > 0).sum())

    for k in range(34):
        scrape(k, [r for r, (b, e) in lives.items()
                   if b <= k < (34 if e is None else e)])
    assert st.born[:7].tolist() == [0, 0, 0, 4, 9, 17, 26]
    assert st.births == {"aligned": 4, "minority": 0}
    check()
    # the slot of row 2 (ended at 20) is freed and taken by a new series:
    # it starts at ITS birth cell, and nothing the old owner left is there
    st.free_rows(np.array([2]))
    sent[2] = []
    for k in range(34, 40):
        scrape(k, [0, 1, 2, 3, 5, 6])
    assert st.born[2] == 34 and st.first_ts[2] == sc.BASE + 34 * sc.IV
    check()
    st.compact(sc.BASE + 12 * sc.IV)
    assert st.born[:7].tolist() == [0, 0, 22, 0, 0, 5, 14]
    check(12)
    for k in range(40, 44):
        scrape(k, [0, 1, 2, 3, 5, 6])
    check(12)


def test_a_raw_selector_returns_each_series_own_samples_and_stamps(fleet):
    """(a): ``m{..}`` and ``timestamp(m{..})`` of late-born and ended
    series, exactly, from the served path."""
    _ms, shard, eng, series = fleet
    st = shard.store
    assert st.grid_cohorts() == ("uniform", 0) and st.res is None
    assert st.born[:len(sc.LIVES)].tolist() == [b for b, _e in sc.LIVES]
    assert st.born_late == sum(b > 0 for b, _e in sc.LIVES)
    T = np.asarray(st.ts)
    for s in series:
        t, _v = s.samples(sc.HEAD - 1, sc.BASE, sc.IV)
        n = int(st.n_host[s.slot])
        assert n == s.born + len(t)
        assert T[s.slot, s.born:n].tolist() == t.tolist()
    pick = [31, 35, 42, 44, 51, 53]           # ended, born late, both
    sel = "|".join(f"h{i}" for i in pick)
    out = np.arange(sc.BASE + 290 * sc.IV, sc.BASE + 715 * sc.IV, 17 * sc.IV)
    for text, stamps in ((f'm{{host=~"{sel}"}}', False),
                         (f'timestamp(m{{host=~"{sel}"}})', True)):
        r = eng.query_range(text, int(out[0]), int(out[-1]), 17 * sc.IV)
        got = {}
        for k, t, v in r.matrix.to_host().iter_series():
            row = np.full(len(out), np.nan)     # a rendered series drops
            row[np.searchsorted(out, np.asarray(t, np.int64))] = v  # its NaNs
            got[k.as_dict()["host"]] = row
        for i in pick:
            v, at = cr.instant(*series[i].samples(sc.HEAD - 1, sc.BASE,
                                                  sc.IV), out)
            ref = np.where(at >= 0, at / 1000.0, np.nan) if stamps else v
            if np.isnan(ref).all():
                assert f"h{i}" not in got
                continue
            np.testing.assert_array_equal(got[f"h{i}"], ref, err_msg=text)


# -- (b) the fused program, both backends, against the reference ------------------

@pytest.mark.parametrize("variant", ["pallas", "xla"])
@pytest.mark.parametrize("rname", list(sc.RANGES))
@pytest.mark.parametrize("name", list(sc.TEXTS))
def test_one_fused_program_answers_rows_born_at_six_cells(fleet, name, rname,
                                                          variant):
    _ms, _shard, eng, series = fleet
    text, agg, fn, by = sc.TEXTS[name]
    old = fusedresident.mode()
    fusedresident.set_mode(variant)
    try:
        tracer.drain()
        r = eng.query_range(text, *sc.out_ts(rname))
        spans = tracer.drain()
    finally:
        fusedresident.set_mode(old)
    tag = "pallas-interpret" if variant == "pallas" else "xla"
    assert r.exec_path == f"local-fused[{tag}]", r.exec_path
    disp = [s for s in spans if s.name == SPAN_QUERY_KERNEL
            and s.tags.get("phase") == "dispatch"]
    assert len(disp) == 1                     # ONE program, whatever the births
    assert disp[0].tags["births"] == 1 and disp[0].tags["stamps"] == "grid"
    sel = [s for s in spans if s.name == "query.exec.select"]
    assert [s.tags["demoted"] for s in sel] == [0]
    close(sc.by_group(r, steps(rname)), want(series, agg, fn, by, steps(rname)),
          exact=fn == "count_over_time")


# -- (c) the parent's answers ---------------------------------------------------

@pytest.mark.parametrize("rname", list(sc.RANGES))
@pytest.mark.parametrize("name", list(sc.TEXTS))
def test_the_parents_answers_for_the_same_fleet(fleet, name, rname):
    """Old and new agree: to the bit where the fold's order is the same
    (the counts; integer sums), within the tolerance elsewhere — the
    parent answered its minority through the general kernels in f64-ish
    arithmetic and folded it in after the kernel's rows."""
    _ms, _shard, eng, _series = fleet
    with open(os.path.join(HERE, "fixtures", "births_parent.json")) as f:
        gold = json.load(f)["answers"][name][rname]
    r = eng.query_range(sc.TEXTS[name][0], *sc.out_ts(rname))
    got = sc.by_group(r, steps(rname))
    ref = {k: np.asarray(v, np.float64) for k, v in gold.items()}
    close(got, ref, exact=sc.TEXTS[name][2] == "count_over_time")


# -- (d) a reused slot ------------------------------------------------------------

def test_a_reused_slots_old_owner_is_not_read_under_a_range_over_both():
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", GAUGE, 0, StoreConfig(
        max_series_per_shard=8, samples_per_series=128,
        flush_batch_size=10**9, dtype="float32"))
    eng = QueryEngine(ms, "prometheus")
    lives = {"a": (0, None), "b": (0, None), "old": (0, 25), "c": (10, None)}
    val = {"a": 10.0, "b": 2000.0, "old": 50_000.0, "c": 7.0, "new": 3.0}
    series = {}

    def scrape(k, names):
        b = RecordBuilder(GAUGE)
        for nm in names:
            born = lives[nm][0]
            v = val[nm] + 3.0 * (k - born)
            b.add({"_metric_": "m", "host": nm}, sc.BASE + k * sc.IV, v)
            series.setdefault(nm, []).append(v)
        shard.ingest(b.build())
        shard.flush()

    for k in range(60):
        scrape(k, [nm for nm, (b, e) in lives.items()
                   if b <= k < (60 if e is None else e)])
    old_pid = int(shard.part_ids_from_filters(
        [__import__("filodb_tpu.core.filters", fromlist=["Equals"])
         .Equals("host", "old")], 0, 1 << 60)[0])
    assert shard.purge_expired_partitions(sc.BASE + 40 * sc.IV) == 1
    lives["new"] = (60, None)
    for k in range(60, 100):
        scrape(k, ["a", "b", "c", "new"])
    st = shard.store
    assert st.born[old_pid] == 60                 # the slot, reused
    assert st.first_ts[old_pid] == sc.BASE + 60 * sc.IV
    assert st.grid_cohorts() == ("uniform", 0)
    V = np.asarray(st.val)
    assert (V[old_pid, :60] == 0).all()           # nothing of the old owner
    out = np.arange(sc.BASE + 5 * sc.IV, sc.BASE + 99 * sc.IV, 4 * sc.IV)
    refs = [cr.Series(i, 0, lives[nm][0], None, np.asarray(series[nm]))
            for i, nm in enumerate(("a", "b", "c", "new"))]
    for variant in ("pallas", "xla"):
        fusedresident.set_mode(variant)
        try:
            for text, agg, fn in (("sum(rate(m[5m]))", "sum", "rate"),
                                  ("sum(count_over_time(m[5m]))", "sum",
                                   "count_over_time"),
                                  ("avg(avg_over_time(m[5m]))", "avg",
                                   "avg_over_time")):
                r = eng.query_range(text, int(out[0]), int(out[-1]),
                                    4 * sc.IV)
                assert r.exec_path.startswith("local-fused"), r.exec_path
                rows = [cr.range_fn(fn, *s.samples(99, sc.BASE, sc.IV), out,
                                    300_000) for s in refs]
                close(sc.by_group(r, out), agg_of(agg, rows),
                      exact=fn == "count_over_time")
        finally:
            fusedresident.set_mode("pallas")


# -- (e) the gathered leaf --------------------------------------------------------

@pytest.mark.parametrize("text,agg,fn", [
    ('sum(rate(m{{host=~"{sel}"}}[5m]))', "sum", "rate"),
    ('max(max_over_time(m{{host=~"{sel}"}}[1m]))', "max", "max_over_time"),
    ('sum(count_over_time(m{{host=~"{sel}"}}[5m]))', "sum",
     "count_over_time"),
    ('avg(m{{host=~"{sel}"}})', "avg", None),
])
def test_the_gathered_leaf_over_late_born_rows_is_one_program(fleet, text,
                                                              agg, fn):
    """(e): a narrow selection of rows born at four cells and one ended:
    ``local-gather``, ONE program a leaf (the rows come with their birth
    cells: ``grid_row_picks``), no minority."""
    _ms, shard, eng, series = fleet
    pick = [1, 34, 42, 41, 47, 54]
    sel = "|".join(f"h{i}" for i in pick)
    old = fusedresident.mode()
    fusedresident.set_mode("off")           # the leaf's own kernels
    try:
        tracer.drain()
        r = eng.query_range(text.format(sel=sel), *sc.out_ts("1h"))
        spans = tracer.drain()
    finally:
        fusedresident.set_mode(old)
    assert r.exec_path == "local-gather", r.exec_path
    assert [s.tags["programs"] for s in spans
            if s.name == SPAN_QUERY_GATHER] == [1]
    out = steps("1h")
    rows = []
    for i in pick:
        t, v = series[i].samples(sc.HEAD - 1, sc.BASE, sc.IV)
        if fn is None:
            rows.append(cr.instant(t, v, out)[0])
        elif fn == "max_over_time":
            lo = np.searchsorted(t, out - 60_000, side="left")
            hi = np.searchsorted(t, out, side="right")
            rows.append(np.array([v[a:b].max() if b > a else np.nan
                                  for a, b in zip(lo, hi)]))
        else:
            rows.append(cr.range_fn(fn, t, v, out, 300_000))
    if agg == "max":
        x = np.array(rows)
        ref = {(): np.where(np.isfinite(x).any(axis=0),
                            np.nanmax(np.where(np.isfinite(x), x, -np.inf),
                                      axis=0), np.nan)}
        ref = {"": ref[()]}
    else:
        ref = agg_of(agg, rows)
    close(sc.by_group(r, out), ref,
          exact=fn in ("count_over_time", "max_over_time"))


# -- (f) the fragment cache's one-step extension across a birth -------------------

def test_the_fragment_caches_extension_crosses_a_birth():
    """``dash``'s path: a refresh one step on extends the cached entry by
    the step; a series born in that step is in the new step's answer and
    in no older one."""
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", GAUGE, 0, StoreConfig(
        max_series_per_shard=16, samples_per_series=256,
        flush_batch_size=10**9, dtype="float32"))
    eng = QueryEngine(ms, "prometheus",
                      config=QueryConfig(fragment_cache_size=16))
    vals = {i: [] for i in range(6)}

    def scrape(k, ids):
        b = RecordBuilder(GAUGE)
        for i in ids:
            v = 100.0 * i + 5.0 * len(vals[i])
            vals[i].append(v)
            b.add({"_metric_": "m", "host": f"h{i}"}, sc.BASE + k * sc.IV, v)
        shard.ingest(b.build())
        shard.flush()

    born = {0: 0, 1: 0, 2: 0, 3: 0, 4: 50, 5: 126}
    for k in range(120):
        scrape(k, [i for i, b in born.items() if b <= k])
    text, step = "sum(count_over_time(m[5m]))", 6 * sc.IV
    end = sc.BASE + 119 * sc.IV
    start = end - 60 * step // 6
    r1 = eng.query_range(text, start, end, step)
    for k in range(120, 132):
        scrape(k, [i for i, b in born.items() if b <= k])
    r2 = eng.query_range(text, start + 2 * step, end + 2 * step, step)
    assert (r2.exec_path or "").startswith("incremental["), r2.exec_path
    assert shard.store.born[5] == 126 and shard.store.born_late == 2
    out = np.arange(start + 2 * step, end + 2 * step + 1, step)
    refs = [cr.Series(i, 0, born[i], None, np.asarray(vals[i]))
            for i in born]
    rows = [cr.range_fn("count_over_time", *s.samples(131, sc.BASE, sc.IV),
                        out, 300_000) for s in refs]
    close(sc.by_group(r2, out), agg_of("sum", rows), exact=True)
    assert r1.matrix.num_series == 1


# -- (g) the selection memo under the time mask ---------------------------------------

ENDS = {          # a query's last scrape -> what the time mask leaves of the births
    "every-birth": 705, "before-the-last": 650, "before-two": 580,
    "half-way": 400, "before-all-but-one": 100,
}


@pytest.mark.parametrize("name", ["sum_rate", "sum_by_rate", "avg_avg",
                                  "sum_count"])
@pytest.mark.parametrize("ends", list(ENDS))
def test_a_kept_selection_answers_every_range_of_its_span(fleet, monkeypatch,
                                                          name, ends):
    """A query that ends before the newest birth selects under the index's
    time mask; the shard keeps what the mask left for the span of ranges
    that leave the same, with its row mask and its count of rows born late.
    The first range of a span and a later one (another phase: a hit) both
    answer as the reference does."""
    _ms, shard, eng, series = fleet
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", 8)   # 55 series are wide
    text, agg, fn, by = sc.TEXTS[name]
    last = ENDS[ends]
    born_late = sum(1 for b, _e in sc.LIVES if 0 < b <= last)
    seen = []
    for phase_ms in (0, 1_009, 7_777):
        end = sc.BASE + last * sc.IV + phase_ms
        out = np.arange(end - 3_600_000, end + 1, 60_000, dtype=np.int64)
        tracer.drain()
        r = eng.query_range(text, int(out[0]), int(out[-1]), 60_000)
        spans = tracer.drain()
        (sel,) = [s for s in spans if s.name == "query.exec.select"]
        (disp,) = [s for s in spans if s.name == SPAN_QUERY_KERNEL
                   and s.tags.get("phase") == "dispatch"]
        assert r.exec_path.startswith("local-fused["), r.exec_path
        assert (disp.tags["births"], disp.tags["born_late"]) == (1, born_late)
        assert sel.tags["demoted"] == 0
        seen.append((sel.tags["memo"], sel.tags.get("memo_why")))
        close(sc.by_group(r, out), want(series, agg, fn, by, out),
              exact=fn == "count_over_time")
    # the first of a span ran the masked pass (unless an earlier case of
    # this module kept the span already); the later ones are hits
    masked = last < max(b for b, _e in sc.LIVES)
    assert seen[0] in (("miss", "time_mask" if masked else None),
                       ("hit", None))
    assert seen[1:] == [("hit", None)] * 2


# -- compression and births ---------------------------------------------------------

def test_compression_waits_while_a_row_born_late_is_held():
    """The narrow forms and the derived stamps read every row from column
    0: a store that holds a row born late declines to compress (and says
    why), a delta form adopted in place rehydrates for a birth, and once
    compaction has aged the births out the store compresses again."""
    st = SeriesStore(16, 64)

    def scrape(k, rows):
        rows = np.asarray(rows, np.int32)
        st.append(rows, np.full(len(rows), sc.BASE + k * sc.IV),
                  rows * 10.0 + k)

    for k in range(8):
        scrape(k, [0, 1, 2, 3])
    assert st.compress_resident() and st._inplace       # delta8, in place
    scrape(8, [0, 1, 2, 3])
    assert st._inplace and st.rehydrates == 0
    scrape(9, [0, 1, 2, 3, 4])                          # row 4 is born
    assert st.rehydrated["births"] == 1 and not st.is_narrow_resident
    assert st.born[4] == 9 and st.born_late == 1
    assert not st.compress_resident()
    assert st.residency_decline == "births"
    for k in range(10, 20):
        scrape(k, [0, 1, 2, 3, 4])
    t, v = st.series_snapshot(4)                        # rows moved left
    assert t.tolist() == [sc.BASE + k * sc.IV for k in range(9, 20)]
    assert v.tolist() == [40.0 + k for k in range(9, 20)]
    ts, _val, n = st.closed_arrays()
    assert int(n[4]) == 11 and int(ts[4, 0]) == sc.BASE + 9 * sc.IV
    st.compact(sc.BASE + 12 * sc.IV)                    # past the birth
    assert st.born_late == 0 and st.grid_cohorts() == ("uniform", 12)
    assert st.compress_resident() and st.residency_decline is None
    t, v = st.series_snapshot(4)
    assert t.tolist() == [sc.BASE + k * sc.IV for k in range(12, 20)]


def test_a_stamp_off_the_cells_takes_the_rows_out_of_them():
    """A series that starts on no cell of the grid turns the store to its
    line form: every row moves left to its own column 0, once, and reads
    as it read (the line form's majority rule from there on)."""
    st = SeriesStore(16, 64)
    for k in range(12):
        rows = np.asarray([0, 1] + ([2] if k >= 5 else []), np.int32)
        st.append(rows, np.full(len(rows), sc.BASE + k * sc.IV),
                  rows * 100.0 + k)
    assert st.born[2] == 5 and st.stamp_form == "grid"
    before = [st.series_snapshot(r) for r in range(3)]
    st.append(np.asarray([0, 1, 2, 3], np.int32),
              np.asarray([sc.BASE + 12 * sc.IV] * 3
                         + [sc.BASE + 12 * sc.IV + 3_333]),
              np.asarray([12.0, 112.0, 212.0, 7.0]))
    assert st.stamp_form == "line" and st.born_late == 0
    assert not st.born.any() and st.births["minority"] == 1
    assert st.n_host[:4].tolist() == [13, 13, 8, 1]
    assert (np.asarray(st.n)[:4] == st.n_host[:4]).all()
    for r, (t0, v0) in enumerate(before):
        t, v = st.series_snapshot(r)
        assert t[:-1].tolist() == t0.tolist() and v[:-1].tolist() == v0.tolist()
        assert t[-1] == sc.BASE + 12 * sc.IV
    t, v = st.series_snapshot(3)
    assert t.tolist() == [sc.BASE + 12 * sc.IV + 3_333] and v.tolist() == [7.0]
