"""The shard's selection memo (core/selection.py): what it hands out equals
what the un-memoized functions compute, it is served only while the index
says the same, what does not fit takes the old path and is counted, and the
leaves keep recording their spans on a hit.
"""

import json
import sys
import threading
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.core import filters as F
from filodb_tpu.core import selection
from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE, PROM_HISTOGRAM
from filodb_tpu.query import exec as qexec
from filodb_tpu.query.rangevector import QueryError
from filodb_tpu.utils.metrics import FILODB_SELECTION_MEMO, registry
from filodb_tpu.utils.tracing import (SPAN_QUERY, SPAN_QUERY_GROUPIDS,
                                      SPAN_QUERY_SELECT, tracer)

from .prom_reference import eval_range_fn
from .test_group_ids import BASE, labels_of, walk

M = [F.Equals("_metric_", "m")]
OTHER = [F.Equals("_metric_", "other")]
KEEP_OVER = 8               # the leaves pass GATHER_THRESHOLD; shards here are small
LATE = BASE + 10_000_000


def counts() -> dict:
    """{(part, outcome or outcome:reason): value} of the memo's counter."""
    out = {}
    for (name, tags), m in list(registry._metrics.items()):
        if name == FILODB_SELECTION_MEMO:
            t = dict(tags)
            what = t["outcome"] + (":" + t["reason"] if "reason" in t else "")
            out[(t["part"], what)] = m.value
    return out


def delta(before: dict) -> dict:
    now = counts()
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


def ingest(shard, ids, t0=BASE, nsamples=3):
    b = RecordBuilder(GAUGE)
    for i in ids:
        for k in range(nsamples):
            b.add(labels_of(i), t0 + k * 10_000, float(i + k))
    shard.ingest(b.build())
    shard.flush()


def mk_shard(n=48, cap=64):
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", GAUGE, 0, StoreConfig(
        max_series_per_shard=cap, samples_per_series=64,
        flush_batch_size=10**9, groups_per_shard=4))
    ingest(shard, range(n))
    return ms, shard


def select(shard, filters=M, end=LATE, keep_over=KEEP_OVER):
    with shard.lock:
        return shard.selection(list(filters), BASE, end, keep_over)


# -- the memo's answers are the un-memoized functions' ------------------------

GROUPINGS = {
    "by": dict(by=("g",)),
    "by-several": dict(by=("az", "g")),
    "by-a-label-some-series-lack": dict(by=("rack",)),
    "without": dict(without=("host",)),
    "without-a-label-some-series-lack": dict(without=("host", "tier")),
}


@pytest.mark.parametrize("case", sorted(GROUPINGS))
def test_memo_groups_as_the_index_and_the_walk_do(case):
    _ms, shard = mk_shard()
    kw = {"by": (), "without": (), **GROUPINGS[case]}
    sel, how = select(shard)
    assert how == "miss"
    want_pids = shard.index.part_ids_from_filters(M, BASE, LATE)
    assert sel.pids.dtype == np.int32 and sel.pids.tolist() == want_pids.tolist()
    assert not sel.is_all               # the ``other`` series are not selected
    again, how = select(shard)
    assert how == "hit" and again is sel
    with shard.lock:
        g, how = sel.grouping(kw["by"], kw["without"])
        assert how == "miss"
        g2, how = sel.grouping(kw["by"], kw["without"])
        assert how == "hit" and g2 is g
        idx_ids, idx_keys = shard.index.group_ids(want_pids, **kw)
    ref_ids, ref_keys = walk(shard.index, want_pids, **kw)
    # the same ids in the same first-appearance order, the same keys
    assert g.gids.dtype == np.int32
    assert g.gids.tolist() == idx_ids.tolist() == ref_ids.tolist()
    assert [k.labels for k in g.keys] == idx_keys == ref_keys
    assert g.gids[0] == 0 and len(g.keys) >= 2
    # through the leaf's function: the dense rows are the scatter's
    R = shard.store.S
    gids, uniq, G, dev = qexec._grouping_for(qexec.LazyKeys(shard, sel),
                                             sel.pids, R, **kw)
    want = np.zeros(R, np.int32)
    want[want_pids] = ref_ids
    assert gids.tolist() == want.tolist() == np.asarray(dev).tolist()
    assert [k.labels for k in uniq] == ref_keys and G == len(ref_keys)
    # and for a caller whose rows are not the selection's pids
    rows = np.arange(len(want_pids), dtype=np.int32)
    gids2, uniq2, _G = qexec._group_ids_for(qexec.LazyKeys(shard, sel), rows,
                                            R, **kw)
    assert gids2[:len(rows)].tolist() == ref_ids.tolist() and uniq2 == uniq


def test_global_aggregation_asks_no_grouping():
    _ms, shard = mk_shard()
    sel, _ = select(shard)
    before = counts()
    gids, uniq, G, dev = qexec._grouping_for(qexec.LazyKeys(shard, sel),
                                             sel.pids, 64, (), ())
    assert not gids.any() and G == 1 and dev is None
    assert delta(before) == {}


def test_whole_shard_selection_is_all():
    _ms, shard = mk_shard()
    sel, _ = select(shard, [F.EqualsRegex("host", "h.*")])
    assert sel.is_all and len(sel.pids) == len(shard.index)


# -- served only while the index says the same --------------------------------

def _new_series(shard, sel):
    ingest(shard, range(48, 54))
    return "miss", None


def _purge(shard, sel):
    ingest(shard, [100], LATE)              # one series stays live
    assert shard.purge_expired_partitions(BASE + 5_000_000) == 48
    ingest(shard, range(200, 248), BASE)    # every slot comes back
    return "miss", None


def _release(shard, sel):
    with shard.lock:                        # what eviction calls
        shard._release_partitions_locked(np.asarray([1, 2], np.int32))
    # a tombstone is an ended entry: the time mask bites until the slot is
    # reused (the test ``part_ids_from_filters`` makes), and what it leaves
    # is kept for the span of ranges that it leaves the same
    return "miss", "time_mask"


def _release_and_reuse(shard, sel):
    _release(shard, sel)
    ingest(shard, [300, 301])               # new owners of both slots
    return "miss", None


def _end_time(shard, sel):
    with shard.lock:
        shard.index.update_end_time(3, BASE + 20_000)
    return "miss", "time_mask"


def _starts_after_the_query(shard, sel):
    ingest(shard, [61], LATE + 500_000)     # starts after the query's end
    return "miss", "time_mask"


def _recovering(shard, sel):
    shard.recovering = True
    return "bypass", "recovering"


EVENTS = {"new-series": _new_series, "purge": _purge, "release": _release,
          "release-and-reuse": _release_and_reuse, "end-time": _end_time, "later-start": _starts_after_the_query,
          "recovering": _recovering}


@pytest.mark.parametrize("event", sorted(EVENTS))
def test_a_changed_index_state_is_not_served_from_the_memo(event):
    _ms, shard = mk_shard()
    sel, how = select(shard)
    assert how == "miss" and select(shard)[1] == "hit"
    lazy = qexec.LazyKeys(shard, sel)       # a query's snapshot, before it
    before = counts()
    want_how, reason = EVENTS[event](shard, sel)
    after, how = select(shard)
    assert how == want_how and after is not sel
    what = how + (":" + reason if reason else "")
    assert delta(before) == {("select", what): 1}
    # whichever way it came: what the index answers now
    want = shard.index.part_ids_from_filters(M, BASE, LATE)
    assert after.pids.tolist() == want.tolist()
    with shard.lock:
        g, _ = after.grouping(("g",), ())
    ref_ids, ref_keys = walk(shard.index, want, by=("g",))
    assert g.gids.tolist() == ref_ids.tolist()
    assert [k.labels for k in g.keys] == ref_keys
    if event in ("purge", "release", "release-and-reuse"):
        # a selection snapshotted before the release still fails loudly
        with pytest.raises(QueryError, match="selection invalidated"):
            lazy.grouping(("g",), ())
        with pytest.raises(QueryError, match="selection invalidated"):
            lazy[0]
    else:
        assert lazy.grouping(("g",), ())[0].gids.tolist() \
            == walk(shard.index, sel.pids, by=("g",))[0].tolist()
    if how == "bypass":
        # not kept: the next one is computed again
        assert select(shard)[0] is not after
    else:
        assert select(shard) == (after, "hit")


# -- under a time mask that bites: one kept selection per span of ranges ------

def _fleet():
    """48 series from BASE, six born 1,000 s later, six 2,000 s later, and
    series 3 and 4 ended (marked, as the purge does) at BASE + 20 s."""
    _ms, shard = mk_shard(cap=128)
    ingest(shard, range(48, 54), BASE + 1_000_000)
    ingest(shard, range(54, 60), BASE + 2_000_000)
    with shard.lock:
        shard.index.update_end_time(3, BASE + 20_000)
        shard.index.update_end_time(4, BASE + 20_000)
    return shard


def _ranged(shard, start, end):
    with shard.lock:
        return shard.selection(list(M), start, end, KEEP_OVER)


def _brute(shard, start, end) -> list:
    idx = shard.index
    return [p for p in range(len(idx)) if idx.is_live(p)
            and idx.labels_of(p)["_metric_"] == "m"
            and idx.start_time(p) <= end and idx.end_time(p) >= start]


RANGES = {      # (start, end) -> the series a brute pass over the index picks
    "before-both-births": (BASE, BASE + 999_999),
    "on-the-first-birth": (BASE, BASE + 1_000_000),
    "between-the-births": (BASE + 5_000, BASE + 1_999_999),
    "on-the-second-birth": (BASE + 20_000, BASE + 2_000_000),
    "past-the-ends": (BASE + 20_001, BASE + 1_500_000),
    "past-the-ends-and-births": (BASE + 30_000, LATE),
}


@pytest.mark.parametrize("case", sorted(RANGES))
def test_a_kept_time_masked_selection_is_the_brute_pass(case):
    shard = _fleet()
    for name in sorted(RANGES):             # every span kept or LRU'd first
        _ranged(shard, *RANGES[name])
    start, end = RANGES[case]
    sel, how = _ranged(shard, start, end)
    assert sel.pids.tolist() == _brute(shard, start, end)
    again, how = _ranged(shard, start, end)
    assert how == "hit" and again is sel


def test_ranges_of_one_span_share_a_selection_and_the_next_span_does_not():
    shard = _fleet()
    before = counts()
    a, how = _ranged(shard, BASE + 30_000, BASE + 1_000_000)
    assert how == "miss" and a.why == "time_mask"
    assert delta(before) == {("select", "miss:time_mask"): 1}
    # anywhere from the first birth up to the second, from past the ends on
    for start, end in ((BASE + 20_001, BASE + 1_000_000),
                       (BASE + 700_000, BASE + 1_999_999),
                       (BASE + 1_500_000, BASE + 1_600_000)):
        assert _ranged(shard, start, end) == (a, "hit")
    # one millisecond over either edge is another selection
    asked = {id(a): (BASE + 30_000, BASE + 1_000_000)}
    for start, end in ((BASE + 30_000, BASE + 2_000_000),
                       (BASE + 20_000, BASE + 1_000_000),
                       (BASE + 30_000, BASE + 999_999)):
        sel, how = _ranged(shard, start, end)
        assert how == "miss" and id(sel) not in asked
        assert sel.pids.tolist() == _brute(shard, start, end) != a.pids.tolist()
        asked[id(sel)] = (start, end)
    # and all four are kept: each has its groupings and its row mask
    for start, end in asked.values():
        sel, how = _ranged(shard, start, end)
        assert how == "hit" and asked[id(sel)] == (start, end)
    host, dev = a.row_mask(shard.store.S)
    assert host.sum() == len(a.pids) and host[a.pids].all()
    assert np.asarray(dev).tolist() == host.tolist()
    assert a.row_mask(shard.store.S)[1] is dev and not host.flags.writeable


def test_an_end_time_that_moves_unkeeps_every_span():
    shard = _fleet()
    a, _ = _ranged(shard, BASE + 30_000, BASE + 1_000_000)
    with shard.lock:
        shard.index.update_end_time(5, BASE + 1_200_000)    # inside a's span
    b, how = _ranged(shard, BASE + 30_000, BASE + 1_000_000)
    assert how == "miss" and b is not a and b.pids.tolist() == a.pids.tolist()
    assert len(shard._selections) == 1      # a, of a state that is gone, went
    c, how = _ranged(shard, BASE + 1_200_001, BASE + 1_300_000)
    assert how == "miss" and 5 not in c.pids and 5 in b.pids


def test_late_rows_are_counted_once_a_mask():
    shard = _fleet()
    a, _ = _ranged(shard, BASE + 30_000, BASE + 1_000_000)
    late = np.zeros(shard.store.S, bool)
    late[a.pids[-6:]] = True
    late[3] = True                          # ended before the range: not selected
    assert a.late_rows(late) == 6 and a._late[0] is late
    late[a.pids[0]] = True                  # the same array: not looked at again
    assert a.late_rows(late) == 6
    assert a.late_rows(late.copy()) == 7


def test_release_of_other_series_leaves_an_old_snapshot_readable():
    _ms, shard = mk_shard()
    sel, _ = select(shard, OTHER, keep_over=4)
    lazy = qexec.LazyKeys(shard, sel)
    mine = set(sel.pids.tolist())
    with shard.lock:
        shard._release_partitions_locked(np.asarray(
            [p for p in range(10) if p not in mine][:2], np.int32))
    assert lazy[0].labels and lazy.grouping(("g",), ())[1] == "miss"


def test_narrow_selection_and_limit_take_the_old_path():
    _ms, shard = mk_shard()
    before = counts()
    sel, how = select(shard, OTHER)         # 7 series, keep_over 8
    assert how == "bypass" and len(sel.pids) == 7
    assert delta(before) == {("select", "bypass:narrow"): 1}
    with shard.lock:
        _g, how = sel.grouping(("g",), ())
    assert how == "bypass"                  # grouped, and not kept
    assert select(shard, OTHER)[0] is not sel and len(shard._selections) == 0
    # a limit is the metadata surface's: it never reaches the memo
    select(shard)
    before = counts()
    got = shard.part_ids_from_filters(M, BASE, LATE, limit=5)
    assert got.tolist() == select(shard)[0].pids[:5].tolist()
    got[0] = 7                              # the caller's own array
    assert delta(before) == {("select", "hit"): 1}


@pytest.mark.parametrize("which", ["pids", "slot-epochs", "gids", "rows"])
def test_handed_out_arrays_refuse_writes(which):
    _ms, shard = mk_shard()
    sel, _ = select(shard)
    sel.snapshot()
    with shard.lock:
        g, _ = sel.grouping(("g",), ())
    arr = {"pids": sel.pids, "slot-epochs": sel._epochs, "gids": g.gids,
           "rows": g.dense(sel.pids, 64)[0]}[which]
    assert not arr.flags.writeable
    with pytest.raises(ValueError, match="read-only"):
        arr[0] = 1


def test_lru_bounds_hold():
    _ms, shard = mk_shard()
    selectors = [[F.EqualsRegex("host", f"h[0-9]*{'x?' * k}")]
                 for k in range(selection.SELECTIONS + 2)]
    for f in selectors:
        assert select(shard, f)[1] == "miss"
    assert len(shard._selections) == selection.SELECTIONS
    assert select(shard, selectors[-1])[1] == "hit"
    assert select(shard, selectors[0])[1] == "miss"     # the oldest went
    sel, _ = select(shard, selectors[-1])
    bys = [("g",), ("az",), ("rack",), ("tier",), ("g", "az"), ("host",)]
    assert len(bys) > selection.GROUPINGS
    with shard.lock:
        for by in bys:
            assert sel.grouping(by, ())[1] == "miss"
        assert len(sel._groupings) == selection.GROUPINGS
        assert sel.grouping(bys[-1], ())[1] == "hit"
        assert sel.grouping(bys[0], ())[1] == "miss"


def test_device_copy_is_kept_and_rebuilt_when_the_height_changes():
    _ms, shard = mk_shard()
    sel, _ = select(shard)
    with shard.lock:
        g, _ = sel.grouping(("g",), ())
    host, dev = g.dense(sel.pids, 64)
    again = g.dense(sel.pids, 64)
    assert again[0] is host and again[1] is dev
    host2, dev2 = g.dense(sel.pids, 128)
    assert host2.shape == dev2.shape == (128,) and dev2 is not dev
    assert host2[:64].tolist() == host.tolist() == np.asarray(dev).tolist()
    assert not host2[64:].any()


# -- four threads query while one registers new series ------------------------

def test_queries_beside_an_ingest_of_new_series_match_the_reference(
        monkeypatch):
    """Every batch of new series brings a group of its own with its whole
    history in one container: a query sees the group whole or not at all,
    and every group it answers equals the plain reference."""
    from filodb_tpu.query.engine import QueryEngine
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", KEEP_OVER)
    per_group, n_samples, n_batches = 12, 24, 10
    ts = BASE + 10_000 * np.arange(n_samples)
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", GAUGE, 0, StoreConfig(
        max_series_per_shard=512, samples_per_series=32,
        flush_batch_size=10**9, groups_per_shard=4))

    def add_group(g):
        b = RecordBuilder(GAUGE)
        for t in range(n_samples):
            b.add_series_batch(
                {"_metric_": "m", "g": f"g{g}",
                 "host": [f"h{g}-{i}" for i in range(per_group)]},
                int(ts[t]), float((g + 1) * 8 * t))
        shard.ingest(b.build())

    for g in range(4):
        add_group(g)
    shard.flush()
    eng = QueryEngine(ms, "prometheus")
    eng.result_cache = eng.fragment_cache = None
    start, end, step = BASE + 120_000, BASE + 200_000, 20_000
    out_ts = np.arange(start, end + 1, step)

    def want(g):
        return per_group * eval_range_fn(
            "rate", ts, (g + 1) * 8.0 * np.arange(n_samples), out_ts, 60_000)

    eng.query_range("sum by (g)(rate(m[1m]))", start, end, step)   # compiled
    before = counts()
    stop, errors, seen = threading.Event(), [], []

    def ask():
        try:
            while not stop.is_set():
                r = eng.query_range("sum by (g)(rate(m[1m]))", start, end,
                                    step)
                m = r.matrix.to_host()
                groups = [dict(k.labels)["g"] for k in m.keys]
                # first appearance: the groups in the order they were added
                assert groups == [f"g{g}" for g in range(len(groups))]
                assert len(groups) >= 4
                for g, row in enumerate(np.asarray(m.values)):
                    np.testing.assert_allclose(row[:len(out_ts)], want(g),
                                               rtol=2e-4)
                seen.append(len(groups))
        except BaseException as e:      # noqa: BLE001 - handed to the test
            errors.append(e)
            stop.set()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    threads = [threading.Thread(target=ask) for _ in range(4)]
    try:
        for t in threads:
            t.start()
        for g in range(4, 4 + n_batches):
            add_group(g)
            time.sleep(0.05)
        deadline = time.monotonic() + 60
        while (not seen or seen[-1] < 4 + n_batches) and not stop.is_set() \
                and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    if errors:
        raise errors[0]
    assert max(seen) == 4 + n_batches
    d = delta(before)
    # a batch makes the index new: the next query misses, and groups anew;
    # the queries between two batches hit
    assert 1 <= d[("select", "miss")] == d[("groupids", "miss")] <= n_batches
    assert d[("select", "hit")] == d[("groupids", "hit")] >= 1
    assert set(d) == {("select", "miss"), ("select", "hit"),
                      ("groupids", "miss"), ("groupids", "hit")}


# -- the leaves record their spans on a hit -----------------------------------

def _spans_of_last():
    roots = [s for s in tracer.snapshot() if s.name == SPAN_QUERY]
    return [s for s in tracer.snapshot()
            if s.trace_id == roots[-1].trace_id]


def _leaf_route(monkeypatch):
    from filodb_tpu.query.engine import QueryEngine
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", KEEP_OVER)
    ms, _shard = mk_shard()
    eng = QueryEngine(ms, "prometheus")
    eng.result_cache = eng.fragment_cache = None
    return (lambda: eng.query_range("sum by (g)(rate(m[1m]))", BASE,
                                    BASE + 60_000, 10_000)), "local", 1, 5


def _fused_hist_route(monkeypatch):
    from filodb_tpu.ops import fusedresident
    from filodb_tpu.query.engine import QueryEngine
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", KEEP_OVER)
    ms = TimeSeriesMemStore()
    shard = ms.setup("hists", PROM_HISTOGRAM, 0, StoreConfig(
        max_series_per_shard=32, samples_per_series=128,
        flush_batch_size=10**9))
    b = RecordBuilder(PROM_HISTOGRAM, bucket_les=np.array(
        [1., 2., 4., 8., 16., 32., 64., np.inf]))
    for t in range(90):
        for i in range(16):
            b.add({"_metric_": "h", "host": f"h{i}", "g": f"g{i % 4}"},
                  BASE + t * 10_000,
                  np.cumsum(np.arange(8) + i + 1.0) * (t + 1))
    shard.ingest(b.build())
    shard.flush()
    eng = QueryEngine(ms, "hists")
    eng.result_cache = eng.fragment_cache = None
    return (lambda: eng.query_range(
        "histogram_quantile(0.9, sum by (g)(rate(h[2m])))", BASE + 300_000,
        BASE + 800_000, 10_000)), f"fused-hist[{fusedresident.tag()}]", 1, 4


def _mesh_route(monkeypatch):
    import jax

    from filodb_tpu.parallel.distributed import make_mesh
    from filodb_tpu.query.engine import QueryEngine
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", KEEP_OVER)
    mesh = make_mesh(jax.devices()[:2])
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=16, samples_per_series=64,
                      flush_batch_size=10**9, dtype="float32")
    shards = [ms.setup("prometheus", GAUGE, i, cfg, device=dev)
              for i, dev in enumerate(mesh.devices.ravel())]
    for s, sh in enumerate(shards):
        b = RecordBuilder(GAUGE)
        for i in range(12):
            for t in range(40):
                b.add({"_metric_": "m", "host": f"h{s}-{i}",
                       "g": "abc"[(i + s) % 3]},
                      BASE + t * 10_000, float((i + 1) * t))
        sh.ingest(b.build())
    ms.flush_all()
    eng = QueryEngine(ms, "prometheus", mesh=mesh)
    eng.result_cache = eng.fragment_cache = None
    return (lambda: eng.query_range("sum by (g)(rate(m[2m]))",
                                    BASE + 150_000, BASE + 350_000,
                                    10_000)), "mesh[pjit]-", 2, 3


ROUTES = {"leaf": _leaf_route, "fused-hist": _fused_hist_route,
          "mesh": _mesh_route}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_spans_are_recorded_on_a_hit(route, monkeypatch):
    ask, path, shards, groups = ROUTES[route](monkeypatch)
    first = ask()
    assert first.exec_path.startswith(path), first.exec_path
    tracer.drain()
    before = counts()
    again = ask()                           # the same selector and grouping
    assert again.exec_path == first.exec_path
    assert again.matrix.keys == first.matrix.keys
    np.testing.assert_array_equal(np.asarray(again.matrix.values),
                                  np.asarray(first.matrix.values))
    members = _spans_of_last()
    sel = [s for s in members if s.name == SPAN_QUERY_SELECT]
    gid = [s for s in members if s.name == SPAN_QUERY_GROUPIDS]
    assert len(sel) == shards
    for s in sel:
        assert s.tags["memo"] == "hit" and s.tags["series"] > KEEP_OVER
    if route == "mesh":
        # one span for the leaf: the shards' rows under the shared numbering
        # come from the engine's memo (parallel/distributed.MeshLeafMemo),
        # which sits above the shards' groupings and does not ask them
        (g,), hits = gid, {("select", "hit"): shards}
        assert g.tags["memo"] == "memo" and g.tags["route"] == "index"
        assert g.tags["keys"] > shards * KEEP_OVER
    else:
        assert len(gid) == shards
        for s in gid:
            assert s.tags["memo"] == "hit" and s.tags["route"] == "index"
            assert s.tags["keys"] > KEEP_OVER
        hits = {("select", "hit"): shards, ("groupids", "hit"): shards}
    assert gid[-1].tags["groups"] == groups
    assert delta(before) == hits


def test_metrics_page_shows_the_memo_counter(monkeypatch):
    from filodb_tpu.config import Config
    from filodb_tpu.standalone import FiloServer
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", KEEP_OVER)
    srv = FiloServer(Config({
        "num_shards": 1, "http": {"port": 0},
        "store": {"max_series_per_shard": 64, "samples_per_series": 64,
                  "flush_batch_size": 10**9}})).start()
    try:
        ingest(srv.memstore.shards_of("prometheus")[0], range(48))
        base = f"http://127.0.0.1:{srv.http.port}"
        for shift in (0, 1):
            q = urllib.parse.urlencode({
                "query": "sum by (g)(rate(m[1m]))",
                "start": BASE / 1000 + shift, "end": BASE / 1000 + 60 + shift,
                "step": 10})
            with urllib.request.urlopen(
                    f"{base}/promql/prometheus/api/v1/query_range?{q}",
                    timeout=60) as r:
                assert json.load(r)["status"] == "success"
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            text = r.read().decode()
    finally:
        srv.shutdown()
    lines = [ln for ln in text.splitlines()
             if ln.startswith("filodb_selection_memo_total")]
    for part in ("select", "groupids"):
        for outcome in ("hit", "miss"):
            assert any(f'outcome="{outcome}"' in ln and f'part="{part}"' in ln
                       and float(ln.rsplit(" ", 1)[1]) >= 1
                       for ln in lines), (part, outcome, lines)
