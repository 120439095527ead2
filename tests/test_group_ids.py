"""Group ids from the part-key index's label columns against a walk over the
series' keys (the test's reference): same groups, same numbering (first
appearance in pid order), same keys — on a bare index, under churn, on a
shard behind ``LazyKeys``, on a two-shard mesh, and through the served path.
"""

import json
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.core import index_columnar
from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.partkey_index import PartKeyIndex
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE
from filodb_tpu.query import exec as qexec
from filodb_tpu.query.rangevector import QueryError, RangeVectorKey

from .prom_reference import eval_range_fn

BASE = 1_700_000_000_000


def walk(index, pids, by=(), without=()):
    """The reference: one key a series, grouped through a dict."""
    uniq: dict = {}
    gids = []
    for p in np.asarray(pids).tolist():
        k = RangeVectorKey.of(index.labels_of(p)).without(("_metric_",))
        if by:
            k = k.only(by)
        elif without:
            k = k.without(without)
        else:
            k = RangeVectorKey(())
        gids.append(uniq.setdefault(k.labels, len(uniq)))
    return np.asarray(gids, np.int32), list(uniq)


def same_as_walk(index, pids, by=(), without=()):
    got_ids, got_keys = index.group_ids(pids, by, without)
    want_ids, want_keys = walk(index, pids, by, without)
    assert got_ids.dtype == np.int32
    assert got_ids.tolist() == want_ids.tolist()
    assert got_keys == want_keys
    return got_keys


def labels_of(i):
    """60 series a lap: g in 5 values, az in 3, rack on two series in
    three, tier on every fourth — so some series lack a grouping label."""
    d = {"_metric_": "m" if i % 7 else "other", "host": f"h{i}",
         "g": f"g{(i * 3) % 5}", "az": f"az{i % 3}"}
    if i % 3:
        d["rack"] = f"r{i % 4}"
    if i % 4 == 0:
        d["tier"] = "gold"
    return d


def build_index(n=240):
    idx = PartKeyIndex()
    for i in range(n):
        idx.add_part_key(i, labels_of(i), BASE)
    return idx


CASES = {
    "by-one": dict(by=("g",)),
    "by-several": dict(by=("az", "g")),
    "by-lacking": dict(by=("rack",)),
    "by-lacking-and-full": dict(by=("tier", "rack", "g")),
    "by-unknown-label": dict(by=("nope",)),
    "by-metric-never-groups": dict(by=("_metric_", "g")),
    "by-repeated-name": dict(by=("g", "g")),
    "by-every-series-its-own": dict(by=("host",)),
    "without-one": dict(without=("host",)),
    "without-several": dict(without=("host", "rack", "tier")),
    "without-unknown": dict(without=("nope", "host")),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_index_group_ids_match_the_walk(case):
    idx = build_index()
    same_as_walk(idx, np.arange(len(idx), dtype=np.int32), **CASES[case])


@pytest.mark.parametrize("case", ["by-one", "by-lacking-and-full",
                                  "without-one"])
def test_subset_of_pids_in_any_order(case):
    idx = build_index()
    pids = np.random.default_rng(3).permutation(len(idx))[:97].astype(np.int32)
    keys = same_as_walk(idx, pids, **CASES[case])
    # numbering follows the order of ``pids``, not the pid space
    _, first = walk(idx, pids[:1], **CASES[case])
    assert keys[0] == first[0]


def test_no_pids_no_groups():
    ids, keys = build_index().group_ids(np.empty(0, np.int32), ("g",), ())
    assert len(ids) == 0 and keys == []


def test_staged_postings_fold_on_the_way():
    idx = build_index()
    g = idx._cols[idx._name_id["g"]]
    assert g._staged_n == len(idx) and not len(g._postings)
    same_as_walk(idx, np.arange(len(idx)), by=("g",))
    assert g._staged_n == 0 and len(g._postings) == len(idx)
    # appends after the column was built stage again and are seen
    idx.add_part_key(len(idx), {"_metric_": "m", "g": "brand-new"}, BASE)
    keys = same_as_walk(idx, np.arange(len(idx)), by=("g",))
    assert (("g", "brand-new"),) in keys


def test_column_is_cached_until_the_labels_postings_change():
    idx = build_index()
    pids = np.arange(len(idx))
    idx.group_ids(pids, ("g",), ())
    col = idx._vid_cols[idx._name_id["g"]][1]
    idx.group_ids(pids[::-1], ("g",), ())
    assert idx._vid_cols[idx._name_id["g"]][1] is col
    # a series without the label grows the pid space: the column follows
    idx.add_part_key(len(idx), {"_metric_": "m", "az": "az9"}, BASE)
    same_as_walk(idx, np.arange(len(idx)), by=("g",))
    assert len(idx._vid_cols[idx._name_id["g"]][1]) == len(idx)


def test_after_purge_and_slot_reuse():
    idx = build_index()
    gone = np.arange(0, 60, 2, dtype=np.int32)
    idx.group_ids(np.arange(len(idx)), ("g", "rack"), ())     # columns cached
    idx.remove_part_keys(gone)
    live = np.setdiff1d(np.arange(len(idx)), gone)
    same_as_walk(idx, live, by=("g", "rack"))
    same_as_walk(idx, live, without=("host",))
    # the freed slots come back under other labels, one without ``g``
    for j, pid in enumerate(gone.tolist()):
        d = {"_metric_": "m", "host": f"again{j}", "rack": "r9"}
        if j % 2:
            d["g"] = f"g{j % 7}"
        idx.add_part_key(pid, d, BASE)
    everyone = np.arange(len(idx))
    keys = same_as_walk(idx, everyone, by=("g", "rack"))
    assert (("rack", "r9"),) in keys
    same_as_walk(idx, everyone[::-1], without=("host", "az"))


def test_after_arena_compaction_renumbers_vids():
    idx = build_index()
    idx.group_ids(np.arange(len(idx)), ("g", "host"), ())
    gone = np.arange(0, 200, dtype=np.int32)       # most of the arena dies
    pools_before = [list(p) for p in idx._val_pool]
    idx.remove_part_keys(gone)                     # runs maybe_compact_arena
    assert idx._dead_pairs == 0 and not idx._vid_cols
    assert [list(p) for p in idx._val_pool] != pools_before
    live = np.arange(200, len(idx))
    same_as_walk(idx, live, by=("g", "host"))
    same_as_walk(idx, live[::-1], without=("host",))


def test_code_space_past_63_bits_compacts_by_stages(monkeypatch):
    idx = build_index()
    monkeypatch.setattr(index_columnar, "_CODE_LIMIT", 16)
    same_as_walk(idx, np.arange(len(idx)), by=("az", "g", "rack", "host"))
    same_as_walk(idx, np.arange(len(idx)), without=("nope",))


def test_wide_code_space_takes_the_sorting_branch():
    # few rows against a wide code space: no table of the space's size
    codes = np.asarray([7, 1 << 40, 7, 3, 1 << 40, 0], np.int64)
    ids, first = index_columnar.first_appearance_ids(codes, 1 << 41)
    assert ids.tolist() == [0, 1, 0, 2, 1, 3] and first.tolist() == [0, 1, 3, 5]
    # and the table branch numbers the same rows the same way
    small = np.asarray([7, 9, 7, 3, 9, 0], np.int64)
    ids2, first2 = index_columnar.first_appearance_ids(small, 10)
    assert ids2.tolist() == ids.tolist() and first2.tolist() == first.tolist()


# -- behind LazyKeys, on a shard ---------------------------------------------

def _mk_shard(n=48):
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", GAUGE, 0, StoreConfig(
        max_series_per_shard=64, samples_per_series=64,
        flush_batch_size=10**9, groups_per_shard=4))
    _ingest(shard, range(n), BASE)
    return ms, shard


def _ingest(shard, ids, t0, nsamples=3):
    b = RecordBuilder(GAUGE)
    for i in ids:
        for k in range(nsamples):
            b.add(labels_of(i), t0 + k * 10_000, float(i + k))
    shard.ingest(b.build())
    shard.flush()


@pytest.mark.parametrize("case", ["by-one", "by-lacking", "without-one"])
def test_group_ids_for_takes_the_index_for_lazy_keys(case):
    _ms, shard = _mk_shard()
    pids = np.arange(shard.num_series, dtype=np.int32)[::-1].copy()
    kw = {"by": (), "without": (), **CASES[case]}
    with shard.lock:
        keys = [shard.rv_key_of(int(p)) for p in pids]
    shard._rv_keys.clear()
    rows = np.arange(2, 2 + len(pids))
    got = qexec._group_ids_for(qexec.LazyKeys(shard, pids), rows, 64, **kw)
    assert shard._rv_keys == {}            # no series key was materialized
    want = qexec._group_ids_for(keys, rows, 64, **kw)
    assert got[0].tolist() == want[0].tolist()
    assert got[1] == want[1] and got[2] == want[2] >= 2
    assert all(isinstance(k, RangeVectorKey) for k in got[1])


def test_release_between_select_and_group_ids_still_raises():
    _ms, shard = _mk_shard()
    _ingest(shard, [100], BASE + 10_000_000)       # one series stays live
    lazy = qexec.LazyKeys(shard, np.arange(48, dtype=np.int32))
    assert shard.purge_expired_partitions(BASE + 5_000_000) == 48
    with pytest.raises(QueryError, match="selection invalidated"):
        qexec._group_ids_for(lazy, None, 48, ("g",), ())
    # the slots come back under new owners; a new selection groups them
    _ingest(shard, range(200, 230), BASE + 10_000_000)
    pids = np.arange(shard.num_series, dtype=np.int32)
    gids, uniq, G = qexec._group_ids_for(qexec.LazyKeys(shard, pids), None,
                                         len(pids), ("g",), ())
    want_ids, want_keys = walk(shard.index, pids, by=("g",))
    assert gids.tolist() == want_ids.tolist()
    assert [k.labels for k in uniq] == want_keys and G == len(want_keys)


# -- the mesh route: per-shard vid pools, one shared numbering ---------------

@pytest.mark.parametrize("agg", ["sum by (g)", "sum by (g, az)",
                                 "sum without (host, g)", "count by (rack)"])
def test_two_shard_mesh_groups_like_the_host_path(agg):
    import jax

    from filodb_tpu.parallel.distributed import make_mesh
    from filodb_tpu.query.engine import QueryEngine
    mesh = make_mesh(jax.devices()[:2])
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=16, samples_per_series=64,
                      flush_batch_size=10**9, dtype="float32")
    shards = [ms.setup("prometheus", GAUGE, i, cfg, device=dev)
              for i, dev in enumerate(mesh.devices.ravel())]
    # the shards intern ``g`` in opposite orders: equal vids, other values
    order = {0: ["a", "b", "c"], 1: ["c", "d", "a"]}
    for s, sh in enumerate(shards):
        b = RecordBuilder(GAUGE)
        for i in range(12):
            lab = {"_metric_": "m", "host": f"h{s}-{i}",
                   "g": order[s][i % 3], "az": f"az{i % 2}"}
            if i % 4:
                lab["rack"] = f"r{(i + s) % 2}"
            for t in range(40):
                b.add(lab, BASE + t * 10_000, float((i + 1) * t))
        sh.ingest(b.build())
    ms.flush_all()
    assert shards[0].index._val_pool[shards[0].index._name_id["g"]] \
        != shards[1].index._val_pool[shards[1].index._name_id["g"]]
    q = f"{agg}(rate(m[2m]))"
    start, end, step = BASE + 150_000, BASE + 350_000, 10_000
    r = QueryEngine(ms, "prometheus", mesh=mesh).query_range(
        q, start, end, step)
    assert r.exec_path.startswith("mesh"), r.exec_path
    assert not any(sh._rv_keys for sh in shards)   # no series key was built
    want = QueryEngine(ms, "prometheus").query_range(q, start, end, step)
    assert not want.exec_path.startswith("mesh")
    # group order too: the first shard's groups first, each in pid order
    assert r.matrix.keys == want.matrix.keys and len(r.matrix.keys) >= 2
    np.testing.assert_allclose(np.asarray(r.matrix.values),
                               np.asarray(want.matrix.values),
                               rtol=2e-4, atol=1e-4, equal_nan=True)


# -- the served path, wider than GATHER_THRESHOLD ----------------------------

def test_served_wide_by_query_matches_reference_and_builds_no_series_keys():
    from filodb_tpu.config import Config
    from filodb_tpu.standalone import FiloServer
    n_groups, per_group, n_samples = 4, 2100, 24
    assert n_groups * per_group > qexec.GATHER_THRESHOLD
    srv = FiloServer(Config({
        "num_shards": 1, "http": {"port": 0},
        "store": {"max_series_per_shard": 16384, "samples_per_series": 32,
                  "flush_batch_size": 10**9}})).start()
    try:
        ts = BASE + 10_000 * np.arange(n_samples)
        for t in range(n_samples):
            b = RecordBuilder(GAUGE)
            for g in range(n_groups):
                b.add_series_batch(
                    {"_metric_": "m", "g": f"g{g}",
                     "host": [f"h{g}-{i}" for i in range(per_group)]},
                    int(ts[t]), float((g + 1) * 8 * t))
            srv.memstore.ingest("prometheus", 0, b.build())
        srv.memstore.flush_all()
        shard = srv.memstore.shards_of("prometheus")[0]
        shard._rv_keys.clear()
        start, end, step = BASE + 120_000, BASE + 200_000, 20_000
        q = urllib.parse.urlencode({
            "query": "sum by (g)(rate(m[1m]))", "start": start / 1000,
            "end": end / 1000, "step": step / 1000})
        url = (f"http://127.0.0.1:{srv.http.port}/promql/prometheus/api/v1/"
               f"query_range?{q}")
        with urllib.request.urlopen(url, timeout=120) as r:
            body = json.load(r)
        assert body["status"] == "success"
        out_ts = np.arange(start, end + 1, step)
        got = {s["metric"]["g"]: s for s in body["data"]["result"]}
        assert sorted(got) == [f"g{g}" for g in range(n_groups)]
        # registration order is the group order the walk would have given
        assert [s["metric"] for s in body["data"]["result"]] \
            == [{"g": f"g{g}"} for g in range(n_groups)]
        for g in range(n_groups):
            one = eval_range_fn("rate", ts, (g + 1) * 8.0 * np.arange(
                n_samples), out_ts, 60_000)
            series = got[f"g{g}"]
            assert [int(float(t) * 1000) for t, _ in series["values"]] \
                == out_ts.tolist()
            np.testing.assert_allclose(
                [float(v) for _, v in series["values"]], per_group * one,
                rtol=2e-4)
        assert shard._rv_keys == {}
    finally:
        srv.shutdown()
