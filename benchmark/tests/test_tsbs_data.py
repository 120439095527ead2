"""The ``tsbs_cpu`` data module (``benchmark/data/tsbs_cpu/``), the mix
``tsbs_single`` and the cell of that name: TSBS DevOps ``cpu-only``.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` (tier-1
collects these too: ``tests/test_benchmark_data.py``). Every case bears a
name of its own (``tsbs`` in it): that file star-imports.

- the law in numpy and in ``jax.numpy``: the same walk, tags and stamps;
- the plain reference tied, series by series, to its brute-force twin
  (``tests/tsbs_reference.py``, plain Python integers);
- ``fill`` against the same scrapes sent through the write path, cell by
  cell, mirrors included;
- the served path — HTTP, planner, index select by matcher, the narrow
  leaf's gather, the general kernels — against the twin, EXACTLY, for each
  of the mix's twelve text kinds;
- probes (a)-(c) on a sound store, and each on the store it is there to
  catch;
- the traffic file against what its script writes from its seed; the
  configuration, the cell and the new ``per_layer`` entries by membership
  and order, never by the tail.
"""

import dataclasses
import importlib.util
import json
import math
import os
import sys
import tempfile
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import correct, data, served, traffic  # noqa: E402
from tests import tsbs_reference as twin               # noqa: E402

BASE, IV = 1_700_000_000_000, 10_000
SEEDS = (0, 7, 2**31 + 12345, 2**33 + 1)


def _deploy(series: int = 2048, fill: int = 720, capacity: int = 768) -> dict:
    with open(os.path.join(BENCH, "configs", "tsbs_cpu_100k.json")) as f:
        d = json.load(f)
    d["series"] = series
    d["fill_columns"] = fill
    d["server"]["store"].update(max_series_per_shard=series,
                                samples_per_series=capacity)
    return d


def _gen():
    spec = importlib.util.spec_from_file_location(
        "tsbs_single_gen", os.path.join(BENCH, "traffic",
                                        "tsbs_single_gen.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tsbs():
    return data.load("tsbs_cpu")


# ---- the law ----------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_tsbs_walk_is_the_same_in_numpy_jax_and_plain_python(tsbs, seed):
    import filodb_tpu  # noqa: F401 — x64 on, as the server has it
    import jax
    import jax.numpy as jnp
    dg = tsbs.datagen
    sids = np.concatenate([np.arange(40), [999_999, 123_457]])
    K = 96
    host = dg.walk_np(seed, sids, K)

    def device(s, word):
        x = dg.start_of(jnp, word, s)
        cols = [x]
        for k in range(1, K + 1):
            x = dg.advance(jnp, x, dg.step_of(
                jnp, word, s, jnp.full(s.shape, k, jnp.uint32)))
            cols.append(x)
        return jnp.stack(cols, axis=1)

    dev = np.asarray(jax.jit(device)(jnp.asarray(sids, jnp.uint32),
                                     jnp.uint32(dg.fold_seed(seed))))
    assert host.dtype == np.int32 and (host == dev).all()
    for i in (0, 7, 40, 41):
        assert host[i].tolist() == twin.walk(seed, int(sids[i]), K)
    assert host.min() >= 0 and host.max() <= 100
    steps = np.diff(host, axis=1)
    assert steps.min() >= -3 and steps.max() <= 3
    # a live scrape is the walk's next column, whatever was asked before
    d = _deploy()
    for k in (0, 5, 6, 3, 96):
        sc = tsbs.scrape(seed, sids, k, d)
        assert sc["ts"].dtype == np.int64 and (sc["ts"] == BASE + k * IV).all()
        assert sc["values"].dtype == np.float64
        assert (sc["values"] == host[:, k]).all()
        assert tsbs.scrape_ms(k, d) == BASE + k * IV


def test_tsbs_steps_have_a_rounded_normals_weights_and_starts_are_flat(tsbs):
    dg = tsbs.datagen
    w = dg.walk_np(11, np.arange(20_000), 64)
    d = np.diff(w, axis=1)
    inner = (w[:, :-1] >= 3) & (w[:, :-1] <= 97)      # the clamp cannot bite
    share = np.bincount((d[inner] + 3).ravel(), minlength=7) / inner.sum()
    want = np.array([407, 3971, 15840, 25100, 15840, 3971, 407]) / 65536
    assert np.abs(share - want).max() < 0.004
    assert sum(twin.STEP_WEIGHTS.values()) == 65536
    first = np.bincount(w[:, 0], minlength=101) / len(w)
    assert len(first) == 101 and first.min() > 0.005 and first.max() < 0.016


def test_tsbs_series_carry_eleven_labels_drawn_a_host(tsbs):
    d = _deploy()
    ids = np.concatenate([np.arange(30), [999_990, 999_999, 54_321]])
    got = tsbs.series_labels(ids, d)
    assert set(got) == {"_metric_"} | set(tsbs.datagen.TAGS)
    assert len(got) == 11 and all(len(v) == len(ids) for v in got.values())
    for i, s in enumerate(ids.tolist()):
        assert {k: v[i] for k, v in got.items()} == twin.labels_of(s), s
    # a host's ten series share its tags and differ in the name alone
    one = {k: set(v[10:20]) for k, v in got.items()}
    assert len(one.pop("_metric_")) == 10
    assert all(len(v) == 1 for v in one.values())
    draws = tsbs.datagen.tag_draws(np.arange(100_000))
    for tag, (n, _word) in tsbs.datagen.TAG_DRAWS.items():
        share = np.bincount(draws[tag], minlength=n) / 100_000
        assert len(share) == n and np.abs(share - 1 / n).max() < 0.2 / n, tag


# ---- the reference and its twin --------------------------------------------

def _same(got, want, fn):
    """Integers exactly; a mean may differ in its last bit by the order of
    its additions."""
    if fn in ("avg_over_time", "sum_over_time"):
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-13)
    else:
        np.testing.assert_array_equal(got, np.asarray(want))


@pytest.mark.parametrize("agg, fn", [
    ("max", "max_over_time"), ("min", "min_over_time"),
    ("sum", "sum_over_time"), ("avg", "avg_over_time"),
    ("count", "count_over_time")])
def test_tsbs_reference_against_the_brute_force_twin(tsbs, agg, fn):
    seed, head, n_series = 2**31 + 5, 400, 512
    d = _deploy(n_series)
    sids = np.arange(n_series)
    hosts = [3, 17, 50, 4000]              # the last one is not registered
    for metric in ("cpu_usage_user", "cpu_usage_guest_nice"):
        for start, step in ((BASE - 45_000, 60_000),
                            (BASE + 3_000_000 + 7, 15_000),
                            (BASE + 400 * IV - 600_000, 60_000)):
            out_ts = start + np.arange(21) * step
            ref = {"agg": agg, "fn": fn, "window_s": 60, "metric": metric,
                   "hosts": hosts}
            got = tsbs.evaluate(seed, sids, ref, out_ts, d, head)
            want = twin.evaluate(seed, n_series, metric, hosts, agg, fn, 60,
                                 out_ts.tolist(), head)
            assert set(got) == {()}
            _same(got[()], want, fn)
    none = tsbs.evaluate(seed, sids, dict(ref, hosts=[4000]), out_ts, d, head)
    assert none == {}
    # series by series: a one-host query IS the series' own answer
    field = tsbs.datagen.METRICS.index(metric)
    for h in (0, 9, 50) if agg != "count" else ():
        got = tsbs.evaluate(seed, sids, dict(ref, hosts=[h]), out_ts, d, head)
        want = twin.series_answer(seed, 10 * h + field, fn, 60,
                                  out_ts.tolist(), head)
        _same(got[()], want, fn)
    raw = tsbs.raw_values(seed, [30, 31, 5119], [0, 399, 7, 400], d)
    for i, s in enumerate((30, 31, 5119)):
        w = twin.walk(seed, s, 400)
        assert raw[i].tolist() == [w[0], w[399], w[7], w[400]]


def test_tsbs_reference_takes_a_replacement_for_its_generator(tsbs):
    """``values=`` (the control computes it in a lower precision)."""
    d = _deploy(512)
    ref = {"agg": "max", "fn": "max_over_time", "window_s": 60,
           "metric": "cpu_usage_user", "hosts": [1, 2]}
    out_ts = BASE + 100 * IV + np.arange(5) * 60_000
    seen = []

    def values(s, c):
        seen.append((s.tolist(), c[0], c[-1]))
        return np.full((len(s), len(c)), 250.0)

    got = tsbs.evaluate(3, np.arange(512), ref, out_ts, d, 400, values=values)
    assert (got[()] == 250.0).all() and seen == [([10, 20], 94, 124)]


def test_tsbs_query_bytes_are_the_gathers_needed_bytes(tsbs):
    d = _deploy()
    ref = {"window_s": 60, "hosts": list(range(8))}
    out_ts = BASE + 720 * IV - 3_600_000 + np.arange(61) * 60_000
    # windows [t - 60 s, t] from 1 h back to the head: 367 columns
    assert tsbs.query_bytes(1 << 20, ref, out_ts, d, 720, 768) == \
        8 * 367 * (4 + 8)
    assert tsbs.query_bytes(4096, dict(ref, hosts=[5]), out_ts, d, 720,
                            768) == 367 * 12
    assert tsbs.query_bytes(4096, ref, out_ts - 10**10, d, 720, 768) == 0.0


# ---- fill against the write path ------------------------------------------

def _shard(series: int, capacity: int, schema):
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    ms = TimeSeriesMemStore()
    sh = ms.setup("tsbsfill", schema, 0, StoreConfig(
        max_series_per_shard=series, samples_per_series=capacity,
        flush_batch_size=10**9, dtype="float32"))
    return ms, sh


def test_tsbs_fill_leaves_the_store_the_write_path_would(tsbs):
    """1,024 rows (1,000 series, 24 rows unused) x 24 scrapes: scrape 0
    through the write path and the fill after it, against all 24 through the
    write path, cell by cell."""
    from filodb_tpu.core.record import RecordBuilder
    S, N, C, FILL, seed = 1024, 1000, 32, 24, 2**31 + 3
    deploy = _deploy(N, FILL, C)
    ids = np.arange(N)
    b = RecordBuilder(tsbs.schema())
    b.add_series_batch(tsbs.series_labels(ids, deploy),
                       tsbs.scrape_ms(0, deploy), 0.0)
    template = b.build()
    stores = []
    for scrapes in (1, FILL):
        ms, sh = _shard(S, C, tsbs.schema())
        for k in range(scrapes):
            ms.ingest("tsbsfill", 0, dataclasses.replace(
                template, **tsbs.scrape(seed, ids, k, deploy)))
            sh.flush()
        stores.append(sh)
    filled, written = stores
    sid = np.full(S, -1, np.int64)
    sid[:N] = ids
    with pytest.raises(RuntimeError, match="not as the write path"):
        tsbs.check_filled(filled, sid, deploy)
    tsbs.fill(filled, sid, seed, deploy)
    assert tsbs.check_filled(filled, sid, deploy) == set(
        filled.store.val.devices())
    tsbs.check_filled(written, sid, deploy)
    a, w = filled.store, written.store
    assert a.stamp_form == w.stamp_form == "grid" and a.grid_ok and w.grid_ok
    for x, y in ((a.val, w.val), (a.ts, w.ts)):
        np.testing.assert_array_equal(np.asarray(x)[:N, :FILL],
                                      np.asarray(y)[:N, :FILL])
    np.testing.assert_array_equal(np.asarray(a.n), np.asarray(w.n))
    assert not np.asarray(a.val)[N:].any() and not np.asarray(a.n)[N:].any()
    for x, y in ((a.n_host, w.n_host), (a.last_ts, w.last_ts),
                 (a.first_ts, w.first_ts)):
        np.testing.assert_array_equal(x, y)
    assert a.grid_info() == w.grid_info() == (BASE, IV)
    assert filled.lead_ms == written.lead_ms
    assert tsbs.landed(filled, 0, FILL - 1) and not tsbs.landed(filled, 0,
                                                                FILL)
    assert tsbs.landed(filled, np.arange(N), FILL - 1).all()
    np.testing.assert_array_equal(
        np.asarray(a.val)[:N, :FILL],
        tsbs.datagen.walk_np(seed, ids, FILL - 1).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(a.ts)[:N, :FILL],
        np.broadcast_to(BASE + np.arange(FILL) * IV, (N, FILL)))


def test_tsbs_fill_walks_a_store_taller_than_its_row_block(tsbs, monkeypatch):
    """Row blocks tile the store; the last one may lap over the one before
    (a walk is a function of the row: written twice, the same)."""
    from filodb_tpu.core.record import RecordBuilder
    monkeypatch.setattr(tsbs._fill, "ROWS", 96)
    S, C, FILL, seed = 256, 16, 12, 9
    deploy = _deploy(S, FILL, C)
    ids = np.arange(S)
    b = RecordBuilder(tsbs.schema())
    b.add_series_batch(tsbs.series_labels(ids, deploy),
                       tsbs.scrape_ms(0, deploy), 0.0)
    ms, sh = _shard(S, C, tsbs.schema())
    ms.ingest("tsbsfill", 0, dataclasses.replace(
        b.build(), **tsbs.scrape(seed, ids, 0, deploy)))
    sh.flush()
    tsbs.fill(sh, ids.astype(np.int64), seed, deploy)
    tsbs.check_filled(sh, ids.astype(np.int64), deploy)
    np.testing.assert_array_equal(
        np.asarray(sh.store.val)[:, :FILL],
        tsbs.datagen.walk_np(seed, ids, FILL - 1).astype(np.float32))


def test_tsbs_a_store_that_takes_its_stamp_block_for_a_gather_is_refused(tsbs):
    """The parent commit's store: the fill stops before it writes."""
    class Old:
        C, n_host = 32, np.ones(4, np.int32)

    class Shard:
        store, shard_num = Old(), 0

    with pytest.raises(RuntimeError, match="whole stamp block"):
        tsbs.fill(Shard(), np.arange(4), 1, _deploy(4, 8, 32))


# ---- the served path against the twin --------------------------------------

class Served:
    """A small deployment served as the benchmark serves it: registered and
    filled by ``served.build``, the first live scrape landed."""

    def __init__(self, module, series: int, seed: int, break_scrape=None):
        self.data, self.seed, self.series = module, seed, series
        self.deploy = _deploy(series)
        self.dir = tempfile.mkdtemp(prefix="tsbs_test_")
        self.srv = served.start_server(self.deploy, self.dir)
        try:
            built = served.build(self.srv, self.deploy, seed, module)
            self.sids = built["sids"]
            (self.writer,) = built["writers"]
            self.col = int(self.deploy["fill_columns"])
            live = break_scrape or module
            self.writer.data = live
            for j in range(len(self.writer.templates)):
                self.writer.publish(j, self.col, seed)
            self.writer.data = module
            self.writer.drain()
        except BaseException:
            self.close()
            raise
        self.port = self.srv.http.port
        self.dataset = self.srv.config["dataset"]
        self.head_ms = module.scrape_ms(self.col, self.deploy)

    def readback(self, n: int = 2):
        lo, hi, _ = self.writer.templates[-1]
        rec = {"writer": self.writer, "row": lo, "col": self.col,
               "rows": hi - lo}
        return correct.readback(self.port, self.dataset, self.deploy,
                                self.seed, rec, n)

    def close(self):
        served.stop_server(self.srv)


@pytest.fixture(scope="module")
def sound(tsbs):
    s = Served(tsbs, 2048, 2**31 + 41)
    yield s
    s.close()


KINDS = [(k, i) for i, k in enumerate(
    ["single-groupby-1-1-1", "single-groupby-1-8-1"]
    + ["single-groupby-5-1-1"] * 5 + ["single-groupby-5-8-1"] * 5)]


@pytest.mark.parametrize("kind, i", KINDS,
                         ids=[f"{k}.{i}" for k, i in KINDS])
def test_tsbs_served_answer_of_every_text_kind_is_the_twins(sound, kind, i):
    """The mix's block of twelve, drawn over the hosts this store holds,
    over the mix's own range at each of its three placements: the served
    integers are the twin's, exactly, on the narrow route."""
    mix = _gen().generate(seed=5, hosts=sound.series // 10, draws=1)
    q = mix["queries"][i]
    assert q["tsbs"] == kind and mix["expect_routes"] == ["local-gather"]
    (rng,) = mix["ranges"]
    for b, back in enumerate(rng["end_back_s"]):
        # a phase of its own a query, as the generator's 1009 ms stride
        # gives: the fragment cache must not extend one from another
        end = sound.head_ms - 1000 * back - 137 * (i + 1) - 1009 * b
        start = end - 1000 * rng["range_s"]
        r = served.query_range(sound.port, sound.dataset, q["promql"], start,
                               end, 1000 * rng["step_s"])
        assert r["code"] == 200
        assert r["body"]["stats"]["exec_path"] == "local-gather"
        out_ts = np.arange(start, end + 1, 1000 * rng["step_s"])
        got = served.answer_rows(r["body"], out_ts, 1000 * rng["step_s"])
        ref = q["ref"]
        want = twin.evaluate(sound.seed, sound.series, ref["metric"],
                             ref["hosts"], ref["agg"], ref["fn"],
                             ref["window_s"], out_ts.tolist(), sound.col)
        assert set(got) == {()} and len(out_ts) == 61
        np.testing.assert_array_equal(got[()], np.asarray(want))
        assert not np.isnan(got[()]).any()
        mine = sound.data.evaluate(sound.seed, sound.sids, ref, out_ts,
                                   sound.deploy, sound.col)
        assert correct.err_ratio(got, mine, 2e-4, 1e-4) == 0.0


def test_tsbs_probes_read_back_exactly_on_a_sound_store(sound):
    err, lines = sound.readback()
    assert err == 0.0, lines
    texts = " ".join(lines)
    assert len(lines) == 2 * 2 + 2
    assert "cpu_usage_idle{hostname=" in texts and "timestamp(" in texts \
        and "count by (os)(cpu_usage_user{rack=" in texts \
        and "max(max_over_time(cpu_usage_user{hostname=~" in texts
    lo, hi, _ = sound.writer.templates[-1]
    ps = sound.data.probes(sound.seed, np.arange(lo, hi), sound.col,
                           sound.deploy, 2)
    last = ps[-1]
    assert last["promql"].count("host_") == 8 and len(last["want"]) == 1
    # the last window holds the landed scrape, the first one does not
    assert last["end_ms"] == sound.head_ms
    assert last["start_ms"] + 60_000 > sound.head_ms - 4 * IV
    tag = ps[-2]
    total = sum(int(w[0]) for _labels, w in tag["want"])
    d = sound.data.datagen.tag_draws(np.arange(sound.series // 10 + 1))
    assert 1 <= total <= 8 and all(
        (w == w[0]).all() and len(w) == 4 for _l, w in tag["want"])
    assert total == max(
        int(((d["rack"] == r) & (d["region"] == g)).sum())
        for r in range(100) for g in range(9)
        if f'rack="{r}"' in tag["promql"]
        and sound.data.datagen.REGIONS[g] + '"' in tag["promql"])


def _variant(tsbs, **over):
    """The module with some of its functions replaced (a store at fault)."""
    mod = types.SimpleNamespace(**{k: getattr(tsbs, k) for k in dir(tsbs)
                                   if not k.startswith("__")})
    for k, v in over.items():
        setattr(mod, k, v)
    return mod


FAULTS = ["values", "stamps", "tag", "leaf"]


@pytest.mark.parametrize("fault", FAULTS)
def test_tsbs_each_probe_fails_on_the_store_it_is_there_to_catch(tsbs, fault):
    """(a) the idle series' values, (a') their stamps, (b) a drawn tag,
    (c) the eight hosts' usage_user under the timed leaf: each broken alone,
    the probe that names it misses and the others read back."""
    seed = 2**31 + 43
    idle = tsbs.datagen.FIELDS.index("usage_idle")
    over, broken = {}, None
    if fault in ("values", "leaf"):
        field = idle if fault == "values" else 0

        def scrape(seed_, ids, k, deploy):
            out = tsbs.scrape(seed_, ids, k, deploy)
            hit = np.asarray(ids) % 10 == field
            # +101 clears every other series' values: a max then shows it
            out["values"] = np.where(hit, out["values"] + 101.0,
                                     out["values"])
            return out
        broken = _variant(tsbs, scrape=scrape)
    elif fault == "stamps":
        def scrape(seed_, ids, k, deploy):
            out = tsbs.scrape(seed_, ids, k, deploy)
            out["ts"] = out["ts"] + 1          # a millisecond late
            return out
        broken = _variant(tsbs, scrape=scrape)
    else:
        def series_labels(ids, deploy):
            out = tsbs.series_labels(ids, deploy)
            out["region"] = ["eu-west-9" if r == "eu-west-1" else r
                             for r in out["region"]]
            return out
        over["series_labels"] = series_labels
    mod = _variant(tsbs, **over)
    if fault == "tag":
        # pid_series compares the index with the module's own labels: it is
        # the PROBE that has to notice, so the harness sees the variant
        s = Served(mod, 1024, seed)
        s.writer.data = tsbs
    else:
        s = Served(tsbs, 1024, seed, break_scrape=broken)
    try:
        lo, hi, _ = s.writer.templates[-1]
        ids = np.arange(lo, hi)
        failed = []
        probes = tsbs.probes(seed, ids, s.col, s.deploy, 2)
        if fault == "tag":
            # take a probe whose region is the one registered wrongly
            rng_hosts = [h for h in range(s.series // 10)
                         if twin.labels_of(10 * h)["region"] == "eu-west-1"]
            probes[-2] = tsbs._tag_probe(rng_hosts[0], s.deploy, {
                k: probes[-2][k] for k in ("start_ms", "end_ms", "step_ms")})
        for p in probes:
            r = served.query_range(s.port, s.dataset, p["promql"],
                                   p["start_ms"], p["end_ms"], p["step_ms"])
            out_ts = np.arange(p["start_ms"], p["end_ms"] + 1, p["step_ms"])
            got = [(set(k), v) for k, v in served.answer_rows(
                r["body"], out_ts, p["step_ms"]).items()]
            ok = True
            for labels, want in p["want"]:
                mine = [v for k, v in got if labels.items() <= k]
                ok &= len(mine) == 1 and bool((mine[0] == want).all())
            if not ok:
                failed.append(p["promql"].split("{")[0])
        want = {"values": ["cpu_usage_idle", "cpu_usage_idle"],
                "stamps": ["timestamp(cpu_usage_idle"] * 2,
                "tag": ["count by (os)(cpu_usage_user"],
                "leaf": ["max(max_over_time(cpu_usage_user"]}[fault]
        if fault == "stamps":
            # a sample a millisecond late is not there yet AT its step: the
            # values read there may miss too; the stamps must
            assert failed.count(want[0]) == 2 and set(failed) <= {
                want[0], "cpu_usage_idle", "max(max_over_time(cpu_usage_user"}
        else:
            assert failed == want
    finally:
        s.close()


# ---- the traffic file, the configuration, the cell --------------------------

def test_tsbs_traffic_file_is_what_its_script_writes_from_its_seed():
    gen = _gen()
    mix = traffic.load("tsbs_single")
    assert mix == json.loads(json.dumps(gen.generate()))
    assert (gen.SEED, gen.HOSTS, gen.DRAWS) == (41, 100_000, 32)
    qs = mix["queries"]
    assert len(qs) == 384 == len({q["promql"] for q in qs})
    for lo in range(0, 384, 12):
        blk = qs[lo:lo + 12]
        assert [q["tsbs"] for q in blk] == [k for k, _ in KINDS]
        assert [q["ref"]["metric"] for q in blk] == (
            ["cpu_usage_user"] * 2 + list(gen.FIRST_FIVE) * 2)
        assert [len(q["ref"]["hosts"]) for q in blk] == (
            [1, 8] + [1] * 5 + [8] * 5)
        assert len({tuple(q["ref"]["hosts"]) for q in blk[2:7]}) == 1
        assert len({tuple(q["ref"]["hosts"]) for q in blk[7:]}) == 1
        for q in blk:
            h = q["ref"]["hosts"]
            assert h == sorted(set(h)) and 0 <= h[0] and h[-1] < 100_000
            assert q["ref"] == {"agg": "max", "fn": "max_over_time",
                                "window_s": 60, "metric": q["ref"]["metric"],
                                "hosts": h}
            assert q["promql"] == data.load("tsbs_cpu").text_of(q["ref"])
    assert np.mean([len(q["ref"]["hosts"]) for q in qs]) == 4.5
    hosts = np.asarray([h for q in qs for h in q["ref"]["hosts"]])
    assert hosts.min() < 5_000 and hosts.max() > 95_000     # over them all
    assert mix["clients"] == 8 and mix["order"] == "shared_deck"
    assert mix["warmup"] == "deck" and mix["tenant"] is None
    assert mix["ranges"] == [{"range_s": 3600, "step_s": 60,
                              "end_back_s": [0, 1800, 3300]}]
    assert mix["expect_routes"] == ["local-gather"]
    for key in ("rendering", "warm_caches", "cache_defeat", "source"):
        assert key in mix
    assert "five queries" in mix["rendering"]
    # every window of every card lies inside the 2 h held, lookback and all
    head = BASE + 720 * IV
    g = traffic.Generator(mix, 5, head)
    warm = g.warmup()
    assert len(warm) == 384 * 3
    for r in warm + [g.next(c % 8) for c in range(1200)]:
        assert r.end_ms <= head and r.start_ms - 60_000 >= BASE
        assert len(r.out_ts()) == 61


def test_tsbs_configuration_cell_and_layers_are_as_named():
    """By membership and order, never by the tail: entries appended after
    these change nothing here."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    confs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    conf, cell = confs["tsbs_cpu_100k"], cells["tsbs_single"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tsbs_cpu_100k", "tsbs_single", 1)
    assert bench["configs"].index(confs["promdev_prom_miss_1m"]) \
        < bench["configs"].index(conf)
    assert bench["workloads"].index(cells["adhoc_prom_miss"]) \
        < bench["workloads"].index(cell)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert "9.66 GB" in cell["why"] and "8 rows of 2^20" in cell["why"]
    with open(os.path.join(ROOT, conf["file"])) as f:
        d = json.load(f)
    with open(os.path.join(BENCH, "configs", "promdev_raw_1m.json")) as f:
        raw = json.load(f)
    assert d["source"] == conf["source"] and len(d["source"]) <= 200
    for word in ("timescale/tsbs", "cpu-only", "100000", "10s",
                 "timeseries-dev-source.conf"):
        assert word in d["source"], word
    assert d["architecture"] is None and d["data"] == "tsbs_cpu"
    assert d["reduced"] == conf["reduced"] == ["history"]
    assert "3 days" in d["reduced_why"]["history"] \
        and "12 h" in d["reduced_why"]["history"] \
        and "2 h" in d["reduced_why"]["history"]
    assert (d["series"], d["hosts"]) == (1_000_000, 100_000)
    assert d["server"] == raw["server"]
    for key in ("scrape_interval_ms", "fill_columns",
                "containers_per_scrape"):
        assert d[key] == raw[key], key
    assert served.chunk_of(d) == 125_000          # 12,500 hosts a container
    stated = dict(d["guarantees"])
    assert stated.pop("integers").startswith(
        "every answer of max / max_over_time is the stored integer, exactly")
    assert stated == raw["guarantees"]
    assert {"samples_per_series", "fill_columns", "containers", "tags",
            "values", "stamps", "queries", "regex_caches"} <= set(
                d["assumed"])
    assert "DEPARTURE" in d["assumed"]["values"]
    metrics = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("query_p50_ms", "leaf_ms"):
        lists = metrics[name]["workloads"]
        assert lists.index("adhoc_prom_miss") < lists.index("tsbs_single")
    assert "tsbs_single" not in metrics["kernel_roofline_pct"]["workloads"]
    assert "tsbs_single" not in metrics["query_p95_ms"]["workloads"]
    names = [m["name"] for m in bench["per_layer"]]
    new = ["gather_mean_ms", "selected_series_mean", "matcher_miss_pct",
           "leaf_device_ms"]
    assert [n for n in names if n in new] == new
    assert names.index("fall_tiles_pct") < names.index("gather_mean_ms")
    for name, unit, source in (("gather_mean_ms", "ms", "program_span"),
                               ("selected_series_mean", "series",
                                "program_span"),
                               ("matcher_miss_pct", "%", "program_span"),
                               ("leaf_device_ms", "ms", "device_trace")):
        assert metrics[name] == {
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": "leaf under the shard lock", "moves": "query_rate",
            "workloads": ["tsbs_single"]}
        assert os.path.isfile(os.path.join(BENCH, "layers", f"{name}.py"))
    # accepted metrics whose readers find nothing in a cell that runs no
    # fused program and no grouping: listed for the cells that report them
    accepted = [w["name"] for w in bench["workloads"]
                if w["name"] != "tsbs_single"]
    for name in ("groupids_mean_ms", "kernel_host_mean_ms"):
        assert metrics[name]["workloads"] == accepted[:6]
    assert metrics["device_ahead_mean"]["workloads"] == [
        c for c in accepted[:6] if c != "dash_live"]
    for f in ("data/tsbs_cpu/__init__.py", "data/tsbs_cpu/datagen.py",
              "data/tsbs_cpu/fill.py", "data/tsbs_cpu/reference.py",
              "traffic/tsbs_single.json", "traffic/tsbs_single_gen.py",
              "configs/tsbs_cpu_100k.json"):
        assert os.path.isfile(os.path.join(BENCH, f)), f
    assert math.isclose(2**20 * 768 * 12 / 1e9, 9.66, abs_tol=0.01)
