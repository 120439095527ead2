"""The ``tsbs_cpu_d8`` data module (``benchmark/data/tsbs_cpu_d8/``), the
mix ``tsbs_single_12h`` and the cell of that name: TSBS DevOps ``cpu-only``
held for 12 h as one byte a sample.

Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` (tier-1
collects these too: ``tests/test_benchmark_data.py``). Every case bears a
name of its own (``d8`` in it): that file star-imports.

- what the module takes from ``tsbs_cpu`` is taken, not copied; a program
  without the narrow-born store is refused when the module is loaded;
- the fill's deltas against the law's walk and against the same scrapes
  sent through the write path into the narrow form, cell by cell, mirrors
  and the scraper's seeded state included; what ``check_filled`` refuses;
- the plain reference at the new depth against its brute-force twin
  (``tests/tsbs_reference.py``);
- the served path under ``compressed_residency: gauge`` over the mix's own
  12 h range for each of its twelve text kinds: one program, decoded inside
  it, the stored integers exactly;
- probes on a sound store, and each on the store it is there to catch: a
  wrong anchor, one delta, the append's column, a tag;
- ``query_bytes``; the traffic file against what its script writes from
  its seed; the configuration, the cell and the new ``per_layer`` entries
  by membership; the three readers on span fixtures; the cell dry-added to
  a scratch copy and rehearsed from there.
"""

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from benchmark import correct, data, served, traffic  # noqa: E402
from benchmark.run import load_layer                    # noqa: E402
from benchmark.tests import test_tsbs_data as hourly    # noqa: E402
from tests import tsbs_reference as twin                # noqa: E402

BASE, IV = 1_700_000_000_000, 10_000
CELL, CONFIG, MIX = "tsbs_single_12h", "tsbs_cpu_100k_12h", "tsbs_single_12h"
D8_LAYERS = ("resident_bytes_per_sample", "narrow_append_pct", "rehydrates")


def _deploy(series: int = 2048, fill: int = 4416, capacity: int = 4608) -> dict:
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        d = json.load(f)
    d["series"] = series
    d["fill_columns"] = fill
    d["server"]["store"].update(max_series_per_shard=series,
                                samples_per_series=capacity)
    return d


def _gen():
    spec = importlib.util.spec_from_file_location(
        "tsbs_single_12h_gen", os.path.join(BENCH, "traffic",
                                            "tsbs_single_12h_gen.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def d8():
    return data.load("tsbs_cpu_d8")


def _shard(series: int, capacity: int, schema, residency: str = "gauge"):
    from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
    ms = TimeSeriesMemStore()
    sh = ms.setup("d8fill", schema, 0, StoreConfig(
        max_series_per_shard=series, samples_per_series=capacity,
        flush_batch_size=10**9, dtype="float32",
        compressed_residency=residency))
    return ms, sh


def _registered(d8, S, N, C, FILL, seed, scrapes=1, residency="gauge"):
    """A shard with scrapes 0..scrapes-1 of N series through the write
    path; (shard, sid, deploy)."""
    from filodb_tpu.core.record import RecordBuilder
    deploy = _deploy(N, FILL, C)
    ids = np.arange(N)
    b = RecordBuilder(d8.schema())
    b.add_series_batch(d8.series_labels(ids, deploy),
                       d8.scrape_ms(0, deploy), 0.0)
    template = b.build()
    ms, sh = _shard(S, C, d8.schema(), residency)
    for k in range(scrapes):
        ms.ingest("d8fill", 0, dataclasses.replace(
            template, **d8.scrape(seed, ids, k, deploy)))
        sh.flush()
    sid = np.full(S, -1, np.int64)
    sid[:N] = ids
    return sh, sid, deploy


# ---- what is taken, and who is refused ---------------------------------------

def test_d8_takes_the_law_the_labels_and_the_reference_from_tsbs_cpu(d8):
    tsbs = data.load("tsbs_cpu")
    for name in ("schema", "series_labels", "scrape_ms", "scrape",
                 "evaluate", "raw_values", "text_of"):
        assert getattr(d8, name) is getattr(tsbs, name), name
    assert d8.datagen is tsbs.datagen and d8.reference is tsbs.reference
    for name in ("fill", "check_filled", "probes", "query_bytes"):
        assert getattr(d8, name) is not getattr(tsbs, name), name
    assert set(data.INTERFACE) <= set(dir(d8))


def test_d8_a_program_without_the_narrow_born_store_is_refused_on_load(
        d8, monkeypatch):
    """The parent commit's store takes no ``born_narrow``: the module stops
    the run when it is loaded — before a server, a store of 58 GB or a
    device — with SystemExit, as ``data.load`` stops on a missing module."""
    from filodb_tpu.core import chunkstore

    class Parent:
        def __init__(self, max_series, capacity, dtype=None, device=None,
                     nbuckets=0, layout=None, default_col=None):
            pass

    d8._refuse_a_program_without_the_form()          # this program: taken
    monkeypatch.setattr(chunkstore, "SeriesStore", Parent)
    with pytest.raises(SystemExit, match="born in its delta8 form"):
        d8._refuse_a_program_without_the_form()


# ---- the fill ----------------------------------------------------------------

def test_d8_fill_writes_the_walks_deltas_into_the_narrow_form(d8):
    """1,024 rows (1,000 series) x 48 scrapes: scrape 0 through the write
    path and the fill after it, against the law's walk and against all 48
    through the write path into the narrow form, cell by cell."""
    S, N, C, FILL, seed = 1024, 1000, 64, 48, 2**31 + 3
    filled, sid, deploy = _registered(d8, S, N, C, FILL, seed)
    written, _, _ = _registered(d8, S, N, C, FILL, seed, scrapes=FILL)
    with pytest.raises(RuntimeError, match="not as the write path"):
        d8.check_filled(filled, sid, deploy)
    d8.fill(filled, sid, seed, deploy)
    a, w = filled.store, written.store
    assert d8.check_filled(filled, sid, deploy) == set(
        a._narrow[1][0].devices())
    d8.check_filled(written, sid, deploy)
    walk = d8.datagen.walk_np(seed, np.arange(N), FILL - 1)
    dv, anchor = (np.asarray(x) for x in a._narrow[1])
    assert dv.dtype == np.int8 and a.val is None and a.ts is None
    np.testing.assert_array_equal(dv[:N, 1:FILL], np.diff(walk, axis=1))
    assert not dv[:N, 0].any() and not dv[N:].any() and not dv[:, FILL:].any()
    np.testing.assert_array_equal(anchor[:N], walk[:, 0])
    for x, y in ((a.value_block(), w.value_block()),
                 (a.ts_block(), w.ts_block())):
        np.testing.assert_array_equal(np.asarray(x)[:N, :FILL],
                                      np.asarray(y)[:N, :FILL])
    np.testing.assert_array_equal(np.asarray(a.value_block())[:N, :FILL],
                                  walk.astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(a.ts_block())[:N, :FILL],
        np.broadcast_to(BASE + np.arange(FILL) * IV, (N, FILL)))
    np.testing.assert_array_equal(np.asarray(a.n), np.asarray(w.n))
    for x, y in ((a.n_host, w.n_host), (a.last_ts, w.last_ts),
                 (a.first_ts, w.first_ts), (a.last_val, w.last_val),
                 (a.anchor_host, w.anchor_host)):
        np.testing.assert_array_equal(x, y)
    assert a.grid_info() == w.grid_info() == (BASE, IV)
    assert a.rehydrates == w.rehydrates == 0
    assert filled.lead_ms == written.lead_ms
    assert d8.landed(filled, 0, FILL - 1) and not d8.landed(filled, 0, FILL)
    # the scraper goes on from the fill's last column, one step a scrape
    k, x, ids = d8.base._WALKS[(seed, 0, N)]
    assert k == FILL - 1 and (ids == np.arange(N)).all()
    np.testing.assert_array_equal(x, walk[:, -1])
    nxt = d8.scrape(seed, np.arange(N), FILL, deploy)
    np.testing.assert_array_equal(
        nxt["values"], d8.datagen.walk_np(seed, np.arange(N), FILL)[:, -1])


def test_d8_fill_walks_a_store_taller_than_its_row_block(d8, monkeypatch):
    monkeypatch.setattr(d8._fill, "ROWS", 96)
    S, C, FILL, seed = 256, 16, 12, 9
    sh, sid, deploy = _registered(d8, S, S, C, FILL, seed)
    d8.fill(sh, sid, seed, deploy)
    d8.check_filled(sh, sid, deploy)
    np.testing.assert_array_equal(
        np.asarray(sh.store.value_block())[:, :FILL],
        d8.datagen.walk_np(seed, np.arange(S), FILL - 1).astype(np.float32))


D8_CHECKS = ["raw-store", "pooled-row", "a-count", "a-delta"]


@pytest.mark.parametrize("fault", D8_CHECKS)
def test_d8_check_filled_names_a_store_that_is_not_narrow_and_whole(
        d8, fault):
    """A store that residency was not asked of is refused before the fill;
    a row in the raw pool, a row short of a sample and a delta that does not
    add up to the mirror's last value are each refused after it."""
    import jax.numpy as jnp
    S, C, FILL, seed = 64, 16, 12, 11
    if fault == "raw-store":
        sh, sid, deploy = _registered(d8, S, S, C, FILL, seed,
                                      residency="off")
        with pytest.raises(RuntimeError, match="not in its delta8 form"):
            d8.fill(sh, sid, seed, deploy)
        return
    sh, sid, deploy = _registered(d8, S, S, C, FILL, seed)
    d8.fill(sh, sid, seed, deploy)
    d8.check_filled(sh, sid, deploy)
    st = sh.store
    if fault == "pooled-row":
        with sh.lock:
            st._pool_rows(np.array([3], np.int32))
        word = "'pooled': 1"
    elif fault == "a-count":
        st.n_host[5] -= 1
        word = "'n_host': [11, 12]"
    else:
        kind, (dv, anchor), *rest = st._narrow
        st._narrow = (kind, (dv.at[7, 4].add(jnp.int8(1)), anchor), *rest)
        word = "!= the host's last value"
    with pytest.raises(RuntimeError, match="narrow form") as e:
        d8.check_filled(sh, sid, deploy)
    assert word in str(e.value)


# ---- the reference at the new depth, against the twin --------------------------

@pytest.mark.parametrize("agg, fn", [("max", "max_over_time"),
                                     ("min", "min_over_time"),
                                     ("sum", "sum_over_time"),
                                     ("count", "count_over_time")])
def test_d8_reference_at_twelve_hours_against_the_brute_force_twin(d8, agg,
                                                                   fn):
    """Thirteen steps an hour apart over the 12 h a query of the mix
    covers, a minute's window each, eight hosts walked 4,416 scrapes: the
    numpy reference and the plain-Python twin agree exactly."""
    seed, series, head = 2**33 + 5, 4096, 4416
    ref = {"agg": agg, "fn": fn, "window_s": 60, "metric": "cpu_usage_user",
           "hosts": [3, 40, 77, 120, 200, 305, 333, 408]}
    out_ts = BASE + head * IV - 137 - np.arange(12, -1, -1) * 3_600_000
    mine = d8.evaluate(seed, np.arange(series), ref, out_ts, _deploy(series),
                       head)
    want = twin.evaluate(seed, series, ref["metric"], ref["hosts"], agg, fn,
                         60, out_ts.tolist(), head)
    np.testing.assert_array_equal(mine[()], np.asarray(want))
    assert not np.isnan(mine[()]).any()


# ---- the served path ---------------------------------------------------------

class Served(hourly.Served):
    """``test_tsbs_data.Served`` over this deployment's file: born narrow,
    filled in that form, the first live scrape landed in place."""

    def __init__(self, module, series, seed, fill=4416, capacity=4608):
        self._deploy = _deploy(series, fill, capacity)
        real, hourly._deploy = hourly._deploy, lambda _series: self._deploy
        try:
            super().__init__(module, series, seed)
        finally:
            hourly._deploy = real

    @property
    def store(self):
        return self.writer.shard.store


@pytest.fixture(scope="module")
def deep(d8):
    s = Served(d8, 2048, 2**31 + 44)
    yield s
    s.close()


D8_KINDS = [(k[:-1] + "12", i) for k, i in hourly.KINDS]


@pytest.mark.parametrize("kind, i", D8_KINDS,
                         ids=[f"{k}.{i}" for k, i in D8_KINDS])
def test_d8_served_answer_of_every_text_kind_over_twelve_hours(deep, kind, i):
    """The mix's block of twelve over the hosts this store holds, over the
    mix's own range (721 steps) at one of its three placements: one
    program, decoded from the delta8 block inside it, the numpy reference's
    integers exactly — and the twin's at every sixtieth step."""
    from filodb_tpu.utils.tracing import tracer
    mix = _gen().generate(seed=5, hosts=deep.series // 10, draws=1)
    q = mix["queries"][i]
    assert q["tsbs"] == kind and mix["expect_routes"] == ["local-gather"]
    (rng,) = mix["ranges"]
    back = rng["end_back_s"][i % 3]
    end = deep.head_ms - 1000 * back - 137 * (i + 1)
    start = end - 1000 * rng["range_s"]
    tracer.drain()
    r = served.query_range(deep.port, deep.dataset, q["promql"], start, end,
                           1000 * rng["step_s"])
    gathers = [s for s in tracer.drain() if s.name == "query.exec.gather"]
    assert r["code"] == 200
    assert r["body"]["stats"]["exec_path"] == "local-gather"
    assert [(g.tags["programs"], g.tags["decode"]) for g in gathers] == [
        (1, "delta8")]
    out_ts = np.arange(start, end + 1, 1000 * rng["step_s"])
    got = served.answer_rows(r["body"], out_ts, 1000 * rng["step_s"])
    ref = q["ref"]
    assert set(got) == {()} and len(out_ts) == 721
    assert not np.isnan(got[()]).any()
    mine = deep.data.evaluate(deep.seed, deep.sids, ref, out_ts, deep.deploy,
                              deep.col)
    np.testing.assert_array_equal(got[()], mine[()])
    assert correct.err_ratio(got, mine, 2e-4, 1e-4) == 0.0
    want = twin.evaluate(deep.seed, deep.series, ref["metric"], ref["hosts"],
                         ref["agg"], ref["fn"], ref["window_s"],
                         out_ts[::60].tolist(), deep.col)
    np.testing.assert_array_equal(got[()][::60], np.asarray(want))
    assert deep.store._inplace and deep.store.rehydrates == 0


def test_d8_probes_read_back_exactly_over_the_whole_depth(deep):
    err, lines = deep.readback()
    assert err == 0.0, lines
    texts = " ".join(lines)
    assert len(lines) == 2 * 2 + 2
    assert "cpu_usage_idle{hostname=" in texts and "timestamp(" in texts \
        and "count by (os)(cpu_usage_user{rack=" in texts \
        and "max(max_over_time(cpu_usage_user{hostname=~" in texts
    lo, hi, _ = deep.writer.templates[-1]
    ps = deep.data.probes(deep.seed, np.arange(lo, hi), deep.col, deep.deploy,
                          2)
    for p in ps:
        steps = np.arange(p["start_ms"], p["end_ms"] + 1, p["step_ms"])
        assert len(steps) == 4 and p["end_ms"] == deep.head_ms
        # the first step in the history's first hour, the last the landed
        assert BASE < p["start_ms"] < BASE + 360 * IV
        assert all(len(w) == 4 for _l, w in p["want"])
    assert ps[-1]["promql"].count("host_") == 8


D8_FAULTS = ["anchor", "delta", "column", "tag"]


@pytest.mark.parametrize("fault", D8_FAULTS)
def test_d8_each_probe_fails_on_the_store_it_is_there_to_catch(d8, fault):
    """A wrong anchor (every sample of the idle rows one too high), one
    delta of the first hour (every later sample of the user rows), the
    append's column (the landed scrape of the idle rows alone), a tag: each
    broken alone in the stored form, the probe that names it misses — the
    values at every step, at all but those before the delta, at the last
    step only — and the others read back."""
    import jax.numpy as jnp
    seed = 2**31 + 45
    if fault == "tag":
        def series_labels(ids, deploy):
            out = d8.series_labels(ids, deploy)
            out["region"] = ["eu-west-9" if r == "eu-west-1" else r
                             for r in out["region"]]
            return out
        s = Served(hourly._variant(d8, series_labels=series_labels), 1024,
                   seed, fill=440, capacity=512)
        s.writer.data = d8
    else:
        s = Served(d8, 1024, seed, fill=440, capacity=512)
    try:
        st = s.store
        field = np.arange(st.S) % 10
        if fault != "tag":
            kind, (dv, anchor), *rest = st._narrow
            if fault == "anchor":
                anchor = anchor + jnp.asarray(field == 2, jnp.float32)
            else:
                col = 30 if fault == "delta" else s.col
                rows = jnp.asarray(field == (0 if fault == "delta" else 2))
                dv = dv.at[:, col].add(rows.astype(jnp.int8))
            with s.writer.shard.lock:
                st._narrow = (kind, (dv, anchor), *rest)
        lo, hi, _ = s.writer.templates[-1]
        probes = d8.probes(seed, np.arange(lo, hi), s.col, s.deploy, 2)
        if fault == "tag":
            hosts = [h for h in range(s.series // 10)
                     if twin.labels_of(10 * h)["region"] == "eu-west-1"]
            probes[-2] = d8.base._tag_probe(hosts[0], s.deploy, {
                k: probes[-2][k] for k in ("start_ms", "end_ms", "step_ms")})
        failed = []
        for p in probes:
            r = served.query_range(s.port, s.dataset, p["promql"],
                                   p["start_ms"], p["end_ms"], p["step_ms"])
            out_ts = np.arange(p["start_ms"], p["end_ms"] + 1, p["step_ms"])
            got = [(set(k), v) for k, v in served.answer_rows(
                r["body"], out_ts, p["step_ms"]).items()]
            for labels, want in p["want"]:
                (mine,) = [v for k, v in got if labels.items() <= k] or [
                    np.full(len(want), np.nan)]
                if not (mine == want).all():
                    failed.append((p["promql"].split("{")[0],
                                   np.flatnonzero(mine != want).tolist()))
        idle, leaf = "cpu_usage_idle", "max(max_over_time(cpu_usage_user"
        if fault == "anchor":
            assert failed == [(idle, [0, 1, 2, 3])] * 2
        elif fault == "column":
            assert failed == [(idle, [3])] * 2
        elif fault == "delta":
            (one,) = failed
            # the first step may lie before scrape 30; a max of eight hosts
            # one too high each is one too high, unless it was clamped at 100
            assert one[0] == leaf and set(one[1]) >= {2, 3} - {
                j for j, w in enumerate(probes[-1]["want"][0][1]) if w >= 100}
        else:
            assert [f[0] for f in failed] == ["count by (os)(cpu_usage_user"]
    finally:
        s.close()


# ---- bytes, the traffic file, the configuration, the cell ----------------------

def test_d8_query_bytes_are_a_byte_a_column_from_the_rows_first_cell(d8):
    d = _deploy()
    ref = {"window_s": 60, "hosts": list(range(8))}
    out_ts = BASE + 4416 * IV - 43_200_000 + np.arange(721) * 60_000
    # decoded from cell 0 to the head's cell, 12 B of row operands beside
    assert d8.query_bytes(1 << 20, ref, out_ts, d, 4416, 4608) == \
        8 * (4417 + 12)
    assert d8.query_bytes(4096, dict(ref, hosts=[5]), out_ts[:1], d, 4416,
                          4608) == (4416 - 4320 + 1) + 12
    assert d8.query_bytes(4096, ref, out_ts - 10**10, d, 4416, 4608) == 0.0


def test_d8_traffic_file_is_what_its_script_writes_from_its_seed(d8):
    gen = _gen()
    mix = traffic.load(MIX)
    assert mix == json.loads(json.dumps(gen.generate()))
    assert (gen.SEED, gen.HOSTS, gen.DRAWS) == (44, 100_000, 32)
    hour = traffic.load("tsbs_single")
    qs = mix["queries"]
    assert len(qs) == 384 == len({q["promql"] for q in qs})
    assert {q["promql"] for q in qs}.isdisjoint(
        q["promql"] for q in hour["queries"])       # draws of its own
    for lo in range(0, 384, 12):
        blk = qs[lo:lo + 12]
        assert [q["tsbs"] for q in blk] == [k for k, _ in D8_KINDS]
        assert [len(q["ref"]["hosts"]) for q in blk] == (
            [1, 8] + [1] * 5 + [8] * 5)
        for q in blk:
            assert q["promql"] == d8.text_of(q["ref"])
            assert q["ref"]["agg"] == "max" and \
                q["ref"]["fn"] == "max_over_time" and \
                q["ref"]["window_s"] == 60
    for key in ("clients", "order", "warmup", "tenant", "expect_routes",
                "cache_defeat", "rendering", "warm_caches"):
        assert mix[key] == hour[key], key
    assert mix["name"] == MIX and "-12" in mix["source"]
    assert mix["ranges"] == [{"range_s": 43_200, "step_s": 60,
                              "end_back_s": [0, 300, 600]}]
    # every window of every card lies inside the 12 h 16 min held
    head = BASE + 4416 * IV
    g = traffic.Generator(mix, 5, head)
    warm = g.warmup()
    assert len(warm) == 384 * 3
    for r in warm + [g.next(c % 8) for c in range(1200)]:
        assert r.end_ms <= head and r.start_ms - 60_000 >= BASE
        assert len(r.out_ts()) == 721


def test_d8_configuration_cell_and_layers_are_as_named_by_membership():
    """Never by the tail: entries appended after these change nothing."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    confs = {c["name"]: c for c in bench["configs"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    conf, cell = confs[CONFIG], cells[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, MIX, 1)
    assert bench["configs"].index(confs["tsbs_cpu_100k"]) \
        < bench["configs"].index(conf)
    assert bench["workloads"].index(cells["tsbs_single"]) \
        < bench["workloads"].index(cell)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert "4.86 GB" in conf["why"] and "721 steps" in cell["why"] \
        and "idle" in cell["why"] and len(cell["why"]) <= 200
    with open(os.path.join(ROOT, conf["file"])) as f:
        d = json.load(f)
    with open(os.path.join(BENCH, "configs", "tsbs_cpu_100k.json")) as f:
        hour = json.load(f)
    assert d["source"] == conf["source"] != hour["source"]
    assert len(d["source"]) <= 200
    for word in ("timescale/tsbs", "cpu-only", "100000", "10s", "-12",
                 "compressed_residency"):
        assert word in d["source"], word
    assert d["architecture"] is None and d["data"] == "tsbs_cpu_d8"
    assert d["reduced"] == conf["reduced"] == ["history"]
    why = d["reduced_why"]["history"]
    assert "3 days" in why and "12 h 16 min" in why and "9.1 GB" in why
    store = dict(d["server"]["store"])
    assert store.pop("compressed_residency") == "gauge"
    assert store.pop("samples_per_series") == 4608 and d["fill_columns"] == 4416
    assert store == {k: v for k, v in hour["server"]["store"].items()
                     if k != "samples_per_series"}
    assert {k: v for k, v in d["server"].items() if k != "store"} == {
        k: v for k, v in hour["server"].items() if k != "store"}
    for key in ("series", "hosts", "scrape_interval_ms",
                "containers_per_scrape"):
        assert d[key] == hour[key], key
    stated = dict(d["guarantees"])
    assert stated.pop("lossless").startswith(
        "the compressed store returns every stored sample and stamp "
        "bit-exactly; a row that does not fit the narrow form is held raw")
    assert stated == hour["guarantees"]
    assert set(hour["assumed"]) <= set(d["assumed"])
    for key in ("containers", "tags", "values", "stamps"):
        assert d["assumed"][key] == hour["assumed"][key], key
    assert abs(2**20 * 4608 / 1e9 - 4.83) < 0.01
    assert abs(2**20 * (4608 + 12) / 1e9 - 4.86) < 0.02
    metrics = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("query_p50_ms", "leaf_ms", "gather_mean_ms",
                 "selected_series_mean", "matcher_miss_pct",
                 "leaf_device_ms", "gather_fused_pct"):
        lists = metrics[name]["workloads"]
        assert lists.index("tsbs_single") < lists.index(CELL), name
    for name in ("kernel_roofline_pct", "kernel_host_mean_ms",
                 "groupids_mean_ms", "device_ahead_mean", "query_p95_ms"):
        assert CELL not in metrics[name]["workloads"], name
    names = [m["name"] for m in bench["per_layer"]]
    assert [n for n in names if n in D8_LAYERS] == list(D8_LAYERS)
    assert names.index("gather_fused_pct") < names.index(D8_LAYERS[0])
    for name, unit, better in zip(D8_LAYERS, ("B/sample", "%", "count"),
                                  ("lower", "higher", "lower")):
        assert metrics[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "program_span", "layer": "write path flush",
            "moves": "query_rate", "workloads": [CELL]}
        assert os.path.isfile(os.path.join(BENCH, "layers", f"{name}.py"))
    for f in ("data/tsbs_cpu_d8/__init__.py", "data/tsbs_cpu_d8/fill.py",
              f"traffic/{MIX}.json", f"traffic/{MIX}_gen.py",
              f"configs/{CONFIG}.json", "control_narrow.py"):
        assert os.path.isfile(os.path.join(BENCH, f)), f


# ---- the three readers ---------------------------------------------------------

def flushes(*tags, others=()):
    spans = [{"name": "ingest.flush", "trace_id": i, "t0": 1.0 + i,
              "dur_s": 0.01, "tags": dict(t)} for i, t in enumerate(tags)]
    return {"spans": spans + [dict(s) for s in others]}


SOUND = {"form": "narrow", "pooled": 0, "rehydrates": 0, "sample_bytes": 1.003}
D8_WINDOWS = {
    "sound": ([SOUND] * 5, (1.003, 100.0, 0.0)),
    "rehydrated-once": ([SOUND, dict(SOUND, form="raw", rehydrates=1,
                                     sample_bytes=12.0),
                         dict(SOUND, form="rebuilt", sample_bytes=1.003),
                         SOUND], (1.003, 50.0, 1.0)),
    "raw-store": ([{"form": "raw", "rehydrates": 0, "sample_bytes": 12.0}] * 3,
                  (12.0, 0.0, 0.0)),
    "mostly-raw": ([dict(SOUND, form="raw", sample_bytes=12.0)] * 2 + [SOUND],
                   (12.0, 100 / 3, 0.0)),
}


@pytest.mark.parametrize("window", list(D8_WINDOWS))
@pytest.mark.parametrize("name", D8_LAYERS)
def test_d8_reader_gives_the_known_value(name, window):
    tags, want = D8_WINDOWS[window]
    got = load_layer(name).read(flushes(*tags, others=[
        {"name": "query", "trace_id": 99, "t0": 1.5, "dur_s": 0.04,
         "tags": {"form": "narrow", "rehydrates": 7, "sample_bytes": 99.0}}]))
    assert got == pytest.approx(want[D8_LAYERS.index(name)], rel=1e-12)


@pytest.mark.parametrize("name", D8_LAYERS)
def test_d8_reader_finds_nothing_in_the_parents_window(name):
    """The parent tags a flush with rows, demoted and holes alone: nothing
    to read, and nothing raised."""
    old = {"rows": 125_000, "demoted": 0, "holes": 0, "lock_wait_ms": 0.1}
    assert load_layer(name).read(flushes(old, old)) is None
    assert load_layer(name).read({"spans": []}) is None


# ---- the cell, dry-added and rehearsed -------------------------------------------

def test_d8_cell_is_dry_added_to_a_scratch_copy_and_rehearsed_there(tmp_path):
    """What this PR adds, laid as new files over a copy of the by-name
    files WITHOUT them, with the cell's entries appended to a BENCHMARK.json
    without them (``rehearse.dry_add``; it copies files, so the data module,
    a package, lies in the copy already): nothing that exists is edited. The
    mix is cut to one host draw over the hosts a 4,096-series store holds
    (the file's draws name hosts up to 100,000); the depth is the cell's
    own. Then the whole of ``run.run`` from there, traced: correct, in
    place, one program a leaf."""
    from benchmark import rehearse
    mine = {"configs": [f"{CONFIG}.json"],
            "traffic": [f"{MIX}.json", f"{MIX}_gen.py"],
            "data": [], "layers": [f"{n}.py" for n in D8_LAYERS]}
    parent, add = tmp_path / "parent", tmp_path / "add"
    for d in rehearse.BY_NAME:
        shutil.copytree(os.path.join(BENCH, d), parent / d,
                        ignore=shutil.ignore_patterns("__pycache__",
                                                      *mine[d]))
        (add / d).mkdir(parents=True)
        for f in mine[d]:
            src = os.path.join(BENCH, d, f)
            (shutil.copytree if os.path.isdir(src) else shutil.copy)(
                src, add / d / f)
    with open(add / "traffic" / f"{MIX}.json", "w") as f:
        json.dump(_gen().generate(seed=7, hosts=409, draws=1), f)
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
    # dry_add names its copy after rehearse.HERE's directory
    os.rename(os.path.join(root, "parent"), os.path.join(root, "benchmark"))
    import argparse
    from benchmark import run as runmod
    args = argparse.Namespace(workload=CELL, seed=2**31 + 44, seconds=3.0,
                              trace=1)
    stub = {"platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": 1}
    res = runmod.run(args, stub, allow_interpret=True,
                     shrink={"series": 4096}, root=root)
    assert res is not None and res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] > 36
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["rehydrates"] == 0 and got["narrow_append_pct"] == 100
    assert got["gather_fused_pct"] == 100
    assert 1.0 < got["resident_bytes_per_sample"] < 1.05
    assert {"leaf_ms", "gather_mean_ms", "selected_series_mean",
            "matcher_miss_pct"} <= set(got)
