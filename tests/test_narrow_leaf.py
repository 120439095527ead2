"""The narrow leaf (query/exec.py): a selection of a few series by matcher.

What PR 41 found at 2^20 x 768 and what it records: a gather of a grid-form
store's rows derives their stamps from each row's first stamp — the s64
``[S, C]`` block is no operand of it (tests/test_tpu_compile.py holds what
the TPU's compiler makes of the other way) — the leaf says how it took its
rows (``route`` on its select span, ``/metrics``, the query's exec path:
``local-gather``), its select span says what the matchers were and whether
the index had to resolve them, and the gather has a span of its own.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from filodb_tpu.core import filters as F
from filodb_tpu.core.chunkstore import TS_PAD
from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE
from filodb_tpu.query import exec as qexec
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.utils.metrics import (FILODB_INDEX_RESOLVE,
                                      FILODB_QUERY_LEAF, registry)
from filodb_tpu.utils.tracing import (SPAN_QUERY_GATHER, SPAN_QUERY_LEAF,
                                      SPAN_QUERY_SELECT, tracer)

BASE, IV = 1_700_000_000_000, 10_000
HOSTS, FIELDS, SCRAPES = 24, 4, 40


def labels(host: int, field: int) -> dict:
    return {"_metric_": f"cpu_f{field}", "hostname": f"host_{host}",
            "os": ("a", "b", "c")[host % 3]}


def value(host: int, field: int, k: int) -> float:
    return float((host * 37 + field * 11 + k * (1 + host % 5)) % 101)


def mk(late_hosts=(), cap=128, capacity=64):
    """HOSTS x FIELDS series on the exact grid; ``late_hosts`` appear five
    scrapes in (churn: another start cohort)."""
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", GAUGE, 0, StoreConfig(
        max_series_per_shard=cap, samples_per_series=capacity,
        flush_batch_size=10**9))
    for k in range(SCRAPES):
        b = RecordBuilder(GAUGE)
        for h in range(HOSTS):
            if h in late_hosts and k < 5:
                continue
            for f in range(FIELDS):
                b.add(labels(h, f), BASE + k * IV, value(h, f, k))
        sh.ingest(b.build())
        sh.flush()
    return ms, sh, QueryEngine(ms, "prometheus")


def want_max(hosts, field, out_ts, window_ms, late_hosts=()):
    out = []
    for t in out_ts:
        vals = [value(h, field, k) for h in hosts for k in range(SCRAPES)
                if t - window_ms <= BASE + k * IV <= t
                and not (h in late_hosts and k < 5)]
        out.append(max(vals) if vals else np.nan)
    return np.asarray(out)


def leaf_counts() -> dict:
    out = {}
    for (name, tags), m in list(registry._metrics.items()):
        if name in (FILODB_QUERY_LEAF, FILODB_INDEX_RESOLVE):
            t = dict(tags)
            out[t.get("route") or "resolve:" + t["outcome"]] = m.value
    return out


# -- the gather's stamps --------------------------------------------------------

@pytest.mark.parametrize("late", [(), (3, 4, 20)], ids=["one-cohort", "churn"])
def test_a_grid_stores_gathered_stamps_are_the_blocks_own(late):
    _ms, sh, _eng = mk(late)
    st = sh.store
    assert st.stamp_form == "grid" and st.grid_info() is not None
    ts, val, n = st.arrays()
    assert ts is st.ts
    derive = st.grid_row_gather()
    assert derive is not None
    rng = np.random.default_rng(5)
    live = len(sh.index)
    for m in (1, 3, 8, 9, 40):
        rows = np.sort(rng.choice(live, m, replace=False)).astype(np.int32)
        a = qexec._gather_rows_padded(ts, val, n, rows)
        b = qexec._gather_rows_padded(ts, val, n, rows, derive)
        assert a[3] == b[3] == qexec._pow2(m)
        for x, y in zip(a[:3], b[:3]):
            assert x.dtype == y.dtype and x.shape == y.shape
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        got = np.asarray(b[0])
        assert (got[m:] == TS_PAD).all() and not np.asarray(b[2])[m:].any()
        nn = np.asarray(b[2])[:m]
        assert (got[np.arange(m), nn - 1] < TS_PAD).all()
        assert (got[np.arange(m), nn] == TS_PAD).all()


def test_a_line_store_hands_out_no_grid_stamps():
    """Off the grid the stamps are line + residual: the gather asks the
    deferred view, as before."""
    ms, sh, eng = mk()
    b = RecordBuilder(GAUGE)
    for h in range(HOSTS):
        for f in range(FIELDS):
            b.add(labels(h, f), BASE + SCRAPES * IV + (7 if h == 2 else 0),
                  1.0)
    sh.ingest(b.build())
    sh.flush()
    st = sh.store
    assert st.stamp_form == "line" and st.grid_row_gather() is None
    end = BASE + SCRAPES * IV + 7
    r = eng.query_range('timestamp(cpu_f1{hostname=~"host_2|host_5"})',
                        end, end, IV)
    got = {k.as_dict()["hostname"]: float(np.asarray(r.matrix.values)[i, 0])
           for i, k in enumerate(r.matrix.keys)}
    assert got == {"host_2": end / 1000.0, "host_5": (end - 7) / 1000.0}
    assert r.exec_path == "local-gather"


class _NoOperand:
    """Stands where the store's s64 block stands; any use of it but its
    shape is the fault this PR mends."""

    def __init__(self, real):
        self.shape, self.dtype, self.ndim = real.shape, real.dtype, 2

    def __getattr__(self, name):
        raise AssertionError(f"the stamp block was used ({name})")

    def __jax_array__(self):
        raise AssertionError("the stamp block became an operand")


@pytest.mark.parametrize("late", [(), (3, 4, 20)], ids=["one-cohort", "churn"])
def test_the_narrow_leaf_of_a_grid_store_never_takes_the_stamp_block(late):
    """Served answers — a window function the grid kernels do not have, an
    instant selector, ``timestamp()`` — with the block made untouchable."""
    _ms, sh, eng = mk(late)
    st = sh.store
    real = st.ts
    hosts = [2, 3, 4, 9, 11, 17, 20, 23]
    alt = "|".join(f"host_{h}" for h in hosts)
    out_ts = np.arange(BASE + 30_000 + 137, BASE + 390_000, 30_000)
    st.ts = _NoOperand(real)
    try:
        r = eng.query_range(f'max(max_over_time(cpu_f2{{hostname=~"{alt}"}}'
                            f'[1m]))', int(out_ts[0]), int(out_ts[-1]),
                            30_000)
        one = eng.query_range('cpu_f0{hostname="host_4"}', int(out_ts[0]),
                              int(out_ts[-1]), 30_000)
        stamps = eng.query_range('timestamp(cpu_f0{hostname="host_4"})',
                                 int(out_ts[0]), int(out_ts[-1]), 30_000)
    finally:
        st.ts = real
    assert r.exec_path == one.exec_path == stamps.exec_path == "local-gather"
    np.testing.assert_array_equal(
        np.asarray(r.matrix.values)[0], want_max(hosts, 2, out_ts, 60_000,
                                                 late))
    held = (out_ts - BASE) // IV
    seen = ~((4 in late) & (held < 5))
    np.testing.assert_array_equal(
        np.asarray(one.matrix.values)[0][seen],
        np.asarray([value(4, 0, int(k)) for k in held])[seen])
    np.testing.assert_array_equal(
        np.asarray(stamps.matrix.values)[0][seen],
        ((BASE + held * IV) / 1000.0)[seen])
    assert np.isnan(np.asarray(one.matrix.values)[0][~seen]).all()


# -- what the leaf says of itself ---------------------------------------------

def test_a_narrow_plan_reads_local_gather_and_others_as_they_did():
    _ms, _sh, eng = mk()
    end = BASE + 390_000
    q = lambda text: eng.query_range(text, end - 300_000, end, 30_000)  # noqa: E731
    assert q('max(max_over_time(cpu_f1{hostname=~"host_1|host_2"}[1m]))'
             ).exec_path == "local-gather"
    assert q('cpu_f1{hostname="host_7"}').exec_path == "local-gather"
    assert q('cpu_f1{hostname="host_77"}').exec_path == "local-gather"
    assert q('cpu_f1{hostname="host_1"} + cpu_f2{hostname="host_1"}'
             ).exec_path == "local-gather"
    # more than half the index: the store's own blocks, n zeroed outside
    assert q('max(max_over_time({_metric_=~"cpu_f.*"}[1m]))').exec_path \
        == "local"
    assert q('cpu_f1{hostname="host_1"} + on() group_left() '
             'max({_metric_=~"cpu_f.*"})').exec_path == "local"
    # a gathered selection a fused program answers keeps its name
    fused = q('sum(rate(cpu_f1{hostname=~"host_1|host_2"}[1m]))').exec_path
    assert fused.startswith("local-fused[")


def test_the_select_span_says_matchers_resolution_and_route_and_the_gather_its_rows():
    _ms, sh, eng = mk()
    end = BASE + 390_000
    text = 'max(max_over_time(cpu_f3{hostname=~"host_5|host_6|host_8",os!="z"}[1m]))'
    before = leaf_counts()
    seen = []
    for shift in (0, 1009):
        tracer.drain()
        eng.query_range(text, end - 300_000 - shift, end - shift, 30_000)
        spans = tracer.drain()
        (sel,) = [s for s in spans if s.name == SPAN_QUERY_SELECT]
        (gat,) = [s for s in spans if s.name == SPAN_QUERY_GATHER]
        (leaf,) = [s for s in spans if s.name == SPAN_QUERY_LEAF]
        # since PR 42 the gather is in the leaf's one program: its span is
        # that program's dispatch, beside the select span, not inside it
        assert gat.parent_id == leaf.span_id == sel.parent_id
        assert gat.tags == {"shard": 0, "rows": 3, "padded": 8,
                            "decode": "raw",
                            "bytes": 3 * sh.store.C * (4 + 8),
                            "programs": 1}
        assert sel.tags["series"] == 3 and sel.tags["route"] == "gather"
        assert sel.tags["matchers"] == "eq+ne+re" and sel.tags["memo"] \
            == "bypass"
        seen.append(sel.tags["resolve"])
    assert seen == ["miss", "hit"]      # the index's filter cache had it
    # a wide selection: no gather span
    wide = []
    for shift in (0, 1009):
        tracer.drain()
        eng.query_range('max(max_over_time({_metric_=~"cpu_f.*"}[1m]))',
                        end - 300_000 - shift, end - shift, 30_000)
        spans = tracer.drain()
        (sel,) = [s for s in spans if s.name == SPAN_QUERY_SELECT]
        assert not [s for s in spans if s.name == SPAN_QUERY_GATHER]
        wide.append((sel.tags["route"], sel.tags["memo"],
                     sel.tags["resolve"], sel.tags["matchers"]))
    # (96 series: under GATHER_THRESHOLD the memo keeps nothing, yet over
    # half the index the leaf takes the store's own blocks)
    assert wide == [("wide", "bypass", "miss", "re"),
                    ("wide", "bypass", "hit", "re")]
    now = leaf_counts()
    grew = {k: now[k] - before.get(k, 0) for k in now
            if now[k] != before.get(k, 0)}
    assert grew == {"gather": 2, "wide": 2, "resolve:miss": 2,
                    "resolve:hit": 2}


def test_an_empty_selection_is_a_gather_of_no_row():
    _ms, _sh, eng = mk()
    tracer.drain()
    r = eng.query_range('max(max_over_time(cpu_f1{hostname=~"host_999"}[1m]))',
                        BASE, BASE + 60_000, 30_000)
    spans = tracer.drain()
    (sel,) = [s for s in spans if s.name == SPAN_QUERY_SELECT]
    assert sel.tags["series"] == 0 and sel.tags["route"] == "gather"
    assert not [s for s in spans if s.name == SPAN_QUERY_GATHER]
    assert r.exec_path == "local-gather" and r.matrix.num_series == 0


def test_filter_kinds_name_every_filter_class():
    kinds = {c.KIND for c in (F.Equals, F.NotEquals, F.In, F.EqualsRegex,
                              F.NotEqualsRegex)}
    assert kinds == {"eq", "ne", "in", "re", "nre"}
    assert "KIND" not in {f.name for f in __import__("dataclasses").fields(
        F.EqualsRegex)}
    assert isinstance(jnp.zeros(1), jnp.ndarray)
