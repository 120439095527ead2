"""A store that is BORN narrow (core/chunkstore.py, PR 44).

Under ``store.compressed_residency: gauge`` a scalar gauge store starts in
its delta8 form with stamps elided — ``dv int8 [S, C]``, ``anchor f32 [S]``,
what the host knows of each row — and is appended to, aged out and freed AS
IT IS: the f32 and s64 ``[S, C]`` blocks are never built (at 2^20 x 4,608
they are 19 and 39 GB). Held here against a raw twin fed the same appends:
equal samples and stamps after every step, a row that leaves the contract
pooled and still exact, no rehydrate, and no program of the store with an
operand or a result of the raw blocks' shape.
"""

import numpy as np
import pytest

import jax

from filodb_tpu.core import chunkstore
from filodb_tpu.core.chunkstore import DecodeRefused, SeriesStore
from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.query.rangevector import QueryError
from filodb_tpu.utils.metrics import (FILODB_QUERY_REFUSED,
                                      FILODB_STORE_RESIDENCY_FALLBACK,
                                      registry)
from filodb_tpu.utils.tracing import SPAN_INGEST_FLUSH, tracer

from . import tsbs_reference as twin

B, IV = 1_700_000_000_000, 10_000
S, C = 24, 40


class Audit:
    """Every jitted program of ``core/chunkstore.py``, wrapped: the shapes
    and dtypes of its array operands and results."""

    live = None     # the one being watched (``both`` turns it on for ``a``)

    def __init__(self, monkeypatch):
        self.seen, self.on = [], False
        Audit.live = self
        for name, fn in list(vars(chunkstore).items()):
            if callable(fn) and hasattr(fn, "lower"):
                monkeypatch.setattr(chunkstore, name, self.wrap(name, fn))

    def wrap(self, name, fn):
        def call(*a, **k):
            out = fn(*a, **k)
            if not self.on:
                return out
            for side, tree in (("operand", (a, k)), ("result", out)):
                for x in jax.tree_util.tree_leaves(tree):
                    if hasattr(x, "shape") and hasattr(x, "dtype"):
                        self.seen.append((name, side, tuple(x.shape),
                                          str(x.dtype)))
            return out
        return call

    def raw_blocks(self, shape):
        return [s for s in self.seen if s[2] == tuple(shape)
                and s[3] in ("float32", "float64", "int64")]


def twins(s=S, c=C):
    """A store born narrow and its raw twin: the raw form such a store
    falls back to, which keeps every row from column 0 as it does (no
    birth cells: ``aligned`` is a store's from its birth)."""
    raw = SeriesStore(s, c)
    raw.aligned = False
    return SeriesStore(s, c, born_narrow=True), raw


def same(a, b):
    """Equal samples and stamps, row by row, and equal host mirrors."""
    np.testing.assert_array_equal(a.n_host, b.n_host)
    np.testing.assert_array_equal(np.asarray(a.n), np.asarray(b.n))
    np.testing.assert_array_equal(a.first_ts, b.first_ts)
    np.testing.assert_array_equal(a.last_ts, b.last_ts)
    ta, va = np.asarray(a.ts_block()), np.asarray(a.value_block())
    tb, vb = np.asarray(b.ts_block()), np.asarray(b.value_block())
    for r in range(a.S):
        k = a.n_host[r]
        np.testing.assert_array_equal(ta[r, :k], tb[r, :k])
        assert va[r, :k].tobytes() == vb[r, :k].tobytes(), (r, va[r, :k],
                                                             vb[r, :k])


def both(a, b, what, *args):
    """The same mutation of the narrow store (audited) and of its raw twin,
    then the comparison (which decodes: not audited)."""
    audit = Audit.live
    if audit is not None:
        audit.on = True
    try:
        getattr(a, what)(*args)
    finally:
        if audit is not None:
            audit.on = False
    getattr(b, what)(*args)
    same(a, b)


@pytest.fixture(autouse=True)
def _no_audit_left_over():
    Audit.live = None
    yield
    Audit.live = None


def scrape(a, b, k, rows, values):
    both(a, b, "append", np.asarray(rows), np.full(len(rows), B + k * IV),
         np.asarray(values, np.float64))


def test_born_narrow_holds_no_raw_block_and_a_byte_a_sample():
    a, _ = twins()
    assert a.ts is None and a.val is None and a._inplace
    kind, (dv, anchor), ok = a.narrow_operands()
    assert kind == "delta8" and dv.dtype == np.int8 and dv.shape == (S, C)
    assert anchor.shape == (S,) and ok.all()
    assert a.is_narrow_resident and a.grid_row_gather() is not None
    assert 1.0 < a.resident_bytes_per_sample() < 1.0 + 8 / C + 0.2
    # a shape of store the form does not take is born raw, as without it
    for kw in (dict(nbuckets=4), dict(dtype=jax.numpy.float64)):
        st = SeriesStore(8, 8, born_narrow=True, **kw)
        assert st.ts is not None and st.val is not None and not st._inplace


def test_a_script_of_appends_against_the_raw_twin(monkeypatch):
    """One and several samples a row a batch, new rows mid-stream, rows of
    differing starts: after every step the narrow store decodes to what the
    raw one holds, bit for bit."""
    audit = Audit(monkeypatch)
    a, b = twins()
    rng = np.random.default_rng(1)
    x = rng.integers(0, 100, S).astype(float)
    live = 10
    for k in range(12):
        if k in (3, 7):
            live += 4                       # new rows mid-stream
        x = np.clip(x + rng.integers(-90, 91, S), 0, 127)
        scrape(a, b, k, np.arange(live), x[:live])
    # several samples a row in one batch, the rows in no order (a row's own
    # samples in time's)
    rows = np.repeat(np.arange(live), 3)
    ts = np.tile(B + (12 + np.arange(3)) * IV, live)
    vals = np.clip(np.repeat(x[:live], 3) + rng.integers(-5, 6, len(rows)),
                   0, 127)
    order = rng.permutation(len(rows))
    order = order[np.argsort(ts[order], kind="stable")]
    both(a, b, "append", rows[order], ts[order], vals[order])
    # an out-of-order sample is dropped alike
    both(a, b, "append", np.array([0, 1]), np.array([B, B + 15 * IV]),
         np.array([1.0, 2.0]))
    assert a.stats.out_of_order_dropped == b.stats.out_of_order_dropped == 1
    assert a._inplace and a.rehydrates == 0 and not (a._slot_host >= 0).any()
    assert a.grid_info() == b.grid_info()
    assert a.grid_cohorts()[0] == b.grid_cohorts()[0] == "mixed"
    assert audit.seen and not audit.raw_blocks((S, C))


LEAVERS = {"non-integer": 0.5, "a-jump-of-200": 200.0,
           "past-2^23": float(1 << 24), "a-nan": float("nan")}


@pytest.mark.parametrize("why", list(LEAVERS))
def test_a_row_that_leaves_the_contract_is_pooled_and_still_exact(
        why, monkeypatch):
    audit = Audit(monkeypatch)
    a, b = twins()
    x = np.arange(12.0)
    for k in range(4):
        scrape(a, b, k, np.arange(12), x + k)
    x = x + 4
    if why == "past-2^23":
        # deltas a byte holds cannot walk 2^23 in 40 columns: the row's
        # reference is what a sample is held against
        a.ref_val[5] = -float(1 << 24)
        x[5] += 1
    elif why == "a-nan":
        x[5] = LEAVERS[why]
    else:
        x[5] += LEAVERS[why]
    audit.on = True
    a.append(np.arange(12), np.full(12, B + 4 * IV), x)
    audit.on = False
    b.append(np.arange(12), np.full(12, B + 4 * IV), x)
    if why != "a-nan":
        same(a, b)
    assert a._slot_host[5] == 0 and (np.delete(a._slot_host, 5) < 0).all()
    assert a.pooled_last_append == 1 and not a.narrow_operands()[2][5]
    assert a._inplace and a.rehydrates == 0
    # the row goes on raw, its neighbours narrow
    for k in range(5, 9):
        scrape(a, b, k, np.arange(12), np.arange(12.0) * 2 + k + 0.25 * (
            np.arange(12) == 5))
    got = np.asarray(a.value_block())[5, :9]
    want = np.asarray(b.value_block())[5, :9]
    assert got.tobytes() == want.tobytes()
    assert a.pooled_last_append == 0 and a.rehydrates == 0
    assert not audit.raw_blocks((S, C))
    # decoded by row as by block
    rid = jax.numpy.asarray(np.array([4, 5, 6], np.int32))
    rows = np.asarray(a.column_array().gather_rows(rid))
    assert rows.tobytes() == np.asarray(a.value_block())[[4, 5, 6]].tobytes()


def test_compact_ages_rows_of_differing_starts_out_in_the_narrow_form(
        monkeypatch):
    audit = Audit(monkeypatch)
    a, b = twins()
    rng = np.random.default_rng(3)
    x = rng.integers(0, 100, 16).astype(float)
    for k in range(20):
        live = 8 if k < 6 else 12 if k < 15 else 16
        x = np.clip(x + rng.integers(-3, 4, 16), 0, 100)
        if k == 9:
            x[2] += 0.5                     # a pooled row ages out too
        scrape(a, b, k, np.arange(live), x[:live])
    assert a._slot_host[2] >= 0
    both(a, b, "compact", B + 5 * IV + 1)   # between stamps
    both(a, b, "compact", B + 10 * IV)      # on a stamp: it stays
    np.testing.assert_array_equal(a.n_host[:16],
                                  [10] * 8 + [10] * 4 + [5] * 4)
    both(a, b, "compact", B + 17 * IV)      # empties nothing yet
    both(a, b, "compact", B + 30 * IV)      # everything
    assert not a.n_host.any() and (a.first_ts == -1).all()
    # and the rows start anew
    for k in range(30, 33):
        scrape(a, b, k, np.arange(16), np.arange(16.0) + k)
    assert a._inplace and a.rehydrates == 0 and a.stats.compactions == 4
    assert not audit.raw_blocks((S, C))


def test_compact_runs_in_row_blocks(monkeypatch):
    monkeypatch.setattr(chunkstore, "BLOCK_ROWS", 8)
    a, b = twins(40, 16)                    # 40 rows: five blocks of 8
    for k in range(10):
        scrape(a, b, k, np.arange(37 if k > 2 else 30), np.arange(
            37.0 if k > 2 else 30.0) + k)
    both(a, b, "compact", B + 4 * IV)
    assert a._inplace and a.rehydrates == 0


def test_free_rows_and_pid_reuse(monkeypatch):
    audit = Audit(monkeypatch)
    a, b = twins()
    for k in range(6):
        x = np.arange(12.0) + k
        x[3] += 0.5 * (k == 2)              # row 3 pooled at scrape 2
        scrape(a, b, k, np.arange(12), x)
    slot = a._slot_host[3]
    assert slot >= 0
    both(a, b, "free_rows", np.array([3, 7]))
    assert a._slot_host[3] == -1 and a._vpool_free == [slot]
    assert a.n_host[3] == a.n_host[7] == 0 and a.narrow_operands()[2][3]
    # the pids come back as other series, starting later, with other values
    for k in range(6, 10):
        scrape(a, b, k, np.arange(12), np.arange(12.0) * 3 + k)
    assert a.first_ts[3] == B + 6 * IV and a._slot_host[3] == -1
    # the freed slot is taken by the next row to leave the form
    x = np.arange(12.0) * 3 + 10
    x[9] += 0.25
    scrape(a, b, 10, np.arange(12), x)
    assert a._slot_host[9] == slot and a._vpool_free == []
    assert a._inplace and a.rehydrates == 0 and a.stats.frees == 1
    assert not audit.raw_blocks((S, C))


def test_the_first_stamp_off_the_grid_and_the_cohort_gate_decline_to_raw():
    """What the form cannot hold it does not hold wrongly: a late scrape
    turns the store raw (then line), too many pooled rows turn it raw for
    the next flush's rebuild — both counted by cause."""
    a, b = twins()
    for k in range(3):
        scrape(a, b, k, np.arange(8), np.arange(8.0) + k)
    for st in (a, b):
        st.append(np.arange(8), np.full(8, B + 3 * IV + 7), np.arange(8.0))
    assert a.rehydrated["off_grid"] == 1 and not a._inplace
    assert a.stamp_form == b.stamp_form == "line"
    same(a, b)
    a, b = twins()
    scrape(a, b, 0, np.arange(8), np.arange(8.0))
    scrape(a, b, 1, np.arange(8), np.arange(8.0) + [0.5, 0.5, 0, 0, 0, 0, 0, 0])
    assert a._inplace and a.rehydrates == 0          # 2 of 8: at the gate
    scrape(a, b, 2, np.arange(8), np.arange(8.0) + 0.5)
    assert a.rehydrated["cohort_gate"] == 1 and a.val is not None
    same(a, b)


def _shard(residency="gauge", series=S, capacity=C, **kw):
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", GAUGE, 0, StoreConfig(
        max_series_per_shard=series, samples_per_series=capacity,
        flush_batch_size=10**9, dtype="float32",
        compressed_residency=residency, **kw))
    return ms, sh


HOSTS, SCRAPES, SEED = 6, 30, 2**31 + 44


def _tsbs(ms, sh, lo, hi):
    """Scrapes lo..hi-1 of HOSTS x 10 series by the plain-Python twin."""
    walks = [twin.walk(SEED, s, hi - 1) for s in range(10 * HOSTS)]
    for k in range(lo, hi):
        b = RecordBuilder(GAUGE)
        for s in range(10 * HOSTS):
            b.add(twin.labels_of(s), twin.stamp(k), float(walks[s][k]))
        sh.ingest(b.build())
        sh.flush()


def test_the_flush_span_says_the_form_and_the_fallback_counter_its_meaning():
    """Integers a byte holds: every flush appends in place, nothing is
    rebuilt, nothing falls back. Continuous values: the store declines at
    its first append, the flush's rebuild declines too and counts ONE
    fallback a flush, as it always has."""
    fell = registry.counter(FILODB_STORE_RESIDENCY_FALLBACK,
                            {"reason": "non-integer"})
    before = fell.value
    ms, sh = _shard()
    tracer.drain()
    _tsbs(ms, sh, 0, 5)
    spans = [s for s in tracer.drain() if s.name == SPAN_INGEST_FLUSH]
    assert [s.tags["form"] for s in spans] == ["narrow"] * 5
    assert all(s.tags["rehydrates"] == 0 and s.tags["pooled"] == 0
               and 1.0 < s.tags["sample_bytes"] < 1.5 for s in spans)
    assert fell.value == before and sh.store.rehydrates == 0
    ms, sh = _shard()
    rng = np.random.default_rng(5)
    tracer.drain()
    for k in range(3):
        b = RecordBuilder(GAUGE)
        for h in range(8):
            b.add({"_metric_": "m", "host": f"h{h}"}, B + k * IV,
                  float(rng.exponential(5.0)))
        sh.ingest(b.build())
        sh.flush()
    spans = [s for s in tracer.drain() if s.name == SPAN_INGEST_FLUSH]
    # born narrow, its first scrape is every row's anchor: in place; the
    # second leaves the contract in every row
    assert [s.tags["form"] for s in spans] == ["narrow", "raw", "raw"]
    assert [s.tags["rehydrates"] for s in spans] == [0, 1, 0]
    assert spans[1].tags["pooled"] == 8 and spans[2].tags["sample_bytes"] == 12
    assert fell.value == before + 2 and not sh.store.is_narrow_resident


def test_the_engine_answers_the_twins_integers_from_a_store_born_narrow():
    """TSBS's single-groupby over a store under ``compressed_residency:
    gauge``, with appends, an age-out and a pooled row between the
    queries: ``tests/tsbs_reference.py``'s integers exactly, one program a
    leaf, never a rehydrate."""
    ms, sh = _shard(series=64, capacity=48)
    _tsbs(ms, sh, 0, 20)
    eng = QueryEngine(ms, "prometheus")

    def ask(metric, hosts, head):
        alt = "|".join(f"host_{h}" for h in hosts)
        out_ts = list(range(twin.stamp(4) + 137, twin.stamp(head) + 1, 30_000))
        tracer.drain()
        r = eng.query_range(
            f'max(max_over_time({metric}{{hostname=~"{alt}"}}[1m]))',
            out_ts[0], out_ts[-1], 30_000)
        (g,) = [s for s in tracer.drain() if s.name == "query.exec.gather"]
        assert (g.tags["programs"], g.tags["decode"]) == (1, "delta8")
        want = twin.evaluate(SEED, 10 * HOSTS, metric, hosts, "max",
                             "max_over_time", 60, out_ts, head)
        np.testing.assert_array_equal(np.asarray(r.matrix.values)[0], want)

    ask("cpu_usage_user", [1, 3, 4], 19)
    _tsbs(ms, sh, 20, SCRAPES)
    ask("cpu_usage_idle", [0, 2, 5], SCRAPES - 1)
    with sh.lock:
        sh.store.compact(twin.stamp(3))         # before every query's reach
        sh.store._pool_rows(np.array([10], np.int32))   # host_1's usage_user
    ask("cpu_usage_user", [1, 3, 4], SCRAPES - 1)
    assert sh.store._inplace and sh.store.rehydrates == 0


def test_a_wide_read_past_the_decode_budget_fails_the_query_not_the_node():
    """A selection too wide to gather asks for the whole f32 view of a
    store held narrow; past the budget (half the device's free memory; set
    by hand here, the CPU has no such number) the QUERY is refused by name
    and counted, and the store is what it was."""
    ms, sh = _shard(series=64, capacity=48)
    _tsbs(ms, sh, 0, 10)
    st = sh.store
    eng = QueryEngine(ms, "prometheus")
    # no aggregate over it to fuse with, no grid kernel for it: the general
    # kernels, over the whole view
    text = "quantile_over_time(0.5, {hostname=~\"host_.*\"}[1m])"
    span = (twin.stamp(2), twin.stamp(9), 30_000)
    want = np.asarray(eng.query_range(text, *span).matrix.values)
    refused = registry.counter(FILODB_QUERY_REFUSED,
                               {"reason": "decode_bytes"})
    before = refused.value
    st.decode_budget = st.S * st.C * 4 - 1
    with pytest.raises(QueryError, match="exceeds the device's budget"):
        QueryEngine(ms, "prometheus").query_range(text, *span)
    assert refused.value == before + 1
    with pytest.raises(DecodeRefused):
        st.column_array().materialize()
    assert st._inplace and st.rehydrates == 0
    # a narrow selection of the same store never asks for the block
    r = QueryEngine(ms, "prometheus").query_range(
        'max(max_over_time(cpu_usage_user{hostname="host_2"}[1m]))', *span)
    assert r.exec_path == "local-gather"
    st.decode_budget = st.S * st.C * 8
    got = np.asarray(QueryEngine(ms, "prometheus").query_range(
        text, *span).matrix.values)
    np.testing.assert_array_equal(got, want)
