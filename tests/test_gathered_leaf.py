"""A gathered leaf as ONE program (query/exec.py ``GatheredRows`` /
``GatheredWindow``, PR 42).

A narrow selection of resident scalar rows dispatches its gather, window
function, step slice and aggregate map phase as one program under the shard
lock, with what the host knows as that call's host arguments. The bodies are
the stepwise form's, so the answers have to be that form's BIT FOR BIT: every
range function the general kernels serve and three the grid kernels serve,
under no aggregate and four, over 1, 8 and 64 rows, on a grid store, on one
off the grid and on one born in its delta8 form (PR 44: its picked rows are
decoded inside the program, and its answers are the raw store's too). What
the selection can observe keeps the stepwise form elsewhere (a quant16
block, a line store, a histogram, cold chunks, a churned cohort, rows a
fused kernel takes), and nothing lazy outlives the lock.
"""

import numpy as np
import pytest

from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE, PROM_HISTOGRAM
from filodb_tpu.ops import fusedresident, gridfns, rangefns
from filodb_tpu.query import exec as qexec
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.query.plancache import plan_cache
from filodb_tpu.utils.metrics import FILODB_QUERY_LEAF_GATHER, registry
from filodb_tpu.utils.tracing import SPAN_QUERY_GATHER, tracer

BASE, IV = 1_700_000_000_000, 10_000
HOSTS, SCRAPES, C = 160, 30, 32
START, END, STEP = BASE + 60_000 + 137, BASE + 290_000, 45_000

# how a range function is spelled over a selector; ``last_sample`` is the
# instant selector itself
TEXT = {fn: fn + "({sel}[1m])" for fn in rangefns.RANGE_FNS}
TEXT.update(last_sample="{sel}",
            quantile_over_time="quantile_over_time(0.7, {sel}[1m])",
            holt_winters="holt_winters({sel}[1m], 0.5, 0.3)",
            predict_linear="predict_linear({sel}[1m], 30)")
AGGS = {"none": "{}", "sum": "sum({})", "max": "max({})", "avg": "avg({})",
        "count-by": "count by (os) ({})"}


def selector(rows: int, kind: str = "grid") -> str:
    alt = "|".join(f"h{h}" for h in range(3, 3 + 2 * rows, 2))
    return f'm{"::sum" if kind == "off-grid" else ""}{{host=~"{alt}"}}'


def value(h: int, k: int) -> float:
    # a counter that resets now and then, never the same in two rows, and no
    # dyadic fractions: a sum folded in another order, a multiply and an add
    # contracted into one fma show in the last bit
    return float((k * (3 + h % 7) + h * 13) % 97) + 0.1 * (h % 4) + 0.013 * k


LES = np.array([1.0, 2.0, np.inf])


def value_int(h: int, k: int) -> float:
    # value's integer part: a delta of at most 96, what an int8 holds
    return float((k * (3 + h % 7) + h * 13) % 97)


def build(off_grid: bool, dtype: str = "float32", value=value, **cfg):
    """A gauge store on its grid, or (``off_grid``) a prom-histogram store
    whose scrapes come late and are missed now and then: a layout store has
    no line form, so its stamps stay a resident s64 block off any grid, and
    its ``sum`` column is a scalar one beside it."""
    schema = PROM_HISTOGRAM if off_grid else GAUGE
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", schema, 0, StoreConfig(
        max_series_per_shard=HOSTS, samples_per_series=C,
        flush_batch_size=10**9, dtype=dtype, **cfg))
    for k in range(SCRAPES):
        b = (RecordBuilder(schema, bucket_les=LES) if off_grid
             else RecordBuilder(schema))
        for h in range(HOSTS):
            if off_grid and (h + k) % 11 == 0:
                continue            # a missed scrape: no common grid
            late = (h * 7 + k * 3) % 900 if off_grid else 0
            v = value(h, k)
            b.add({"_metric_": "m", "host": f"h{h}", "os": "abc"[h % 3]},
                  BASE + k * IV + late,
                  {"sum": v, "count": float(k), "h": np.full(3, float(k))}
                  if off_grid else v)
        sh.ingest(b.build())
        sh.flush()
    return ms, sh


@pytest.fixture(scope="module")
def stores():
    """One store a kind for the whole matrix; the fused tier off, so that a
    grid function under sum / avg / count is the composed grid kernel's and
    not the fused kernel's (which takes gathered rows: its own case
    below)."""
    old = fusedresident.mode()
    fusedresident.set_mode("off")
    out = {"grid": build(False), "off-grid": build(True),
           "narrow": build(False, value=value_int, narrow_resident=True),
           "narrow-raw": build(False, value=value_int)}
    g, o = out["grid"][1].store, out["off-grid"][1].store
    nb = out["narrow"][1].store
    assert nb._inplace and nb.narrow_operands()[0] == "delta8"
    assert nb.rehydrates == 0 and nb.ts is None and nb.val is None
    assert g.grid_info() is not None and g.grid_row_gather() is not None
    assert o.grid_info() is None and o.ts is not None and o.res is None
    assert o.extra["sum"].shape == (o.S, C)
    yield out
    fusedresident.set_mode(old)


def run(ms, text, stepwise: bool, monkeypatch, start=START, end=END,
        step=STEP):
    """(result, the query's gather spans) from a fresh engine (no cache of
    another run), in the form asked for."""
    if stepwise:
        monkeypatch.setattr(qexec, "_joins_one_program",
                            lambda *a: False)
    tracer.drain()
    r = QueryEngine(ms, "prometheus").query_range(text, start, end, step)
    spans = [s for s in tracer.drain() if s.name == SPAN_QUERY_GATHER]
    monkeypatch.undo()
    return r, spans


def same_bits(a, b):
    assert [k.labels for k in a.matrix.keys] == [k.labels
                                                 for k in b.matrix.keys]
    x, y = np.asarray(a.matrix.values), np.asarray(b.matrix.values)
    assert x.dtype == y.dtype and x.shape == y.shape
    assert x.tobytes() == y.tobytes()


@pytest.mark.parametrize("kind", ["grid", "off-grid", "narrow"])
@pytest.mark.parametrize("rows", [1, 8, 64])
@pytest.mark.parametrize("agg", list(AGGS))
@pytest.mark.parametrize("fn", rangefns.RANGE_FNS)
def test_one_program_answers_the_stepwise_forms_bits(stores, monkeypatch, fn,
                                                     agg, rows, kind):
    """On the grid store the eight functions the grid kernels have run the
    composed grid kernel and the other twelve the general one; off the grid
    all twenty run the general one; the delta8 store is a grid store whose
    gather decodes, and answers as its raw twin does."""
    ms, _sh = stores[kind]
    on_grid = kind != "off-grid"
    kernel = "grid" if on_grid and fn in gridfns.GRID_FNS else "periodic"
    text = AGGS[agg].format(TEXT[fn].format(sel=selector(rows, kind)))
    one, spans = run(ms, text, False, monkeypatch)
    (gat,) = spans
    assert gat.tags["programs"] == 1 and gat.tags["rows"] == rows
    assert gat.tags["padded"] == qexec._pow2(rows)
    op = None if agg == "none" else agg.split("-")[0]
    assert any(k[:4] == ("leaf", kernel, fn, op) for k in plan_cache.keys())
    steps, sspans = run(ms, text, True, monkeypatch)
    (sgat,) = sspans
    assert sgat.tags["programs"] == qexec.STEPWISE_PROGRAMS[on_grid] > 1
    assert gat.tags["decode"] == sgat.tags["decode"] == (
        "delta8" if kind == "narrow" else "raw")
    assert one.exec_path == steps.exec_path == "local-gather"
    assert one.matrix.num_series == (min(rows, 3) if agg == "count-by" else
                                     rows if agg == "none" else 1)
    assert not np.isnan(np.asarray(one.matrix.values)).all()
    same_bits(one, steps)
    if kind == "narrow":
        raw, _ = run(stores["narrow-raw"][0], text, False, monkeypatch)
        same_bits(one, raw)


# -- what keeps the stepwise form ------------------------------------------------

FEW, K = 12, 30
PICK = (3, 5, 7, 10)
WINDOW = 60_000


FORMS = ("delta8", "delta16", "quant16")     # of a compressed-resident block


def gather_forms() -> dict:
    return {form: registry.counter(FILODB_QUERY_LEAF_GATHER,
                                   {"form": form}).value
            for form in ("one", "steps")}


def stream(kind: str):
    """(schema, stamps [FEW, K] with -1 where a scrape is missed, values
    [FEW, K] or [FEW, K, 3]): integer counters (a compressed block takes
    them, and f32 holds their sums exactly)."""
    rng = np.random.default_rng(42)
    stamps = BASE + np.arange(K)[None, :] * IV + np.zeros((FEW, 1), np.int64)
    # increments an int8 holds; for "quant16" past it and inside u16's span
    top = {"quant16": 1000, "delta16": 20000}.get(kind, 50)
    vals = np.cumsum(rng.integers(1, top, (FEW, K)), axis=1).astype(np.float64)
    if kind == "line-holes":
        h, k = np.mgrid[:FEW, :K]
        stamps = np.where((h + k) % 11 == 10, -1,
                          stamps + (h * 7 + k * 3) % 60)
    if kind == "histogram":
        inc = rng.integers(0, 9, (FEW, K, 3))
        vals = np.cumsum(np.cumsum(inc, axis=2), axis=1).astype(np.float64)
    return (PROM_HISTOGRAM if kind == "histogram" else GAUGE), stamps, vals


def feed(kind: str, tmp_path):
    schema, stamps, vals = stream(kind)
    sink = None
    if kind == "paged":
        from filodb_tpu.core.store import FileColumnStore
        sink = FileColumnStore(str(tmp_path))
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", schema, 0, StoreConfig(
        max_series_per_shard=FEW, samples_per_series=C,
        flush_batch_size=10**9, groups_per_shard=1, dtype="float32",
        narrow_resident=kind in FORMS), sink=sink)
    for k in range(K):
        b = (RecordBuilder(schema, bucket_les=LES) if kind == "histogram"
             else RecordBuilder(schema))
        for h in range(FEW):
            if stamps[h, k] < 0:
                continue
            v = vals[h, k]
            b.add({"_metric_": "m", "host": f"h{h}"}, int(stamps[h, k]),
                  {"sum": float(v[-1]), "count": float(v[-1]), "h": v}
                  if kind == "histogram" else float(v))
        sh.ingest(b.build())
        sh.flush()
    st = sh.store
    if kind in FORMS:
        assert st.is_narrow_resident and st.ts is None
        assert st.narrow_operands()[0] == kind
    elif kind == "line-holes":
        assert st.res is not None and st.ts is None and st.hole_cells > 0
    elif kind == "histogram":
        assert st.nbuckets == 3 and st.grid_info() is not None
    else:
        sh.flush_all_groups()
        st.compact(BASE + 12 * IV)          # the early samples: sink only
    return ms, sh, stamps, vals


def want_sum_of_sums(stamps, vals, out_ts):
    """sum(sum_over_time(m{PICK}[1m])) by the plain reference, a bucket at
    a time for a histogram."""
    from .prom_reference import eval_range_fn
    cols = vals.reshape(FEW, K, -1)
    out = np.zeros((len(out_ts), cols.shape[2]))
    for h in PICK:
        ok = stamps[h] >= 0
        for b in range(cols.shape[2]):
            out[:, b] += eval_range_fn("sum_over_time", stamps[h][ok],
                                       cols[h, ok, b], out_ts, WINDOW)
    return out


@pytest.mark.parametrize("kind", ["delta8", "delta16"])
def test_a_delta_block_is_decoded_inside_the_one_program(kind, tmp_path):
    """A store held as deltas on a grid — born so (delta8) or rebuilt so
    after its first rows outgrew a byte (delta16) — joins the one program:
    its gather span says which form it read, and the answer is the plain
    reference's."""
    ms, sh, stamps, vals = feed(kind, tmp_path)
    assert sh.store._inplace
    alt = "|".join(f"h{h}" for h in PICK)
    text = f'sum(sum_over_time(m{{host=~"{alt}"}}[1m]))'
    before = gather_forms()
    tracer.drain()
    r = QueryEngine(ms, "prometheus").query_range(text, BASE + 14 * IV + 137,
                                                  END, STEP)
    (g,) = [s for s in tracer.drain() if s.name == SPAN_QUERY_GATHER]
    assert g.tags["programs"] == 1 and g.tags["decode"] == kind
    assert g.tags["bytes"] == len(PICK) * C * (1 if kind == "delta8" else 2)
    assert gather_forms()["one"] == before["one"] + 1
    want = want_sum_of_sums(stamps, vals, np.asarray(r.matrix.out_ts))
    got = np.asarray(r.matrix.values, np.float64)
    np.testing.assert_allclose(got.reshape(want.shape), want, rtol=1e-6)
    # born delta8 it was never decoded; the other declined a byte once (or
    # passed through quant16 while its span was small) and was rebuilt
    assert sh.store.is_narrow_resident
    assert (sh.store.rehydrates == 0) == (kind == "delta8")


@pytest.mark.parametrize("kind", ["quant16", "line-holes", "histogram",
                                  "paged"])
def test_what_the_selection_observes_keeps_the_stepwise_form(kind, tmp_path):
    """A quant16 block decodes row by row on the host's say, a
    line store's stamps are laid together from host and device state, a
    histogram's kernels are others, cold chunks come from the sink: these
    leaves gather on their own (``programs`` > 1; the paged route gathers
    nothing) and answer as the plain reference does."""
    ms, sh, stamps, vals = feed(kind, tmp_path)
    alt = "|".join(f"h{h}" for h in PICK)
    text = f'sum(sum_over_time(m{{host=~"{alt}"}}[1m]))'
    start = BASE + (8 if kind == "paged" else 14) * IV + 137
    before = gather_forms()
    tracer.drain()
    r = QueryEngine(ms, "prometheus").query_range(text, start, END, STEP)
    spans = tracer.drain()
    gat = [s for s in spans if s.name == SPAN_QUERY_GATHER]
    (sel,) = [s for s in spans if s.name == "query.exec.select"]
    after = gather_forms()
    assert after["one"] == before["one"]
    if kind == "paged":
        assert sel.tags["route"] == "paged" and not gat
        assert after == before
    else:
        assert sel.tags["route"] == "gather"
        (g,) = gat
        assert g.tags["programs"] > 1 and g.tags["rows"] == len(PICK)
        assert g.parent_id == sel.span_id      # gathered in the select
        assert after["steps"] == before["steps"] + 1
    assert r.matrix.num_series == 1
    want = want_sum_of_sums(stamps, vals, np.asarray(r.matrix.out_ts))
    got = np.asarray(r.matrix.values, np.float64)
    np.testing.assert_allclose(got.reshape(want.shape), want, rtol=1e-6)


@pytest.mark.parametrize("aligned", [False, True],
                         ids=["cohort-rule", "aligned-cells"])
def test_a_churned_cohort_and_a_fused_kernels_rows_gather_on_their_own(
        aligned):
    """Two more that the selection can observe: rows of a minority start
    cohort are recomputed from the gathered rows (the cohort rule: a store
    without birth cells), and a fused aggregate's kernel takes gathered
    rows — both through the stepwise gather. In time-aligned cells a row
    born late is no minority: its leaf is one program."""
    ms = TimeSeriesMemStore()
    sh = ms.setup("prometheus", GAUGE, 0, StoreConfig(
        max_series_per_shard=HOSTS, samples_per_series=C,
        flush_batch_size=10**9, dtype="float32"))
    sh.store.aligned = aligned
    for k in range(SCRAPES):
        b = RecordBuilder(GAUGE)
        for h in range(HOSTS):
            if h == 5 and k < 4:
                continue                # host 5 starts late: churn
            b.add({"_metric_": "m", "host": f"h{h}", "os": "abc"[h % 3]},
                  BASE + k * IV, value(h, k))
        sh.ingest(b.build())
        sh.flush()
    eng = QueryEngine(ms, "prometheus")

    def programs_of(text):
        tracer.drain()
        r = eng.query_range(text, START, END, STEP)
        return r, [s.tags["programs"] for s in tracer.drain()
                   if s.name == SPAN_QUERY_GATHER]

    # rows 3..17, host 5 among them: one of eight off the majority's start
    r, forms = programs_of(f"max(max_over_time({selector(8)}[1m]))")
    assert forms == [1 if aligned else qexec.STEPWISE_PROGRAMS[True]]
    assert sh.store.born_late == (1 if aligned else 0)
    # without it: one cohort, one program, and the same answer where host 5
    # is not the largest
    _r, forms = programs_of('max(max_over_time(m{host=~"h3|h7|h9"}[1m]))')
    assert forms == [1]
    # a fused aggregate over a gathered selection: the kernel's operands
    # are gathered rows
    old = fusedresident.mode()
    fusedresident.set_mode("xla")
    try:
        r, forms = programs_of('sum(rate(m{host=~"h3|h7|h9"}[1m]))')
        if r.exec_path.startswith("local-fused"):
            assert forms == [qexec.STEPWISE_PROGRAMS[True]]
        else:
            assert forms == [1]
    finally:
        fusedresident.set_mode(old)


# -- nothing lazy outlives the shard lock ----------------------------------------

CHAINS = {
    "selector": "{sel}",
    "window": "max_over_time({sel}[1m])",
    "aggregate": "sum by (os) (max_over_time({sel}[1m]))",
    "instant-fn": "abs(deriv({sel}[1m]))",
    "order-stat": "topk(2, max_over_time({sel}[1m]))",
    "quantile": "quantile(0.5, avg_over_time({sel}[1m]))",
    "join": "max_over_time({sel}[1m]) / min_over_time({sel}[1m])",
    "scalar-op": "2 * sum(rate({sel}[1m]))",
}


@pytest.fixture
def fused_off():
    """No fused tier: a grid function under an aggregate is the composed
    grid kernel's (a fused kernel takes gathered rows: its own case
    above)."""
    old = fusedresident.mode()
    fusedresident.set_mode("off")
    yield
    fusedresident.set_mode(old)


@pytest.mark.parametrize("chain", list(CHAINS))
def test_a_lazy_gathered_view_never_outlives_the_shard_lock(chain, fused_off,
                                                            monkeypatch):
    """Whatever follows the selection, the leaf's one program is dispatched
    while its thread holds the shard lock, the leaf's ``execute`` hands back
    nothing lazy, and a flush right after it — which donates the store's
    buffers — changes no answer."""
    ms, sh = build(False)
    text = CHAINS[chain].format(sel=selector(8))
    want = QueryEngine(ms, "prometheus").query_range(text, START, END, STEP)

    held, leaves = [], []
    dispatch = qexec.GatheredWindow._dispatch
    leaf = qexec.SelectRawPartitionsExec.execute

    def dispatch_seen(self, *a, **kw):
        held.append(sh.lock._is_owned())
        return dispatch(self, *a, **kw)

    def leaf_then_flush(self, ctx):
        out = leaf(self, ctx)
        assert not sh.lock._is_owned()
        assert not isinstance(out, (qexec.GatheredRows, qexec.GatheredWindow,
                                    qexec.FusedWindowData))
        leaves.append(len(held))
        # the next scrape lands and is flushed before the answer is read
        b = RecordBuilder(GAUGE)
        k = SCRAPES + len(leaves) - 1
        for h in range(HOSTS):
            b.add({"_metric_": "m", "host": f"h{h}", "os": "abc"[h % 3]},
                  BASE + k * IV, value(h, k))
        sh.ingest(b.build())
        sh.flush()
        return out

    monkeypatch.setattr(qexec.GatheredWindow, "_dispatch", dispatch_seen)
    monkeypatch.setattr(qexec.SelectRawPartitionsExec, "execute",
                        leaf_then_flush)
    tracer.drain()
    got = QueryEngine(ms, "prometheus").query_range(text, START, END, STEP)
    forms = [s.tags["programs"] for s in tracer.drain()
             if s.name == SPAN_QUERY_GATHER]
    n_leaves = 2 if chain == "join" else 1
    assert held == [True] * n_leaves and forms == [1] * n_leaves
    assert leaves == list(range(1, n_leaves + 1))   # dispatched, then back
    same_bits(got, want)
