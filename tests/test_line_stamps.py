"""Prometheus-shaped stamps: the store's line form and the fused kernel
that reads it, against the golden model (``tests/prom_reference.py``).

A scraper stamps series ``s``'s scrape ``k`` at ``BASE + k * IV + phase(s) +
late(s, k)``: every target on its own phase inside the interval, a late
scrape stamped as it came. The store keeps that as a line a row plus an
int8 residual a cell (core/chunkstore.py, the text at ``RES_DTYPE``), and
the fused scalar tier decides each window's edge cells row by row from the
true stamps (ops/fusedgrid.py ``_line_contrib``). Here: the kernel on both
backends against the reference for every fused function and aggregate, with
window edges ON a stamp, one millisecond before and one after; a store that
turns from grid to line in mid-stream; the three demotion reasons; the raw
selector; a control that drops the residuals and must miss the tolerance;
and the grid form left byte for byte as it was.
"""

import functools
import json
import urllib.parse
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from filodb_tpu.core import chunkstore
from filodb_tpu.core.chunkstore import HOLE_RUN_MAX, RES_MAX, SeriesStore
from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE
from filodb_tpu.ops import fusedgrid, fusedresident, gridfns
from filodb_tpu.query import exec as qexec
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.utils.tracing import (SPAN_INGEST_FLUSH, SPAN_QUERY_KERNEL,
                                      SPAN_QUERY_SELECT, tracer)

from .prom_reference import eval_range_fn
from .test_fused_resident import fused_mode

BASE, IV = 1_700_000_000_000, 10_000
S, C, K, G = 64, 128, 100, 4
WINDOW = 300_000
RTOL, ATOL = 2e-4, 1e-4          # the deployments' stated exactness
FNS = ("rate", "increase", "delta", "avg_over_time", "sum_over_time",
       "count_over_time")
AGGS = ("sum", "avg", "stddev", "count")
BACKENDS = ("pallas", "xla")     # "pallas" runs interpreted on the CPU


def stream(seed=0, rows=S, scrapes=K, reset_row=5):
    """(stamps [rows, scrapes] i64, values [rows, scrapes] f64): phases
    over the whole interval (0 and IV - 1 among them), one scrape in four
    3..63 ms late, rows 2 and 3 at the residual width's two ends, integer
    counters (exact in f32) with one reset."""
    rng = np.random.default_rng(seed)
    phase = rng.integers(0, IV, rows)
    phase[0], phase[1] = 0, IV - 1
    late = np.where(rng.random((rows, scrapes)) < 0.25,
                    rng.integers(3, 64, (rows, scrapes)), 0)
    late[:, 0] = 0
    late[1, 0] = 63              # a first scrape late: residuals below 0
    late[2] = 0                  # ... and to both ends of the width: the
    late[2, 0] = RES_MAX         # line starts RES_MAX late, the scrapes on
    late[2, 7] = 2 * RES_MAX     # schedule lie that much under it
    t = BASE + phase[:, None] + np.arange(scrapes)[None, :] * IV + late
    v = np.cumsum(rng.integers(0, 100, (rows, scrapes)), axis=1) \
        + rng.integers(0, 1000, rows)[:, None]
    if reset_row is not None:
        v[reset_row, scrapes // 2:] -= v[reset_row, scrapes // 2] - 3
    return t.astype(np.int64), v.astype(np.float64)


def fed(t, v, rows=None, cap=C) -> SeriesStore:
    st = SeriesStore(len(t) if rows is None else rows, cap)
    for k in range(t.shape[1]):
        st.append(np.arange(len(t)), t[:, k], v[:, k])
    return st


def steps_on_the_edges(t, step, start):
    """61 steps of ``step`` ms from ``start`` (no multiple of the interval
    where ``step`` is none), six of them moved so that a window's END falls
    on a stamp, 1 ms before it and 1 ms after, and likewise its START: the
    cases in which membership flips."""
    out = np.arange(start, BASE + (K - 1) * IV, step)[:61]
    on, below = t[7, 60], t[9, 45] + WINDOW
    out[3:9] = on, on - 1, on + 1, below, below - 1, below + 1
    return np.sort(out)


GRIDS = {"15s": (15_000, BASE + 400_007), "60s": (60_000, BASE + 310_000),
         "7001ms": (7_001, BASE + 500_123)}


def reference(t, v, fn, out_ts, rows=None):
    rows = range(len(t)) if rows is None else rows
    return np.array([eval_range_fn(fn, t[s], v[s], out_ts, WINDOW)
                     for s in rows])


def aggregate(agg, x, gids, ngroups):
    """[ngroups, T] of the per-series matrix ``x`` (NaN = absent), f64."""
    out = np.full((ngroups, x.shape[1]), np.nan)
    for g in range(ngroups):
        rows = x[gids == g]
        ok = np.isfinite(rows)
        n = ok.sum(0)
        s = np.where(ok, rows, 0).sum(0)
        with np.errstate(invalid="ignore", divide="ignore"):
            mean = s / n
            var = (np.where(ok, (rows - mean) ** 2, 0)).sum(0) / n
        res = {"sum": s, "avg": mean, "count": n.astype(float),
               "stddev": np.sqrt(var)}[agg]
        out[g] = np.where(n > 0, res, np.nan)
    return out


def present(agg, parts):
    s, n = (np.asarray(parts[k], np.float64) for k in ("sum", "count"))
    with np.errstate(invalid="ignore", divide="ignore"):
        mean = s / n
        res = {"sum": s, "avg": mean, "count": n,
               "stddev": np.sqrt(np.maximum(
                   np.asarray(parts["sumsq"], np.float64) / n - mean * mean,
                   0))}[agg]
    return np.where(n > 0, res, np.nan)


def err(got, want):
    assert (np.isfinite(got) == np.isfinite(want)).all()
    m = np.isfinite(want)
    return float(np.max(np.abs(got[m] - want[m])
                        / (ATOL + RTOL * np.abs(want[m])), initial=0.0))


@functools.lru_cache(maxsize=None)
def the_store():
    t, v = stream()
    st = fed(t, v)
    assert st.stamp_form == "line" and not any(st.demoted.values())
    return t, v, st


@functools.lru_cache(maxsize=None)
def kernel_parts(backend, fn, grouped, grid, drop_residuals=False):
    """One kernel run gives all four aggregates' partial state."""
    t, v, st = the_store()
    info = st.line_info()
    assert len(info.minority) == 0
    step, start = GRIDS[grid]
    out_ts = steps_on_the_edges(t, step, start)
    gids = (np.arange(S) % G if grouped else np.zeros(S)).astype(np.int32)
    res = jnp.zeros_like(info.res) if drop_residuals else info.res
    parts = fusedgrid.fused_grid_aggregate(
        "stddev", fn, st.val, st.n, jnp.asarray(gids), G if grouped else 1,
        out_ts, WINDOW, info.base_ts, info.interval_ms, variant=backend,
        line=(info.start, res))
    return out_ts, gids, parts


@functools.lru_cache(maxsize=None)
def want_matrix(fn, grid):
    t, v, _ = the_store()
    step, start = GRIDS[grid]
    return reference(t, v, fn, steps_on_the_edges(t, step, start))


# -- B: kernel against the reference, both backends ---------------------------

@pytest.mark.parametrize("grouped", (False, True), ids=("global", "by"))
@pytest.mark.parametrize("agg", AGGS)
@pytest.mark.parametrize("fn", FNS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_line_kernel_matches_the_reference(backend, fn, agg, grouped):
    worst = 0.0
    for grid in GRIDS:
        _, gids, parts = kernel_parts(backend, fn, grouped, grid)
        got = present(agg, parts)
        want = aggregate(agg, want_matrix(fn, grid), gids,
                         G if grouped else 1)
        worst = max(worst, err(got, want))
    assert worst < 1.0, worst


# rows that END under a window's low edge: the stream's rows stop at
# differing scrapes, those on the latest phases among them (only a row that
# starts in the top quarter second of the interval can hold cell lo - 2 in
# a window), and the steps put a window's low edge ON each such row's last
# stamp, 1 ms before it and 1 ms after
ENDED_ROWS = (1, 10, 11, 12, 13, 30)


def ended_stream():
    t, v = stream(seed=11, reset_row=None)
    phase = {10: IV - 2, 11: IV - 60, 12: IV - 126, 13: IV - 250}
    for r, p in phase.items():
        t[r] += p - (t[r, 0] - BASE)
    n = K - 3 - (np.arange(S) * 7) % 41
    n[list(ENDED_ROWS)] = (60, 52, 71, 66, 58, 64)
    return t, v, n


@functools.lru_cache(maxsize=None)
def the_ended_store():
    t, v, n = ended_stream()
    st = SeriesStore(S, C)
    for k in range(K):
        rows = np.flatnonzero(n > k)
        st.append(rows, t[rows, k], v[rows, k])
    assert st.stamp_form == "line" and not any(st.demoted.values())
    assert st.n_host[:S].tolist() == n.tolist()
    assert len(st.line_info().minority) == 0
    lasts = np.array([t[r, n[r] - 1] for r in ENDED_ROWS])
    out_ts = np.sort(np.concatenate(
        [lasts + WINDOW + d for d in (-1, 0, 1)]
        + [np.arange(BASE + 400_007, BASE + (K - 1) * IV, 17_003)]))
    return t, v, n, st, out_ts


@pytest.mark.parametrize("fn", FNS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_a_row_that_ended_keeps_its_last_sample_in_the_window(backend, fn):
    t, v, n, st, out_ts = the_ended_store()
    info = st.line_info()
    gids = (np.arange(S) % G).astype(np.int32)
    parts = fusedgrid.fused_grid_aggregate(
        "stddev", fn, st.val, st.n, jnp.asarray(gids), G, out_ts, WINDOW,
        info.base_ts, info.interval_ms, variant=backend,
        line=(info.start, info.res))
    want = np.array([eval_range_fn(fn, t[s, :n[s]], v[s, :n[s]], out_ts,
                                   WINDOW) for s in range(S)])
    for agg in AGGS:
        assert err(present(agg, parts), aggregate(agg, want, gids, G)) < 1.0


@pytest.mark.parametrize("holes", [False, True])
@pytest.mark.parametrize("fn", ["rate", "delta", "avg_over_time",
                                "count_over_time"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_what_lies_past_a_rows_count_moves_no_bit(backend, fn, holes):
    """The picks are int8 products of the residual block itself: whatever
    int8 a cell at or past a row's count holds — a hole's mark among them
    — is masked before the product and every reader asks the count first,
    in both modes of the line program, and the partial state is the clean
    block's to the bit. The values' cells past the count are masked as
    they were: garbage there too."""
    _t, _v, n, st, out_ts = the_ended_store()
    info = st.line_info()
    gids = jnp.asarray((np.arange(S) % G).astype(np.int32))
    rng = np.random.default_rng(5)
    past = np.arange(C)[None, :] >= np.asarray(st.n)[:S, None]
    assert past[list(ENDED_ROWS)].any(1).all()
    res = np.where(past, rng.integers(-128, 128, (S, C)),
                   np.asarray(info.res)).astype(np.int8)
    val = np.where(past, rng.normal(0, 1e6, (S, C)),
                   np.asarray(st.val)).astype(np.float32)
    assert (res[past] == chunkstore.RES_HOLE).any()

    def run(v, r):
        parts = fusedgrid.fused_grid_aggregate(
            "stddev", fn, jnp.asarray(v), st.n, gids, G, out_ts[:61], WINDOW,
            info.base_ts, info.interval_ms, variant=backend,
            line=(info.start, jnp.asarray(r)), holes=holes)
        return {k: np.asarray(a).tobytes() for k, a in parts.items()}
    assert run(val, res) == run(st.val, info.res)


# -- C: two edge slots a 128-lane block (up to 64 steps) against one ----------

STEP_COUNTS = (1, 61, 64, 65, 128)       # packed, packed, packed; one a block


@functools.lru_cache(maxsize=None)
def ended_steps(T):
    """The first ``T`` of 128 steps over the ended store, the same for every
    ``T``: first the eighteen that put a window's low edge on an ended row's
    last stamp, 1 ms before and 1 ms after (rows that end at lo - 2, at
    lo - 1, inside the window and before it, by their phases), then steps
    of a stride that is no multiple of the interval."""
    t, _v, n, _st, _ = the_ended_store()
    lasts = np.array([t[r, n[r] - 1] for r in ENDED_ROWS])
    edge = (lasts[:, None] + WINDOW + np.array([-1, 0, 1])[None, :]).ravel()
    steps = np.concatenate([edge, BASE + 400_007 + 4_673 * np.arange(110)])
    assert len(np.unique(steps)) == 128
    return np.sort(steps[:T])


@functools.lru_cache(maxsize=None)
def ended_parts(backend, fn, T, grouped):
    _t, _v, _n, st, _ = the_ended_store()
    info = st.line_info()
    gids = (np.arange(S) % G if grouped else np.zeros(S)).astype(np.int32)
    parts = fusedgrid.fused_grid_aggregate(
        "stddev", fn, st.val, st.n, jnp.asarray(gids), G if grouped else 1,
        ended_steps(T), WINDOW, info.base_ts, info.interval_ms,
        variant=backend, line=(info.start, info.res))
    return gids, {k: np.asarray(a) for k, a in parts.items()}


@functools.lru_cache(maxsize=None)
def ended_want(fn):
    t, v, n, _st, _ = the_ended_store()
    return ended_steps(128), np.array([eval_range_fn(
        fn, t[s, :n[s]], v[s, :n[s]], ended_steps(128), WINDOW)
        for s in range(S)])


@pytest.mark.parametrize("T", STEP_COUNTS)
@pytest.mark.parametrize("fn", FNS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_the_line_kernel_at_every_slot_layout_matches_the_reference(
        backend, fn, T):
    steps, want = ended_want(fn)
    want = want[:, np.searchsorted(steps, ended_steps(T))]
    for grouped in (False, True):
        gids, parts = ended_parts(backend, fn, T, grouped)
        assert parts["sum"].shape == (G if grouped else 1, T)
        for agg in AGGS:
            assert err(present(agg, parts),
                       aggregate(agg, want, gids, G if grouped else 1)) < 1.0


@pytest.mark.parametrize("fn", FNS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_two_slots_a_block_answer_what_one_slot_a_block_answers(backend, fn):
    """The same steps through both layouts (61 and 64 steps packed, the
    first of 65 and of 128 not): every product is exact either way and the
    algebra after it is the same, so the partial state is equal to the
    bit."""
    for grouped in (False, True):
        for packed, plain in ((61, 65), (64, 128), (1, 128)):
            assert fusedgrid.slots_per_block(packed) == 2
            assert fusedgrid.slots_per_block(plain) == 1
            at = np.searchsorted(ended_steps(plain), ended_steps(packed))
            _, a = ended_parts(backend, fn, packed, grouped)
            _, b = ended_parts(backend, fn, plain, grouped)
            for k in ("sum", "count", "sumsq"):
                np.testing.assert_array_equal(a[k], b[k][:, at])


def test_the_slot_layout_is_part_of_a_line_programs_key_and_tag():
    """Tp is 128 for 61 steps and for 100: the plan key and the dispatch
    span say which layout a line program has; a grid program's key has
    neither word and its span no ``packed``."""
    from filodb_tpu.query.plancache import plan_cache
    _t, _v, _n, st, _ = the_ended_store()
    info = st.line_info()
    plan_cache.clear()
    tracer.drain()
    for T, line in ((61, True), (100, True), (61, False)):
        fusedgrid.fused_grid_aggregate(
            "sum", "rate", st.val, st.n, jnp.zeros(S, jnp.int32), 1,
            ended_steps(128)[:T], WINDOW, info.base_ts, info.interval_ms,
            variant="xla", line=(info.start, info.res) if line else None)
    keys = [k for k in plan_cache._entries if k[0] == "fused-grid"]
    assert [k[14:] for k in keys] == [("line", 2), ("line",), ()]
    assert len({k[:14] for k in keys}) == 1
    spans = [s.tags for s in tracer.drain() if s.name == SPAN_QUERY_KERNEL
             and s.tags.get("phase") == "dispatch"]
    assert [(s["stamps"], s.get("packed")) for s in spans] == [
        ("line", 2), ("line", 1), ("grid", None)]
    # the operands: six slots in three blocks, or in six
    for T, width in ((61, 3 * 128), (64, 3 * 128), (65, 6 * 128)):
        for kind in ("rate", "window"):
            band, ohe, lo, hi, rel, eb, c0, ca = fusedgrid.host_operands(
                C, 128, ended_steps(128)[:T], WINDOW, info.base_ts, IV, kind,
                line=True)
            assert band.shape == (ca, 128) and ohe.shape == (ca, width)
            # picks only, int8: every column one-hot or zeros, whatever
            # the function; a band adds cells and is the bf16 ``band``
            assert band.dtype == jnp.bfloat16 and ohe.dtype == np.int8
            assert eb.shape == (8, 128) and lo.shape == (1, 128)
            ones = ohe.astype(np.int64).sum(0)
            assert (ones <= 1).all() and ones.sum() > 4 * T
            assert set(np.unique(ohe)) <= {0, 1}


@pytest.mark.parametrize("T", [61, 65])
@pytest.mark.parametrize("kind", ["rate", "window"])
def test_a_line_plan_holds_its_picks_as_int8_and_its_band_as_the_band(
        kind, T):
    """``host_operands(..., line=True)`` since the picks run as int8
    products (fusedgrid.pick_exact): ``ohe`` is int8 and holds the six
    one-hot slots and nothing else, whatever the function — entry for entry
    what the bf16 operand held beside the packed window form's band — and
    that band is where every line program is passed one, in ``band``: the
    closed band for the window functions, the open one for the rate
    family."""
    out_ts = ended_steps(128)[:T]
    band, ohe, lo_p, hi_p, _rel, _eb, c0, ca = fusedgrid.host_operands(
        C, 128, out_ts, WINDOW, BASE, IV, kind, line=True)
    assert ohe.dtype == np.int8 and band.dtype == jnp.bfloat16
    lo, hi = gridfns.grid_edges(out_ts, WINDOW, BASE, IV,
                                fusedgrid.line_spread(IV))
    np.testing.assert_array_equal(lo_p[0, :T], lo)
    np.testing.assert_array_equal(hi_p[0, :T], hi)
    slot = 128 // fusedgrid.slots_per_block(T)
    want = np.zeros((C, fusedgrid.EDGE_SLOTS * slot), np.int8)
    cells = (lo - 2, lo - 1, hi + 1, hi + 2, np.maximum(lo, 0), hi)
    for j, cell in enumerate(cells):
        for t in range(T):
            if 0 <= cell[t] < C:
                want[cell[t], j * slot + t] = 1
    assert ohe.shape == (ca, want.shape[1])
    assert ohe.tobytes() == want[c0:c0 + ca].tobytes()
    closed = np.zeros((C, 128), np.float32)
    closed[:, :T] = gridfns.band_matrix(C, lo, hi, kind == "rate", np.float32)
    assert band.astype(np.float32).tobytes() == closed[c0:c0 + ca].tobytes()


# -- D: the telescoped delta, and the tiles that fall back to the band ---------
#
# A rate tile takes its sure range's delta as v[hi] - v[max(lo, 0)] and runs
# the band product over the increments only where tile_fell says it must: a
# counter fell among the cells some window sums, or a row ends under a
# window. The line's arrays are built by hand here (three tiles of 512 rows;
# a start a row, residuals in the int8 width): the band form — every tile
# falls, what every tile computed before — is the same program with the test
# answering True, and the reference says whether either is right.

RATE_FNS = ("rate", "increase", "delta")
TS, TC, TK = 1536, 128, 100             # three tiles of 512 rows
TILE_STEPS = {"packed": 61, "unpacked": 100}
RESET_ROW = 700                         # in tile 1, of 0..2


@functools.lru_cache(maxsize=None)
def tile_stream(kind, rows=TS):
    """(start [rows] i32, res [rows, TC] i8, val [rows, TC] f32, n [rows]):
    ``clean`` integer counters that only grow, every row full; ``reset``:
    row RESET_ROW falls once; ``ends``: row 100 stops at scrape 60;
    ``walk``: integers that go up and down (no counter: ``delta``'s diet);
    ``fraction``: counters that grow by fractions no f32 sum holds
    exactly."""
    rng = np.random.default_rng(len(kind) + rows)
    start = rng.integers(0, IV, rows).astype(np.int32)
    start[0] = 0
    res = np.zeros((rows, TC), np.int8)
    res[:, :TK] = np.where(rng.random((rows, TK)) < 0.25,
                           rng.integers(-60, 61, (rows, TK)), 0)
    res[:, 0] = 0
    inc = rng.integers(0, 100, (rows, TK)).astype(np.float64)
    if kind == "walk":
        inc -= 50
    if kind == "fraction":
        inc = inc * 1.0009765625 + rng.random((rows, TK))
    v = np.zeros((rows, TC))
    v[:, :TK] = np.cumsum(inc, axis=1) + rng.integers(0, 1000, rows)[:, None]
    n = np.full(rows, TK, np.int32)
    if kind == "reset":
        v[RESET_ROW, TK // 2:TK] -= v[RESET_ROW, TK // 2] - 3
    if kind == "ends":
        n[100] = 60
    return start, res, v.astype(np.float32), n


def tile_stamps(kind, rows=TS):
    start, res, _v, _n = tile_stream(kind, rows)
    return (BASE + start[:, None].astype(np.int64)
            + np.arange(TK)[None, :] * IV + res[:, :TK])


def tile_steps(T):
    return BASE + 400_007 + (TK * IV - 420_000) // T * np.arange(T)


def run_line(backend, fn, kind, out_ts, grouped, rows=TS, v=None):
    """(partial state, the fetch's fall tags) of one dispatch."""
    start, res, val, n = tile_stream(kind, rows)
    gids = (np.arange(rows) % G if grouped else np.zeros(rows)).astype(
        np.int32)
    p = fusedgrid.fused_grid_aggregate(
        "stddev", fn, jnp.asarray(val if v is None else v), jnp.asarray(n),
        jnp.asarray(gids), G if grouped else 1, np.asarray(out_ts, np.int64),
        WINDOW, BASE, IV, fetch=False, variant=backend,
        line=(jnp.asarray(start), jnp.asarray(res)))
    parts = {k: np.asarray(a) for k, a in p.resolve().items()}
    return parts, dict(p.fall_tags)


class band_form:
    """Every rate tile takes the band product, as every tile did before the
    delta was telescoped: the test answers True while this is open (and no
    program built under it outlives it)."""

    @staticmethod
    def _forget():
        from filodb_tpu.query.plancache import plan_cache
        fusedgrid.build_pallas.cache_clear()
        plan_cache.clear()

    def __enter__(self):
        self._real = fusedgrid.tile_fell
        fusedgrid.tile_fell = lambda *a: jnp.bool_(True)
        self._forget()

    def __exit__(self, *exc):
        fusedgrid.tile_fell = self._real
        self._forget()


@functools.lru_cache(maxsize=None)
def both_forms(backend, fn, kind, layout, grouped):
    """(telescoped parts, its fall tags, the band form's parts)."""
    out_ts = tile_steps(TILE_STEPS[layout])
    got, falls = run_line(backend, fn, kind, out_ts, grouped)
    with band_form():
        want, all_fell = run_line(backend, fn, kind, out_ts, grouped)
    assert all_fell == {"fall_tiles": TS // 512, "tiles": TS // 512}
    return got, falls, want


def same_bits(a, b):
    for k in ("sum", "count", "sumsq"):
        np.testing.assert_array_equal(a[k], b[k])


# the tiles that run the band form, by stream and function (counters,
# delta): the tile that fell and the one after it, which runs the band form
# at once because its neighbour fell (fusedgrid.fallen_fold) and, clean
# itself, hands the next tile back to the telescoped form
WANT_FALLS = {"clean": (0, 0), "reset": (2, 0), "ends": (2, 2),
              "walk": (TS // 512, 0)}


@pytest.mark.parametrize("grouped", (False, True), ids=("global", "by"))
@pytest.mark.parametrize("layout", TILE_STEPS)
@pytest.mark.parametrize("fn", RATE_FNS)
@pytest.mark.parametrize("kind", WANT_FALLS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_the_telescoped_delta_answers_the_band_form_to_the_bit(
        backend, kind, fn, layout, grouped):
    """On integer counters both are exact: a stream without a fall reports
    no fallen tile; one reset among clean tiles costs its own tile and the
    next the band form, the third none; a row that ends under a window
    falls for ``delta`` too; values that go down make every counter tile
    fall and no ``delta`` tile."""
    got, falls, want = both_forms(backend, fn, kind, layout, grouped)
    same_bits(got, want)
    assert falls == {"tiles": TS // 512,
                     "fall_tiles": WANT_FALLS[kind][fn == "delta"]}


@pytest.mark.parametrize("fn", RATE_FNS)
@pytest.mark.parametrize("kind", ("clean", "reset"))
def test_the_telescoped_backends_agree_to_the_bit(kind, fn):
    for layout in TILE_STEPS:
        a = both_forms("pallas", fn, kind, layout, True)[0]
        b = both_forms("xla", fn, kind, layout, True)[0]
        same_bits(a, b)


# one target step, asked alone (two slots a block) or 65 times over (one a
# block: the cells some window sums are then exactly its (lo, hi]), with a
# reset of row 5 at a cell named from the step's sure range [lo, hi]. An
# increment lies in its LATER sample's cell: the cells lo - 1, lo (the pair
# lo - 1 -> lo is the edge's, decided by picks) and hi + 1, hi + 2 need no
# fall; lo + 1, the interior and hi do
PLACED = {"lo-1": (-1, 0, 0), "lo": (0, 0, 0), "lo+1": (1, 0, 1),
          "interior": (12, 0, 1), "hi": (0, 1, 1), "hi+1": (1, 1, 0),
          "hi+2": (2, 1, 0)}
TARGET = BASE + 600_000 + 4_321
ROWS_PLACED = 64


def placed_values(place):
    off, from_hi, _ = PLACED[place]
    lo, hi = gridfns.grid_edges(np.array([TARGET]), WINDOW, BASE, IV,
                                fusedgrid.line_spread(IV))
    c = int(hi[0] if from_hi else lo[0]) + off
    v = tile_stream("clean", ROWS_PLACED)[2].copy()
    v[5, c:TK] -= v[5, c] - 3
    assert v[5, c] < v[5, c - 1]
    return v


@pytest.mark.parametrize("T", (1, 65), ids=("packed", "unpacked"))
@pytest.mark.parametrize("fn", RATE_FNS)
@pytest.mark.parametrize("place", PLACED)
@pytest.mark.parametrize("backend", BACKENDS)
def test_a_reset_falls_where_a_window_sums_it_and_nowhere_else(
        backend, place, fn, T):
    v = placed_values(place)
    out_ts = np.full(T, TARGET)
    t = tile_stamps("clean", ROWS_PLACED)
    want = np.array([eval_range_fn(fn, t[s], v[s, :TK].astype(np.float64),
                                   out_ts, WINDOW)
                     for s in range(ROWS_PLACED)])
    for grouped in (False, True):
        parts, falls = run_line(backend, fn, "clean", out_ts, grouped,
                                ROWS_PLACED, v)
        gids = np.arange(ROWS_PLACED) % G if grouped else np.zeros(
            ROWS_PLACED, int)
        for agg in AGGS:
            assert err(present(agg, parts),
                       aggregate(agg, want, gids, G if grouped else 1)) < 1.0
        assert falls == {"tiles": 1, "fall_tiles":
                         PLACED[place][2] if fn != "delta" else 0}


@pytest.mark.parametrize("fn", RATE_FNS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_fractions_telescope_inside_the_tolerance(backend, fn):
    """Values no f32 sum holds exactly: the difference of two samples and
    the sum of the increments between them are each within rounding of
    the reference, which is Prometheus's ``last - first``."""
    rows = ROWS_PLACED
    t = tile_stamps("fraction", rows)
    v = tile_stream("fraction", rows)[2]
    for layout, T in TILE_STEPS.items():
        out_ts = tile_steps(T)
        want = np.array([eval_range_fn(fn, t[s], v[s, :TK].astype(np.float64),
                                       out_ts, WINDOW) for s in range(rows)])
        for grouped in (False, True):
            parts, falls = run_line(backend, fn, "fraction", out_ts, grouped,
                                    rows)
            gids = np.arange(rows) % G if grouped else np.zeros(rows, int)
            for agg in AGGS:
                assert err(present(agg, parts), aggregate(
                    agg, want, gids, G if grouped else 1)) < 1.0
            assert falls == {"tiles": 1, "fall_tiles": 0}


@pytest.mark.parametrize("fn", RATE_FNS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_an_empty_sure_range_and_the_padded_steps_add_nothing(backend, fn):
    """Steps at the stream's very start: no cell is sure (hi < lo: what the
    one-hots of lo and hi pick there is garbage) or one is (hi = lo: the
    range holds no increment), the window's samples are edge cells by each
    row's own stamps. And the lanes past the steps are no step at all."""
    rows = ROWS_PLACED
    out_ts = BASE + np.array([5_000, 9_999, 15_000, 20_050, 31_000, 305_000])
    lo, hi = gridfns.grid_edges(out_ts, WINDOW, BASE, IV,
                                fusedgrid.line_spread(IV))
    assert (hi < np.maximum(lo, 0)).any() and (hi == np.maximum(lo, 0)).any()
    t = tile_stamps("clean", rows)
    v = tile_stream("clean", rows)[2]
    want = np.array([eval_range_fn(fn, t[s], v[s, :TK].astype(np.float64),
                                   out_ts, WINDOW) for s in range(rows)])
    parts, falls = run_line(backend, fn, "clean", out_ts, True, rows)
    gids = np.arange(rows) % G
    for agg in AGGS:
        assert err(present(agg, parts), aggregate(agg, want, gids, G)) < 1.0
    assert falls == {"tiles": 1, "fall_tiles": 0}
    assert parts["sum"].shape == (G, len(out_ts))


@pytest.mark.parametrize("backend", BACKENDS)
def test_the_suites_own_streams_report_their_falls(backend):
    """The one-reset stream of section B falls (one tile holds it all); so
    do the rows of section C that end under a window; the window functions
    and a grid program return no count."""
    tracer.drain()
    t, v, st = the_store()
    info = st.line_info()
    out_ts = steps_on_the_edges(t, *GRIDS["15s"])
    for fn, store, line in (("rate", st, True), ("avg_over_time", st, True),
                            ("rate", the_ended_store()[3], True),
                            ("rate", st, False)):
        i = store.line_info()
        fusedgrid.fused_grid_aggregate(
            "sum", fn, store.val, store.n, jnp.zeros(S, jnp.int32), 1, out_ts,
            WINDOW, i.base_ts, i.interval_ms, variant=backend,
            line=(i.start, i.res) if line else None)
    fetches = [s.tags for s in tracer.drain() if s.name == SPAN_QUERY_KERNEL
               and s.tags.get("phase") == "fetch"]
    assert fetches == [{"phase": "fetch", "tiles": 1, "fall_tiles": 1},
                       {"phase": "fetch"},
                       {"phase": "fetch", "tiles": 1, "fall_tiles": 1},
                       {"phase": "fetch"}]


def test_the_ended_rows_meet_the_case_they_are_there_for():
    """Of the chosen steps some hold, of some row, exactly its LAST sample
    in cell lo - 2 (the row has no cell lo - 1): the case in which a2 has
    to be decided without a1."""
    t, v, n, st, out_ts = the_ended_store()
    info = st.line_info()
    lo, _hi = gridfns.grid_edges(out_ts, WINDOW, info.base_ts, IV,
                                 fusedgrid.line_spread(IV))
    hits = 0
    for r in ENDED_ROWS:
        last = t[r, n[r] - 1]
        alone = (lo - 2 == n[r] - 1) & (last >= out_ts - WINDOW)
        hits += int(alone.sum())
        c = eval_range_fn("count_over_time", t[r, :n[r]], v[r, :n[r]],
                          out_ts[alone], WINDOW)
        assert (c == 1).all()
    assert hits >= 4


def test_the_stream_reaches_both_ends_of_the_width_and_every_phase():
    t, v, st = the_store()
    res = np.asarray(st.res)[:, :K]
    assert res.min() == -RES_MAX and res.max() == RES_MAX
    info = st.line_info()
    start = np.asarray(info.start)
    assert start.min() == 0 and start.max() >= IV - 1
    np.testing.assert_array_equal(np.asarray(st.ts_block())[:, :K], t)
    # membership really flips on the chosen edges: the step ON a stamp
    # holds one sample more than the step 1 ms before it
    on = t[7, 60]
    c = reference(t, v, "count_over_time", np.array([on - 1, on, on + 1]),
                  rows=[7])[0]
    assert c[1] == c[0] + 1 == c[2]
    lo = t[9, 45] + WINDOW
    c = reference(t, v, "count_over_time", np.array([lo - 1, lo, lo + 1]),
                  rows=[9])[0]
    # (row 9's scrape 75 may lie WINDOW after its scrape 45 to the
    # millisecond: then the step on the lower edge is on the upper one too)
    assert c[1] == c[2] + 1 and c[1] - c[0] in (0, 1)


@pytest.mark.parametrize("fn", ("rate", "avg_over_time", "count_over_time"))
def test_dropping_the_residual_misses_the_tolerance(fn):
    """The control: the kernel fed the LINE's stamps instead of the true
    ones. Leaving the residual out is a failure, not a speed-up."""
    grid = "7001ms"
    _, gids, parts = kernel_parts("xla", fn, False, grid, drop_residuals=True)
    want = aggregate("sum", want_matrix(fn, grid), gids, 1)
    got = present("sum", parts)
    m = np.isfinite(want)
    e = float(np.max(np.abs(got[m] - want[m]) / (ATOL + RTOL * np.abs(want[m]))))
    assert e > 1.0, e
    _, _, sound = kernel_parts("xla", fn, False, grid)
    assert err(present("sum", sound), want) < 1.0


# -- the store ----------------------------------------------------------------

def test_a_zero_residual_one_phase_store_is_todays_store():
    """Every stamp on one grid: no residual block, the s64 block resident,
    today's operands — their shapes and every entry, the 0/1 matrices held
    in bf16 since the kernel spells its passes out — and today's program
    key."""
    t = BASE + np.arange(K)[None, :] * IV + np.zeros((S, 1), np.int64)
    st = fed(t, stream()[1])
    assert st.stamp_form == "grid" and st.res is None and st.grid_ok
    assert st.ts is not None and st.line_info() is None
    assert st.grid_info() == (BASE, IV)
    out_ts = np.arange(BASE + 400_007, BASE + 900_000, 15_000)
    got = fusedgrid.host_operands(C, 128, out_ts, WINDOW, BASE, IV, "rate")
    # the closed forms as they stood before there was a line form
    lo = np.ceil((out_ts - WINDOW - BASE) / IV).astype(np.int64)
    hi = np.floor((out_ts - BASE) / IV).astype(np.int64)
    T = len(out_ts)
    band = np.zeros((C, 128), np.float32)
    band[:, :T] = gridfns.band_matrix(C, lo, hi, True, np.float32)
    ohlo = np.zeros((C, 128), np.float32)
    ohlo[:, :T] = gridfns.onehot_matrix(C, np.maximum(lo, 0), np.float32)
    lo_p, hi_p, rel_p = fusedgrid.pad_edges(lo, hi, out_ts - BASE, WINDOW, 128)
    c0, ca = fusedgrid.active_columns(C, lo, hi)
    want = (band[c0:c0 + ca], ohlo[c0:c0 + ca], lo_p, hi_p, rel_p, c0, ca)
    assert len(got) == len(want) == 7
    for i, (g, w) in enumerate(zip(got, want)):
        if isinstance(w, np.ndarray):
            assert g.dtype == (jnp.bfloat16 if i < 2 else w.dtype)
            assert g.shape == w.shape
            assert g.astype(w.dtype).tobytes() == w.tobytes()
        else:
            assert g == w
    from filodb_tpu.query.plancache import plan_cache
    plan_cache.clear()
    fusedgrid.fused_grid_aggregate("sum", "rate", st.val, st.n,
                                   jnp.zeros(S, jnp.int32), 1, out_ts, WINDOW,
                                   BASE, IV, variant="xla")
    keys = [k for k in plan_cache._entries if k[0] == "fused-grid"]
    assert len(keys) == 1 and "line" not in keys[0]


def test_a_layout_store_keeps_todays_behaviour():
    """No line form for a histogram store: its first off-grid stamp clears
    grid_ok for the shard and the s64 block stays."""
    st = SeriesStore(8, 16, nbuckets=4)
    for k in range(4):
        ts = np.full(8, BASE + k * IV, np.int64)
        ts[3] += 5 * (k == 2)
        st.append(np.arange(8), ts, np.ones((8, 4)) * k)
    assert st.stamp_form == "grid" and st.res is None
    assert not st.grid_ok and st.grid_info() is None and st.ts is not None
    assert int(np.asarray(st.ts)[3, 2]) == BASE + 2 * IV + 5


def test_the_interval_is_the_median_step_not_the_first():
    t, v = stream()
    t[0, 1] += 40                      # row 0's first step is 10,040 ms
    st = fed(t[:, :6], v[:, :6])
    assert st.grid_interval == IV and not any(st.demoted.values())


REASONS = {"gap": 20, "residual": 21, "interval": 22}


def demoting_stream():
    """Scrape 50 on: row 20 skips four cells (one more than a line keeps
    as holes), row 21 is 300 ms late once, row 22 goes on at a 16 s
    interval."""
    t, v = stream(seed=3, reset_row=None)
    t[20, 50:] += (HOLE_RUN_MAX + 1) * IV
    t[21, 50] += 300
    t[22, 50:] += 6_000 * np.arange(1, K - 49)
    return t, v


def test_each_misfit_demotes_its_row_alone_counted_by_reason():
    t, v = demoting_stream()
    st = SeriesStore(S, C)
    for k in range(K):
        st.append(np.arange(S), t[:, k], v[:, k])
        assert st.demoted_last_append == (3 if k == 50 else 0)
        if k == 49:
            assert not any(st.demoted.values())
            assert len(st.line_info().minority) == 0
    assert st.stamp_form == "line"
    assert st.demoted == {"gap": 1, "residual": 1, "interval": 1}
    info = st.line_info()
    assert info.minority.tolist() == sorted(REASONS.values())
    assert st.rows_off_line() == 3
    # the stamps it was given, demoted rows and all
    np.testing.assert_array_equal(np.asarray(st.ts_block())[:, :K], t)
    ts, _val, _n = st.arrays()
    rid = jnp.asarray([22, 0, 20, 21], jnp.int32)
    np.testing.assert_array_equal(np.asarray(ts.gather_rows(rid))[:, :K],
                                  t[[22, 0, 20, 21]])


def labels(i):
    return {"_metric_": "m", "host": f"h{i}", "g": f"g{i % G}"}


def ingest(shard, t, v, cols):
    b = RecordBuilder(GAUGE)
    for k in cols:
        for i in range(len(t)):
            b.add(labels(i), int(t[i, k]), float(v[i, k]))
    shard.ingest(b.build())
    shard.flush()


def mk_engine(rows=S, cap=C):
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", GAUGE, 0, StoreConfig(
        max_series_per_shard=rows, samples_per_series=cap,
        flush_batch_size=10**9, groups_per_shard=4))
    return ms, shard, QueryEngine(ms, "prometheus")


def answer(r):
    """{group label or "": f64[T]} of an aggregate's result."""
    m = r.matrix
    vals = np.asarray(m.values, np.float64)
    return {k.as_dict().get("g", ""): vals[i] for i, k in enumerate(m.keys)}


def check_query(eng, t, v, q, fn, agg, by, start, end, step, rows=None):
    r = eng.query_range(q, start, end, step)
    out_ts = np.arange(start, end + 1, step)
    sel = np.arange(len(t)) if rows is None else np.asarray(rows)
    x = reference(t, v, fn, out_ts, rows=sel)
    gids = sel % G if by else np.zeros(len(sel), int)
    want = aggregate(agg, x, gids, G if by else 1)
    got = answer(r)
    for g in range(G if by else 1):
        key = f"g{g}" if by else ""
        assert err(got[key], want[g]) < 1.0, (q, key)
    return r


@pytest.mark.parametrize("mode", ("pallas", "xla"))
def test_demoted_rows_are_answered_through_the_minority_correction(mode):
    t, v = demoting_stream()
    ms, shard, eng = mk_engine()
    ingest(shard, t, v, range(K))
    assert shard.store.demoted == {"gap": 1, "residual": 1, "interval": 1}
    tracer.drain()
    with fused_mode(mode):
        for q, fn, agg, by in (
                ("sum(rate(m[5m]))", "rate", "sum", False),
                ("avg by (g)(avg_over_time(m[5m]))", "avg_over_time", "avg",
                 True),
                ("stddev(sum_over_time(m[5m]))", "sum_over_time", "stddev",
                 False),
                ("count by (g)(count_over_time(m[5m]))", "count_over_time",
                 "count", True)):
            r = check_query(eng, t, v, q, fn, agg, by, BASE + 400_007,
                            BASE + 950_007, 15_000)
            assert r.exec_path == f"local-fused[{fusedgrid.kernel_tag(mode)}]"
    spans = tracer.drain()
    kernels = [s for s in spans if s.name == SPAN_QUERY_KERNEL
               and s.tags.get("phase") == "dispatch"]
    assert len(kernels) == 4
    assert all(s.tags["stamps"] == "line" for s in kernels)
    selects = [s for s in spans if s.name == SPAN_QUERY_SELECT]
    assert selects and all(s.tags["demoted"] == 3 for s in selects)
    flushes = [s for s in spans if s.name == SPAN_INGEST_FLUSH]
    assert not flushes          # nothing was flushed since the drain


@pytest.mark.parametrize("mode", ("pallas", "xla"))
def test_the_served_path_counts_the_last_sample_of_a_row_that_ended(
        mode, monkeypatch):
    """Through the engine, a selector over half the shard (wide: the fused
    kernel answers, its row mask in ``n``): steps 1 ms apart across the
    instant a window's low edge passes an ended row's last stamp."""
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", 8)
    t, v, n = ended_stream()
    ms, shard, eng = mk_engine()
    b = RecordBuilder(GAUGE)
    for k in range(K):
        for i in np.flatnonzero(n > k):
            b.add(labels(i), int(t[i, k]), float(v[i, k]))
    shard.ingest(b.build())
    shard.flush()
    assert shard.store.stamp_form == "line"
    sel = [i for i in range(S) if i % G in (1, 3)]
    with fused_mode(mode):
        for row in (1, 13):
            last = int(t[row, n[row] - 1])
            out_ts = last + WINDOW + np.arange(-2, 3)
            r = eng.query_range('sum by (g)(count_over_time(m{g=~"g[13]"}'
                                '[5m]))', int(out_ts[0]), int(out_ts[-1]), 1)
            assert r.exec_path == f"local-fused[{fusedgrid.kernel_tag(mode)}]"
            got = answer(r)
            for g in (1, 3):
                want = np.nansum([eval_range_fn(
                    "count_over_time", t[i, :n[i]], v[i, :n[i]], out_ts,
                    WINDOW) for i in sel if i % G == g], axis=0)
                assert got[f"g{g}"].tolist() == want.tolist()
            # the row's own count falls from 1 to absent as the edge passes
            own = eval_range_fn("count_over_time", t[row, :n[row]],
                                v[row, :n[row]], out_ts, WINDOW)
            assert own[:3].tolist() == [1, 1, 1] and np.isnan(own[3:]).all()


def test_past_the_gate_the_general_path_answers():
    """More than a quarter of the selection off its line: no fused kernel,
    the general kernels over the derived stamps, still the reference."""
    t, v = stream(seed=5, reset_row=None)
    t[::3, 30:] += (HOLE_RUN_MAX + 1) * IV  # a third of the rows: a gap
    ms, shard, eng = mk_engine()
    ingest(shard, t, v, range(K))
    assert shard.store.demoted["gap"] == len(range(0, S, 3))
    r = check_query(eng, t, v, "sum(rate(m[5m]))", "rate", "sum", False,
                    BASE + 400_007, BASE + 950_007, 15_000)
    assert "fused" not in r.exec_path and r.stats.fused_kernels == 0


def test_a_store_that_turns_from_grid_to_line_in_mid_stream():
    """Exact stamps for 40 scrapes, a scraper's from then on (each series
    then keeps the phase of its first stamp, 0: only the late scrapes
    show). Same answers before, across and after; the form turns once."""
    _, v = stream(seed=7)
    rng = np.random.default_rng(7)
    late = np.where(rng.random((S, K)) < 0.25, rng.integers(3, 64, (S, K)), 0)
    late[:, :40] = 0
    t = BASE + np.arange(K)[None, :] * IV + late
    ms, shard, eng = mk_engine()
    queries = (("sum(rate(m[5m]))", "rate", "sum", False),
               ("stddev by (g)(avg_over_time(m[5m]))", "avg_over_time",
                "stddev", True))
    tracer.drain()
    ingest(shard, t, v, range(40))
    st = shard.store
    assert st.stamp_form == "grid" and st.ts is not None
    for q in queries:                               # before
        r = check_query(eng, t[:, :40], v[:, :40], *q, BASE + 310_000,
                        BASE + 390_000, 10_000)
        assert "fused" in r.exec_path
    for k in range(40, K):
        ingest(shard, t, v, [k])
    assert st.stamp_form == "line" and st.ts is None and st.res is not None
    assert not any(st.demoted.values())
    np.testing.assert_array_equal(np.asarray(st.ts_block())[:, :K], t)
    for lo, hi in ((BASE + 310_001, BASE + 390_001),    # before the turn
                   (BASE + 330_003, BASE + 700_003),    # across it
                   (BASE + 720_007, BASE + 980_007)):   # after
        for q in queries:
            r = check_query(eng, t, v, *q, lo, hi, 10_000)
            assert "fused" in r.exec_path
    spans = tracer.drain()
    forms = [s.tags["stamps"] for s in spans if s.name == SPAN_QUERY_KERNEL
             and s.tags.get("phase") == "dispatch"]
    assert forms == ["grid"] * 2 + ["line"] * 6
    assert all(s.tags["demoted"] == 0 for s in spans
               if s.name == SPAN_INGEST_FLUSH)


def test_the_raw_selector_returns_the_stamps_it_was_given():
    """Values through the instant selector, stamps through timestamp():
    each sample's own, exactly, from line + residual (and from the pool
    for a demoted row)."""
    t, v = demoting_stream()
    ms, shard, eng = mk_engine()
    ingest(shard, t, v, range(K))
    rows = [20, 21, 22, 24]                     # three demoted, one not
    out_ts = np.arange(BASE + 500_000, BASE + 600_001, 5_000)
    last = [[int(np.flatnonzero(t[i] <= x)[-1]) for x in out_ts] for i in rows]
    for q, want in (
            ('m{host=~"h2[0124]"}',
             [[v[i, k] for k in ks] for i, ks in zip(rows, last)]),
            ('timestamp(m{host=~"h2[0124]"})',
             [[t[i, k] / 1000.0 for k in ks] for i, ks in zip(rows, last)])):
        r = eng.query_range(q, int(out_ts[0]), int(out_ts[-1]), 5_000)
        got = {k.as_dict()["host"]: np.asarray(r.matrix.values)[j]
               for j, k in enumerate(r.matrix.keys)}
        assert sorted(got) == [f"h{i}" for i in rows]
        for i, w in zip(rows, want):
            assert got[f"h{i}"].tolist() == w, (q, i)
    # of anything but a selector, a value's stamp is still the step
    r = eng.query_range('timestamp(rate(m{host="h24"}[5m]))',
                        int(out_ts[0]), int(out_ts[-1]), 5_000)
    assert np.asarray(r.matrix.values)[0].tolist() == (out_ts / 1000).tolist()


def test_compaction_and_frees_keep_the_line():
    t, v = demoting_stream()
    st = fed(t, v)
    cut = int(t[:, 30].max()) + 1
    st.compact(cut)
    keep = t >= cut
    n = keep.sum(1)
    assert st.n_host[:S].tolist() == n.tolist()
    got = np.asarray(st.ts_block())
    for i in range(S):
        assert got[i, :n[i]].tolist() == t[i][keep[i]].tolist()
    assert st.line_info().minority.tolist() == sorted(REASONS.values())
    st.free_rows(np.array([21, 40]))
    assert st.line_info().minority.tolist() == [20, 22]
    # a freed slot starts a new row on a line of its own
    st.append(np.array([21, 21]), np.array([cut + 77, cut + 77 + IV]),
              np.array([1.0, 2.0]))
    assert not st.off_line[21] and st.n_host[21] == 2
    assert np.asarray(st.ts_block())[21, :2].tolist() == [cut + 77,
                                                          cut + 77 + IV]


def test_the_derived_block_is_kept_until_the_next_mutation():
    """Queries off the fused path share one derivation of the s64 block
    (and one upload of the demoted rows' pool) per state of the store."""
    t, v = demoting_stream()
    st = fed(t[:, :60], v[:, :60])
    a = st.ts_block()
    assert st.ts_block() is a and st._pool_dev is not None
    pool = st._pool_dev
    ts, _val, _n = st.arrays()
    rid = jnp.asarray([20, 3], jnp.int32)
    np.testing.assert_array_equal(np.asarray(ts.gather_rows(rid))[:, :60],
                                  t[[20, 3], :60])
    assert st._pool_dev is pool                 # no second upload
    st.append(np.arange(S), t[:, 60], v[:, 60])
    assert st._line_block is None and st._pool_dev is None
    b = st.ts_block()
    assert b is not a
    np.testing.assert_array_equal(np.asarray(b)[:, :61], t[:, :61])
    st.free_rows(np.array([5]))
    assert st._line_block is None
    assert (np.asarray(st.ts_block())[5] == chunkstore.TS_PAD).all()
    st.compact(int(t[:, 10].max()) + 1)
    assert st._line_block is None
    got = np.asarray(st.ts_block())
    for i in (20, 21, 22, 30):
        keep = t[i, :61] > t[:, 10].max()
        assert got[i, :keep.sum()].tolist() == t[i, :61][keep].tolist()


def test_a_freed_rows_pool_slot_is_taken_again():
    """Churn does not grow the pool: a demoted row's slot goes back when
    the row is freed, and the next demotion takes it."""
    t, v = demoting_stream()
    st = fed(t[:, :60], v[:, :60])
    assert st._pool_next == 3 and len(st._pool_ts) == 8
    for turn in range(12):
        row = 30 + turn
        st.free_rows(np.array([20 if turn == 0 else row - 1]))
        st.append(np.array([row]), np.array([t[row, 60] + 300]),
                  np.array([1.0]))             # 300 ms off its line
        assert st.off_line[row] and st._pool_next == 3
    assert len(st._pool_free) == 0 and len(st._pool_ts) == 8
    assert st.demoted["residual"] == 13
    got = np.asarray(st.ts_block())
    assert got[41, :61].tolist() == t[41, :60].tolist() + [t[41, 60] + 300]
    assert got[22, :60].tolist() == t[22, :60].tolist()


def test_where_a_window_lies_in_a_row_is_one_function():
    """grid_edges with the line's spread: [lo, hi] are in the window for
    every start and residual, and at most two cells a side are open."""
    rng = np.random.default_rng(2)
    out_ts = BASE + rng.integers(WINDOW, 10**7, 200)
    dmin, dmax = fusedgrid.line_spread(IV)
    lo, hi = gridfns.grid_edges(out_ts, WINDOW, BASE, IV, (dmin, dmax))
    for d in (dmin, dmax):
        assert (BASE + lo * IV + d >= out_ts - WINDOW).all()
        assert (BASE + hi * IV + d <= out_ts).all()
        assert (BASE + (lo - 3) * IV + d < out_ts - WINDOW).all()
        assert (BASE + (hi + 3) * IV + d > out_ts).all()
    assert fusedgrid.line_fusable(WINDOW, IV)
    assert not fusedgrid.line_fusable(2 * IV, IV)
    assert not fusedgrid.line_fusable(WINDOW, 4 * RES_MAX)


def test_the_served_path_on_a_line_store(monkeypatch):
    """HTTP, a flush that turns the form, the kernel tags, /metrics."""
    from filodb_tpu.config import Config
    from filodb_tpu.standalone import FiloServer
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", 8)
    t, v = demoting_stream()
    srv = FiloServer(Config({
        "num_shards": 1, "http": {"port": 0},
        "store": {"max_series_per_shard": S, "samples_per_series": C,
                  "flush_batch_size": 10**9}})).start()
    try:
        shard = srv.memstore.shards_of("prometheus")[0]
        tracer.drain()
        ingest(shard, t, v, range(K))
        url = f"http://127.0.0.1:{srv.http.port}"
        q = urllib.parse.urlencode({
            "query": "sum by (g)(rate(m[5m]))", "start": BASE / 1000 + 400.007,
            "end": BASE / 1000 + 950.007, "step": 15})
        with urllib.request.urlopen(
                f"{url}/promql/prometheus/api/v1/query_range?{q}",
                timeout=120) as r:
            body = json.load(r)
        with urllib.request.urlopen(f"{url}/metrics", timeout=30) as r:
            text = r.read().decode()
    finally:
        srv.shutdown()
    assert body["status"] == "success"
    assert body["stats"]["exec_path"].startswith("local-fused[")
    out_ts = np.arange(BASE + 400_007, BASE + 950_008, 15_000)
    want = aggregate("sum", reference(t, v, "rate", out_ts),
                     np.arange(S) % G, G)
    for s in body["data"]["result"]:
        g = int(s["metric"]["g"][1:])
        got = np.array([float(x) for _, x in s["values"]])
        assert err(got, want[g]) < 1.0
    spans = tracer.drain()
    flush = [s for s in spans if s.name == SPAN_INGEST_FLUSH]
    assert sum(s.tags["demoted"] for s in flush) == 3
    lines = text.splitlines()
    # the rate program's fetch says how many of its tiles ran the band form
    # (no counter of this stream falls under the range, no row ends under
    # it: none), and /metrics counts them apart from the hist kernel's
    fetch, = (s.tags for s in spans if s.name == SPAN_QUERY_KERNEL
              and s.tags.get("phase") == "fetch")
    assert fetch == {"phase": "fetch", "tiles": 1, "fall_tiles": 0}
    assert sum(ln.startswith('filodb_query_fused_fall_tiles_total{'
                             'kernel="line",mode="pallas"} ')
               for ln in lines) == 1
    assert 'filodb_store_stamp_form{shard="0"} 1' in lines
    assert 'filodb_store_rows_off_line{shard="0"} 3' in lines
    for why in chunkstore.DEMOTE_REASONS:
        assert (f'filodb_store_rows_demoted_total{{reason="{why}",'
                f'shard="0"}} 1') in lines
