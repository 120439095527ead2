"""Missed scrapes on a line store: a hole is kept as a hole, and the fused
kernel reads around it (``tests/prom_reference.py`` given the same holes).

A scraper misses scrapes as a matter of course; Prometheus then appends a
staleness marker (``value.StaleNaN``) at the scrape's stamp and sends
nothing more until a scrape succeeds. The store keeps either — a marker's
row, a cell a row skipped — as a HOLE in the cell of the row's line
(core/chunkstore.py, the text at ``RES_DTYPE``), runs of up to
``HOLE_RUN_MAX``, and the fused line kernel's hole-aware mode
(ops/fusedgrid.py ``_hole_contrib``) counts by validity, takes an increment
from a sample to the NEXT sample and finds a window's first and last
samples among those that exist. Here: that mode on both backends, packed
and unpacked, with a hole put at every place of a window that the algebra
tells apart; the store (marker, skipped cell, a run past the bound, the
form's turn, eviction, compaction, recovery); the served path with
``timestamp()`` and the absent step; the plan keys of a store without
holes; and a control that ignores the marks and must miss the tolerance.
"""

import functools
import json
import urllib.parse
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from filodb_tpu.core import chunkstore
from filodb_tpu.core.chunkstore import (HOLE_RUN_MAX, RES_HOLE, STALE_NAN,
                                        TS_PAD, SeriesStore)
from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE
from filodb_tpu.ops import fusedgrid, gridfns
from filodb_tpu.query import exec as qexec
from filodb_tpu.query.engine import QueryEngine
from filodb_tpu.utils.tracing import (SPAN_INGEST_FLUSH, SPAN_QUERY_KERNEL,
                                      SPAN_QUERY_SELECT, tracer)

from .prom_reference import eval_range_fn
from .test_fused_resident import fused_mode
from .test_line_stamps import (AGGS, BACKENDS, BASE, FNS, IV, RATE_FNS,
                               RESET_ROW, ROWS_PLACED, TARGET, TC, TILE_STEPS,
                               TK, TS, WANT_FALLS, WINDOW, aggregate,
                               band_form, err, present, same_bits, tile_steps)

# where a hole sits, relative to a target step's sure range [lo, hi] (the
# cells EVERY row holds in that window) and its four open edge cells
PLACES = ("a2", "a1", "lo", "lo+1", "interior", "hi-1", "hi", "b1", "b2",
          "run3 across lo", "run3 across hi", "run3 inside", "ends in holes",
          "window left with 1", "window left with 0")
ROWS_A_PLACE = 4
SIZES = {"1024x128": (1024, 128, 100, (30, 52, 74)),
         "2048x256": (2048, 256, 200, (60, 120, 170))}
LAYOUTS = {"packed": 61, "unpacked": 100}       # steps: two slots a block, one
# where in the interval a target step lies: anywhere; where a late phase
# puts cell lo - 2 into the window; where an early one puts hi + 2 there
OFFSETS = np.array([4_321, IV - 100, 200])


def cells_of(place, lo, hi):
    """The cells (relative numbering of the row's line) a place makes
    holes of, for one target window."""
    mid = (lo + hi) // 2
    return {"a2": [lo - 2], "a1": [lo - 1], "lo": [lo], "lo+1": [lo + 1],
            "interior": [mid], "hi-1": [hi - 1], "hi": [hi], "b1": [hi + 1],
            "b2": [hi + 2], "run3 across lo": [lo - 1, lo, lo + 1],
            "run3 across hi": [hi - 1, hi, hi + 1],
            "run3 inside": [mid - 1, mid, mid + 1]}.get(place, [])


@functools.lru_cache(maxsize=None)
def the_stream(size):
    """(stamps [R, K], values [R, K], miss [R, K], gone [R, K], rows [R],
    gids [R], steps {layout: out_ts}): R live rows spread over a store of
    S, ROWS_A_PLACE a place plus as many again with seeded holes; phases at
    both ends of the interval among them (an edge cell is in the window
    for a row or not by its phase), one scrape in four late. ``miss``:
    the scrape failed (a marker came); ``gone``: nothing came at all (the
    row skipped the cell, or had ended)."""
    S, C, K, targets = SIZES[size]
    rng = np.random.default_rng(len(size) + S)
    P = len(PLACES)
    R = 2 * P * ROWS_A_PLACE
    phase = rng.integers(0, IV, R)
    phase[0] = 0                                    # the base of the lines
    phase[1::ROWS_A_PLACE] = IV - 1 - rng.integers(0, 60, len(
        phase[1::ROWS_A_PLACE]))                    # a2 can be in the window
    phase[2::ROWS_A_PLACE] = rng.integers(0, 120, len(phase[2::ROWS_A_PLACE]))
    late = np.where(rng.random((R, K)) < 0.25, rng.integers(3, 64, (R, K)), 0)
    late[:, 0] = 0
    t = BASE + phase[:, None] + np.arange(K)[None, :] * IV + late
    v = (np.cumsum(rng.integers(0, 100, (R, K)), axis=1)
         + rng.integers(0, 1000, R)[:, None]).astype(np.float64)
    v[3, K // 2:] -= v[3, K // 2] - 3               # one counter reset
    out = {}
    for name, T in LAYOUTS.items():
        stride = (K * IV - 200_000) // T | 1        # over the whole stream
        grid = BASE + 330_007 + stride * np.arange(T - 6)
        hits = BASE + np.array(targets) * IV + WINDOW // 2 + OFFSETS
        steps = np.unique(np.concatenate([hits, hits + 1, grid]))
        assert len(steps) == T
        out[name] = steps
    lo, hi = gridfns.grid_edges(hits, WINDOW, BASE, IV,
                                fusedgrid.line_spread(IV))
    miss = np.zeros((R, K), bool)
    gone = np.zeros((R, K), bool)
    gids = np.zeros(R, np.int32)
    for p, place in enumerate(PLACES):
        for j in range(ROWS_A_PLACE):
            r = p * ROWS_A_PLACE + j
            gids[r] = p
            how = miss if j % 2 == 0 else gone      # markers, skipped cells
            for a, b in zip(lo, hi):
                how[r, cells_of(place, int(a), int(b))] = True
            a, b = int(lo[-1]), int(hi[-1])
            if place == "ends in holes":            # two markers, then ends
                miss[r, b - 3:b - 1], gone[r, b - 1:] = True, True
            elif place == "window left with 1":     # lo alone is left
                miss[r, [a - 2, a - 1, a + 1, a + 2]] = True
                gone[r, a + 3:] = True
            elif place == "window left with 0":     # a run of 3 at its end
                miss[r, a - 2:a + 1], gone[r, a + 1:] = True, True
    # the other half: seeded holes, runs capped at the bound
    raw = rng.random((R, K)) < 1 / 16
    for k in range(K):
        if k >= HOLE_RUN_MAX:
            raw[:, k] &= ~raw[:, k - HOLE_RUN_MAX:k].all(axis=1)
    half = P * ROWS_A_PLACE
    miss[half:] = raw[half:]
    gids[half:] = P + np.arange(R - half) % 3
    miss[:, 0] = gone[:, 0] = False                 # the registration scrape
    rows = (np.arange(R) * (S // R) + 3).astype(np.int32)
    return t, v, miss, gone, rows, gids, out


@functools.lru_cache(maxsize=None)
def the_store(size):
    S, C, K, _ = SIZES[size]
    t, v, miss, gone, rows, _gids, _ = the_stream(size)
    st = SeriesStore(S, C)
    for k in range(K):
        here = ~gone[:, k]
        st.append(rows[here], t[here, k],
                  np.where(miss[here, k], STALE_NAN, v[here, k]))
    assert st.stamp_form == "line" and not any(st.demoted.values())
    info = st.line_info()
    assert info.holes and info.base_ts == BASE and len(info.minority) == 0
    return st


NGROUPS = len(PLACES) + 3


@functools.lru_cache(maxsize=None)
def kernel_parts(size, backend, fn, layout, marks=True, grouped=True):
    t, v, miss, gone, rows, gids, steps = the_stream(size)
    st = the_store(size)
    info = st.line_info()
    g = np.zeros(st.S, np.int32)
    if grouped:
        g[rows] = gids
    line = (info.start, info.res)
    if not marks:       # the control: a kernel that ignores the marks
        line = (info.start, jnp.where(info.res == RES_HOLE, 0, info.res))
    parts = fusedgrid.fused_grid_aggregate(
        "stddev", fn, st.val, st.n, jnp.asarray(g), NGROUPS, steps[layout],
        WINDOW, info.base_ts, info.interval_ms, variant=backend, line=line,
        holes=marks)
    return {k: np.asarray(a) for k, a in parts.items()}


@functools.lru_cache(maxsize=None)
def want_matrix(size, fn, layout):
    t, v, miss, gone, *_rest, steps = the_stream(size)
    there = ~(miss | gone)
    return np.array([eval_range_fn(fn, t[r][there[r]], v[r][there[r]],
                                   steps[layout], WINDOW)
                     for r in range(len(t))])


# -- the hole-aware mode against the reference, both backends -----------------

@pytest.mark.parametrize("place", range(len(PLACES)), ids=PLACES)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("fn", FNS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_a_hole_at_every_place_of_a_window(backend, fn, layout, place):
    """Each place's rows are a group of their own: the kernel's partial
    state of that group against the reference's rows (no stddev of four
    rows here: in f32 its squares cancel past the tolerance on ANY store;
    the next test takes it over all rows)."""
    size = "1024x128"
    gids = the_stream(size)[5]
    parts = kernel_parts(size, backend, fn, layout)
    want = want_matrix(size, fn, layout)
    for agg in ("sum", "avg", "count"):
        got = present(agg, parts)[place]
        assert err(got[None], aggregate(agg, want, gids, NGROUPS)[place][None]) \
            < 1.0, (PLACES[place], agg)


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("fn", FNS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_the_hole_mode_matches_the_reference_at_both_sizes(backend, fn,
                                                           layout, size):
    """Every group at once, the seeded rows too, and every aggregate over
    all rows as one group; counts are EXACT."""
    gids = the_stream(size)[5]
    parts = kernel_parts(size, backend, fn, layout)
    want = want_matrix(size, fn, layout)
    for agg in ("sum", "avg", "count"):
        assert err(present(agg, parts),
                   aggregate(agg, want, gids, NGROUPS)) < 1.0, agg
    whole = kernel_parts(size, backend, fn, layout, grouped=False)
    for agg in AGGS:
        assert err(present(agg, whole)[:1],
                   aggregate(agg, want, gids * 0, 1)) < 1.0, agg
    if fn == "count_over_time":
        np.testing.assert_array_equal(
            parts["sum"], np.nan_to_num(aggregate("sum", want, gids, NGROUPS)))


@pytest.mark.parametrize("fn", FNS)
def test_both_backends_agree_to_the_bit(fn):
    for layout in LAYOUTS:
        a = kernel_parts("1024x128", "pallas", fn, layout)
        b = kernel_parts("1024x128", "xla", fn, layout)
        for k in ("sum", "count", "sumsq"):
            np.testing.assert_array_equal(a[k], b[k])


def test_the_places_meet_the_cases_they_are_there_for():
    """At the target steps the reference itself says so: a hole at an edge
    cell takes a sample out of some row's window, the window left with one
    sample counts 1 and has no rate, the one left with none has no count."""
    size = "1024x128"
    t, v, miss, gone, rows, gids, steps = the_stream(size)
    _S, _C, K, targets = SIZES[size]
    out_ts = steps["packed"]
    hits = np.searchsorted(out_ts, BASE + np.array(targets) * IV
                           + WINDOW // 2 + OFFSETS)
    hit = hits[-1]
    cnt = want_matrix(size, "count_over_time", "packed")
    rate = want_matrix(size, "rate", "packed")
    ends = [np.flatnonzero(~g)[-1] + 1 for g in gone]   # had every scrape
    whole = np.array([eval_range_fn("count_over_time", t[r][:ends[r]],
                                    v[r][:ends[r]], out_ts, WINDOW)
                      for r in range(len(t))])            # come, up to its end
    for p, place in enumerate(PLACES):
        mine = slice(p * ROWS_A_PLACE, (p + 1) * ROWS_A_PLACE)
        if place == "window left with 1":
            assert (cnt[mine, hit] == 1).all() and np.isnan(rate[mine, hit]).all()
        elif place == "window left with 0":
            assert np.isnan(cnt[mine, hit]).all()
        elif place in ("a2", "a1", "b1", "b2"):
            # in the window for the rows whose phase puts it there only
            assert (cnt[mine][:, hits] < whole[mine][:, hits]).any(), place
            assert (cnt[mine][:, hits] == whole[mine][:, hits]).any(), place
        elif place != "ends in holes":
            assert (cnt[mine, hit] < whole[mine, hit]).all(), place
    st = the_store(size)
    ends = rows[PLACES.index("ends in holes") * ROWS_A_PLACE]
    n = int(st.n_host[ends])
    assert np.asarray(st.res)[ends, n - 2:n + 1].tolist() == [RES_HOLE] * 2 \
        + [0]
    assert st.hole_cells == int(st.holes_host.sum()) > 100


@pytest.mark.parametrize("fn", ("rate", "avg_over_time", "count_over_time"))
def test_a_kernel_that_ignores_the_marks_misses_the_tolerance(fn):
    """The control: the line kernel of a store WITHOUT holes over this one,
    every mark read as a residual of 0 — a missed scrape taken for a sample
    (of what its value cell holds). A failure, not a speed-up."""
    size, layout = "1024x128", "packed"
    gids = the_stream(size)[5]
    want = aggregate("sum", want_matrix(size, fn, layout), gids, NGROUPS)
    blind = present("sum", kernel_parts(size, "xla", fn, layout, marks=False))
    m = np.isfinite(want) & np.isfinite(blind)
    e = float(np.max(np.abs(blind[m] - want[m])
                     / (1e-4 + 2e-4 * np.abs(want[m]))))
    assert e > 1.0, e
    assert err(present("sum", kernel_parts(size, "xla", fn, layout)),
               want) < 1.0


def test_a_store_without_holes_builds_todays_programs():
    """No hole, no new operand, key or tag value: the line program's key is
    what PR 34 left, and the mode's own adds one word."""
    from filodb_tpu.query.plancache import plan_cache
    S, C, K = 64, 128, 60
    t = BASE + (np.arange(S) * 137 % IV)[:, None] + np.arange(K)[None] * IV
    st = SeriesStore(S, C)
    for k in range(K):
        st.append(np.arange(S), t[:, k], np.full(S, float(k)))
    info = st.line_info()
    assert st.stamp_form == "line" and not info.holes and st.hole_cells == 0
    out_ts = BASE + 330_007 + 9_013 * np.arange(20)
    plan_cache.clear()
    tracer.drain()
    for holes in (False, True):
        fusedgrid.fused_grid_aggregate(
            "sum", "rate", st.val, st.n, jnp.zeros(S, jnp.int32), 1, out_ts,
            WINDOW, info.base_ts, info.interval_ms, variant="xla",
            line=(info.start, info.res), holes=holes)
    keys = [k for k in plan_cache._entries if k[0] == "fused-grid"]
    assert [k[14:] for k in keys] == [("line", 2), ("line", 2, "holes")]
    spans = [s.tags for s in tracer.drain() if s.name == SPAN_QUERY_KERNEL
             and s.tags.get("phase") == "dispatch"]
    assert [(s["stamps"], s["packed"], s["holes"]) for s in spans] == [
        ("line", 2, 0), ("line", 2, 1)]
    for kind in ("rate", "window"):
        a = fusedgrid.host_operands(C, 128, out_ts, WINDOW, BASE, IV, kind,
                                    line=True)
        b = fusedgrid.host_operands(C, 128, out_ts, WINDOW, BASE, IV, kind,
                                    line=True, holes=True)
        assert [x.shape for x in a[:6]] == [x.shape for x in b[:6]] \
            or a[-1] != b[-1]           # one more cell a side may widen Ca
        for x, y in zip(a[1:6], b[1:6]):
            if x.shape == y.shape:
                assert x.tobytes() == y.tobytes()


# -- the telescoped delta of the hole mode ------------------------------------
#
# tests/test_line_stamps.py section D, on a line that has holes: the sure
# range's delta is its LAST sample's value less its FIRST's (the two filled
# planes picked at hi and lo), and the band product over the pairs runs in
# a tile where a pair that starts in some sure range fell, or a row's last
# sample lies beyond the fills' reach before some hi. Arrays by hand, three
# tiles of 512 rows; a hole's value cell holds a number no function reads.

HG = 4


@functools.lru_cache(maxsize=None)
def hole_stream(kind, rows=TS):
    """(start, res with RES_HOLE marks, val, n, there [rows, TK]): one cell
    in sixteen a hole, runs capped at the bound, never the first cell;
    ``kind`` as test_line_stamps.tile_stream has it."""
    rng = np.random.default_rng(len(kind) + rows + 1)
    start = rng.integers(0, IV, rows).astype(np.int32)
    start[0] = 0
    res = np.zeros((rows, TC), np.int8)
    res[:, :TK] = np.where(rng.random((rows, TK)) < 0.25,
                           rng.integers(-60, 61, (rows, TK)), 0)
    res[:, 0] = 0
    hole = rng.random((rows, TK)) < 1 / 16
    hole[:, 0] = False
    for k in range(HOLE_RUN_MAX, TK):
        hole[:, k] &= ~hole[:, k - HOLE_RUN_MAX:k].all(axis=1)
    inc = rng.integers(0, 100, (rows, TK)).astype(np.float64)
    if kind == "walk":
        inc -= 50
    if kind == "fraction":
        inc = inc * 1.0009765625 + rng.random((rows, TK))
    v = np.zeros((rows, TC))
    v[:, :TK] = np.cumsum(inc, axis=1) + rng.integers(0, 1000, rows)[:, None]
    n = np.full(rows, TK, np.int32)
    if kind == "reset":
        c = TK // 2
        hole[RESET_ROW, c - 1:c + 1] = False
        v[RESET_ROW, c:TK] -= v[RESET_ROW, c] - 3
    if kind == "ends":
        n[100] = 60
        hole[100, 59] = False
    res[:, :TK][hole] = RES_HOLE
    v[:, :TK][hole] = 7.0
    return start, res, v.astype(np.float32), n, ~hole


def hole_reference(fn, kind, rows, out_ts, v=None, there=None):
    start, res, val, n, th = hole_stream(kind, rows)
    v = val if v is None else v
    th = th if there is None else there
    t = (BASE + start[:, None].astype(np.int64) + np.arange(TK)[None, :] * IV
         + np.where(th, res[:, :TK], 0))
    out = []
    for s_ in range(rows):
        m = th[s_] & (np.arange(TK) < n[s_])
        out.append(eval_range_fn(fn, t[s_][m], v[s_, :TK][m].astype(
            np.float64), out_ts, WINDOW))
    return np.array(out)


def run_holes(backend, fn, kind, out_ts, grouped, rows=TS, v=None, res=None):
    """(partial state, the fetch's fall tags) of one hole-mode dispatch."""
    start, res0, val, n, _ = hole_stream(kind, rows)
    gids = (np.arange(rows) % HG if grouped else np.zeros(rows)).astype(
        np.int32)
    p = fusedgrid.fused_grid_aggregate(
        "stddev", fn, jnp.asarray(val if v is None else v), jnp.asarray(n),
        jnp.asarray(gids), HG if grouped else 1, np.asarray(out_ts, np.int64),
        WINDOW, BASE, IV, fetch=False, variant=backend,
        line=(jnp.asarray(start), jnp.asarray(res0 if res is None else res)),
        holes=True)
    parts = {k: np.asarray(a) for k, a in p.resolve().items()}
    return parts, dict(p.fall_tags)


@functools.lru_cache(maxsize=None)
def both_hole_forms(backend, fn, kind, layout, grouped):
    out_ts = tile_steps(TILE_STEPS[layout])
    got, falls = run_holes(backend, fn, kind, out_ts, grouped)
    with band_form():
        want, all_fell = run_holes(backend, fn, kind, out_ts, grouped)
    assert all_fell == {"fall_tiles": TS // 512, "tiles": TS // 512}
    return got, falls, want


@pytest.mark.parametrize("grouped", (False, True), ids=("global", "by"))
@pytest.mark.parametrize("layout", TILE_STEPS)
@pytest.mark.parametrize("fn", RATE_FNS)
@pytest.mark.parametrize("kind", WANT_FALLS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_the_hole_modes_telescoped_delta_answers_the_band_form_to_the_bit(
        backend, kind, fn, layout, grouped):
    """As on a line without holes: a stream without a fall reports none;
    one reset costs its own tile and the next the band form; a row whose
    last sample lies beyond the fills' reach before some hi falls for
    ``delta`` too; values that go down make every counter tile fall and no
    ``delta`` tile."""
    got, falls, want = both_hole_forms(backend, fn, kind, layout, grouped)
    same_bits(got, want)
    assert falls == {"tiles": TS // 512,
                     "fall_tiles": WANT_FALLS[kind][fn == "delta"]}


@pytest.mark.parametrize("fn", RATE_FNS)
@pytest.mark.parametrize("kind", ("clean", "reset"))
def test_the_hole_modes_telescoped_backends_agree_to_the_bit(kind, fn):
    for layout in TILE_STEPS:
        same_bits(both_hole_forms("pallas", fn, kind, layout, True)[0],
                  both_hole_forms("xla", fn, kind, layout, True)[0])


# a reset of row 5 at a cell named from the target step's sure range, and
# the holes beside it: (cell off lo or hi, from hi?, holes off the same,
# falls). A pair lies in its EARLIER sample's cell and the band sums the
# cells [lo, hi - 1]: the pair INTO lo and the pair that leaves hi are the
# edges', decided by picks — over a hole too
PLACED_H = {"lo": (0, 0, (), 0), "lo+1": (1, 0, (), 1),
            "interior": (12, 0, (), 1), "hi": (0, 1, (), 1),
            "hi+1": (1, 1, (), 0),
            "lo+1 over a hole at lo": (1, 0, (0,), 0),
            "hi over a hole at hi-1": (0, 1, (-1,), 1),
            "interior over a run of three": (12, 0, (9, 10, 11), 1)}


def placed_holes(place):
    off, from_hi, holes, _ = PLACED_H[place]
    lo, hi = gridfns.grid_edges(np.array([TARGET]), WINDOW, BASE, IV,
                                fusedgrid.line_spread(IV))
    lo, hi = int(lo[0]), int(hi[0])
    at = hi if from_hi else lo
    _start, res, val, _n, there = hole_stream("clean", ROWS_PLACED)
    res, v, there = res.copy(), val.copy(), there.copy()
    near = slice(lo - 5, hi + 6)
    # row 5 holds every cell around the window but the placed holes
    res[5, near] = np.where(res[5, near] == RES_HOLE, 0, res[5, near])
    there[5, near] = True
    v[5, :TK] = np.cumsum(np.full(TK, 17.0)) + 100
    for h in holes:
        res[5, at + h], there[5, at + h], v[5, at + h] = RES_HOLE, False, 7.0
    c = at + off
    v[5, c:TK] = np.where(there[5, c:], v[5, c:TK] - (v[5, c] - 3), 7.0)
    return res, v, there


@pytest.mark.parametrize("T", (1, 65), ids=("packed", "unpacked"))
@pytest.mark.parametrize("fn", RATE_FNS)
@pytest.mark.parametrize("place", PLACED_H)
@pytest.mark.parametrize("backend", BACKENDS)
def test_a_reset_beside_holes_falls_where_a_window_sums_its_pair(
        backend, place, fn, T):
    res, v, there = placed_holes(place)
    out_ts = np.full(T, TARGET)
    want = hole_reference(fn, "clean", ROWS_PLACED, out_ts, v, there)
    for grouped in (False, True):
        parts, falls = run_holes(backend, fn, "clean", out_ts, grouped,
                                 ROWS_PLACED, v, res)
        gids = np.arange(ROWS_PLACED) % HG if grouped else np.zeros(
            ROWS_PLACED, int)
        for agg in ("sum", "avg", "count"):
            assert err(present(agg, parts), aggregate(
                agg, want, gids, HG if grouped else 1)) < 1.0
        assert falls == {"tiles": 1, "fall_tiles":
                         PLACED_H[place][3] if fn != "delta" else 0}


@pytest.mark.parametrize("fn", RATE_FNS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_fractions_telescope_inside_the_tolerance_around_holes(backend, fn):
    rows = ROWS_PLACED
    for layout, T in TILE_STEPS.items():
        out_ts = tile_steps(T)
        want = hole_reference(fn, "fraction", rows, out_ts)
        for grouped in (False, True):
            parts, falls = run_holes(backend, fn, "fraction", out_ts, grouped,
                                     rows)
            gids = np.arange(rows) % HG if grouped else np.zeros(rows, int)
            for agg in ("sum", "avg", "count"):
                assert err(present(agg, parts), aggregate(
                    agg, want, gids, HG if grouped else 1)) < 1.0
            assert falls == {"tiles": 1, "fall_tiles": 0}


@pytest.mark.parametrize("fn", RATE_FNS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_an_empty_sure_range_adds_nothing_in_the_hole_mode(backend, fn):
    """The stream's very start: no sure cell, or one; and the padded
    lanes."""
    rows = ROWS_PLACED
    out_ts = BASE + np.array([5_000, 9_999, 15_000, 20_050, 31_000, 305_000])
    want = hole_reference(fn, "clean", rows, out_ts)
    parts, falls = run_holes(backend, fn, "clean", out_ts, True, rows)
    gids = np.arange(rows) % HG
    for agg in ("sum", "avg", "count"):
        assert err(present(agg, parts), aggregate(agg, want, gids, HG)) < 1.0
    assert falls == {"tiles": 1, "fall_tiles": 0}


@pytest.mark.parametrize("backend", BACKENDS)
def test_the_places_stream_reports_its_one_reset(backend):
    """The stream of the places has ONE counter reset (row 3) and rows that
    end in holes under a window: its rate programs report a fallen tile,
    its window programs no count."""
    size, layout = "1024x128", "packed"
    st = the_store(size)
    info = st.line_info()
    out_ts = the_stream(size)[6][layout]
    tracer.drain()
    for fn in ("rate", "sum_over_time"):
        fusedgrid.fused_grid_aggregate(
            "sum", fn, st.val, st.n, jnp.zeros(st.S, jnp.int32), 1, out_ts,
            WINDOW, info.base_ts, info.interval_ms, variant=backend,
            line=(info.start, info.res), holes=True)
    fetches = [s.tags for s in tracer.drain() if s.name == SPAN_QUERY_KERNEL
               and s.tags.get("phase") == "fetch"]
    assert fetches[0]["tiles"] == 2 and fetches[0]["fall_tiles"] >= 1
    assert fetches[1] == {"phase": "fetch"}


# -- the store ----------------------------------------------------------------

S8, C32, K20 = 8, 32, 20


def small_stream(seed=0):
    rng = np.random.default_rng(seed)
    phase = rng.integers(0, IV, S8)
    t = BASE + phase[:, None] + np.arange(K20)[None] * IV
    v = np.cumsum(rng.integers(1, 100, (S8, K20)), 1).astype(np.float64)
    miss = np.zeros((S8, K20), bool)
    gone = np.zeros((S8, K20), bool)
    miss[1, 5] = True                               # one marker
    miss[2, 7:10] = True                            # a run of three markers
    miss[3, 19] = True                              # a row that ends in one
    gone[4, 6:8] = True                             # two skipped cells
    gone[5, 3:3 + HOLE_RUN_MAX + 1] = True          # one more than the bound
    return t, v, miss, gone


def feed(st, t, v, miss, gone, cols):
    for k in cols:
        rows = np.flatnonzero(~gone[:, k])
        st.append(rows, t[rows, k], np.where(miss[rows, k], STALE_NAN,
                                             v[rows, k]))
    return st


def check_rows(st, t, v, miss, gone, skip=(), since=None):
    """The store's stamps, hole for hole: a sample its own, a hole past
    TS_PAD in its cell; and the closed view: the samples alone."""
    ts = np.asarray(st.ts_block())
    cts, cv, cn = (np.asarray(a) for a in st.closed_arrays())
    for i in range(len(t)):
        if i in skip:
            continue
        there = ~(miss[i] | gone[i])
        if since is not None:
            there &= t[i] >= since
        row = ts[i, :st.n_host[i]]
        assert row[row < TS_PAD].tolist() == t[i][there].tolist(), i
        assert st.holes_host[i] == (row >= TS_PAD).sum(), i
        assert st.samples_host[i] == cn[i] == there.sum(), i
        assert cts[i, :cn[i]].tolist() == t[i][there].tolist(), i
        assert cv[i, :cn[i]].tolist() == v[i][there].tolist(), i
        assert (cts[i, cn[i]:] == TS_PAD).all()


def test_a_marker_and_a_skipped_cell_both_leave_a_hole():
    t, v, miss, gone = small_stream()
    st = feed(SeriesStore(S8, C32), t, v, miss, gone, range(K20))
    assert st.stamp_form == "line"
    assert st.demoted == {"residual": 0, "gap": 1, "interval": 0}
    assert st.off_line.tolist() == [i == 5 for i in range(S8)]
    assert st.n_host[:5].tolist() == [K20] * 5       # cells used, not samples
    assert st.holes_host.tolist() == [0, 1, 3, 1, 2, 0, 0, 0]
    assert st.hole_cells == 7 and st.stats.stale_markers == 5
    res = np.asarray(st.res)
    assert res[1, 5] == res[4, 6] == res[4, 7] == res[3, 19] == RES_HOLE
    assert (res[2, 7:10] == RES_HOLE).all() and res[2, 10] != RES_HOLE
    assert not res[:, K20:].any()                    # n says where a row ends
    check_rows(st, t, v, miss, gone, skip=(5,))
    # a marker's stamp rides along, past TS_PAD; a skipped cell's is its line's
    ts = np.asarray(st.ts_block())
    assert ts[1, 5] == TS_PAD + t[1, 5] and ts[4, 6] == TS_PAD + t[4, 6]
    # the demoted row holds its samples in order, the gap closed
    assert ts[5, :st.n_host[5]].tolist() == t[5][~gone[5]].tolist()
    assert st.line_info().holes and st.line_info().minority.tolist() == [5]


def test_a_marker_before_any_sample_and_on_a_demoted_row():
    st = SeriesStore(S8, C32)
    st.append(np.array([0]), np.array([BASE]), np.array([STALE_NAN]))
    assert st.n_host[0] == 0 and st.stats.stale_markers == 1
    t, v, miss, gone = small_stream()
    miss[5, 12] = True                              # after its demotion
    feed(st, t, v, miss, gone, range(K20))
    ts = np.asarray(st.ts_block())
    at = 12 - (HOLE_RUN_MAX + 1)
    assert ts[5, at] == TS_PAD + t[5, 12] and st.holes_host[5] == 1
    assert st._pool_ts[st._pool_slot[5], at] == TS_PAD + t[5, 12]


def test_the_first_hole_on_a_grid_store_turns_the_form():
    t = BASE + np.arange(K20)[None] * IV + np.zeros((S8, 1), np.int64)
    v = np.ones((S8, K20))
    st = SeriesStore(S8, C32)
    for k in range(6):
        st.append(np.arange(S8), t[:, k], v[:, k])
    assert st.stamp_form == "grid" and st.grid_ok and st.ts is not None
    vals = v[:, 6].copy()
    vals[3] = STALE_NAN
    st.append(np.arange(S8), t[:, 6], vals)
    assert st.stamp_form == "line" and st.ts is None and st.hole_cells == 1
    assert np.asarray(st.res)[3, :8].tolist() == [0] * 6 + [RES_HOLE, 0]
    # ... and so does a skipped cell
    st2 = SeriesStore(S8, C32)
    for k in (0, 1, 2, 4):
        rows = np.arange(S8) if k != 4 else np.arange(S8)
        st2.append(rows, t[:, k], v[:, k])
    assert st2.stamp_form == "line" and st2.hole_cells == S8
    assert not any(st2.demoted.values())
    # any other NaN is a value, not a marker
    st3 = SeriesStore(S8, C32)
    for k in range(3):
        st3.append(np.arange(S8), t[:, k], np.full(S8, np.nan))
    assert st3.stamp_form == "grid" and st3.stats.stale_markers == 0


def test_a_layout_store_keeps_todays_behaviour_on_a_hole():
    st = SeriesStore(8, 16, nbuckets=4)
    for k in (0, 1, 2, 4):
        st.append(np.arange(8), np.full(8, BASE + k * IV, np.int64),
                  np.ones((8, 4)) * k)
    assert st.stamp_form == "grid" and not st.grid_ok and st.hole_cells == 0
    assert st.n_host[:8].tolist() == [4] * 8


def test_compaction_eviction_and_a_new_row_carry_holes():
    t, v, miss, gone = small_stream()
    st = feed(SeriesStore(S8, C32), t, v, miss, gone, range(K20))
    cut = int(t[:, 8].max()) + 1        # row 2's next sample is scrape 10
    st.compact(cut)
    check_rows(st, t, v, miss, gone, skip=(5,), since=cut)
    assert st.n_host[2] == K20 - 10 and st.holes_host[2] == 0
    assert st.holes_host[3] == 1 and st.hole_cells == 1
    assert st.tail_holes.tolist() == [0, 0, 0, 1, 0, 0, 0, 0]
    assert np.asarray(st.ts_block())[3, st.n_host[3] - 1] == TS_PAD + t[3, 19]
    st.free_rows(np.array([3]))
    assert st.hole_cells == 0 and not st.line_info().holes
    # the freed slot starts a new row, whose skipped cell is a hole again
    st.append(np.array([3, 3]), np.array([cut + 50_000, cut + 50_000 + 2 * IV]),
              np.array([1.0, 2.0]))
    assert st.n_host[3] == 3 and st.holes_host[3] == 1 and st.hole_cells == 1
    assert np.asarray(st.res)[3, :3].tolist() == [0, RES_HOLE, 0]


# -- the bound is on the RUN: markers and skipped cells together ---------------

# m: a marker came; g: nothing came (the cell is skipped). Runs of three
# stay on the line; one more hole of either kind, in any order, demotes
RUN_PATTERNS = ("mmm", "mgg", "ggm", "gmg", "mmmm", "mggg", "mmgg", "ggmm",
                "gmmg", "mmmmm", "ggggg")
RUN_S, RUN_C, RUN_K, RUN_AT = 64, 128, 60, 40


def run_stream(pattern, ends=False):
    """64 series on their own phases, a quarter of the scrapes LATE — the
    first of series 2 and 9 too, so that a marker of theirs, which comes
    on schedule, lies before its cell's line stamp; series 2 misses the scrapes of ``pattern`` from RUN_AT on,
    series 9 the same as the LAST scrapes it ever sends (``ends``)."""
    rng = np.random.default_rng(11)
    late = rng.integers(0, 64, (RUN_S, RUN_K)) \
        * (rng.random((RUN_S, RUN_K)) < 1 / 4)
    late[2, 0], late[9, 0] = 37, 21
    sched = BASE + rng.integers(0, IV, RUN_S)[:, None] \
        + np.arange(RUN_K)[None] * IV
    v = np.cumsum(rng.integers(1, 50, (RUN_S, RUN_K)), 1).astype(np.float64)
    miss = np.zeros((RUN_S, RUN_K), bool)
    gone = np.zeros((RUN_S, RUN_K), bool)
    for j, c in enumerate(pattern):
        (miss if c == "m" else gone)[2, RUN_AT + j] = True
        if ends:
            (miss if c == "m" else gone)[9, RUN_K - len(pattern) + j] = True
    return np.where(miss, sched, sched + late), v, miss, gone


def run_engine(t, v, miss, gone, batches):
    ms, shard, eng = mk_engine(RUN_S, RUN_C)
    groups = [range(RUN_K)] if batches == "one batch" \
        else [[k] for k in range(RUN_K)]
    for cols in groups:
        b = RecordBuilder(GAUGE)
        for k in cols:
            for i in np.flatnonzero(~gone[:, k]):
                b.add({"_metric_": "m", "host": f"h{i}", "g": f"g{i % 4}"},
                      int(t[i, k]),
                      STALE_NAN if miss[i, k] else float(v[i, k]))
        shard.ingest(b.build())
        shard.flush()
    return shard.store, eng


def check_served(eng, t, v, miss, gone, fused):
    """The served answers against the reference given the same holes: the
    range functions a series (general path), the fused aggregates (the
    demoted row through the minority correction), the instant selector."""
    there = ~(miss | gone)
    start, end, step = BASE + 330_007, BASE + 590_007, 10_000
    out_ts = np.arange(start, end + 1, step)

    def by_host(q):
        r = eng.query_range(q, start, end, step)
        return r, {k.as_dict()["host"]: np.asarray(r.matrix.values)[j]
                   for j, k in enumerate(r.matrix.keys)}
    for fn in ("rate", "count_over_time", "delta"):
        r, got = by_host(f"{fn}(m[1m])")
        assert "fused" not in r.exec_path
        for i in range(RUN_S):
            want = eval_range_fn(fn, t[i][there[i]], v[i][there[i]], out_ts,
                                 60_000)
            assert err(got[f"h{i}"][None], want[None]) < 1.0, (fn, i)
    for q, fn, agg in (("sum by (g)(rate(m[5m]))", "rate", "sum"),
                       ("sum by (g)(count_over_time(m[5m]))",
                        "count_over_time", "sum"),
                       ("avg by (g)(delta(m[5m]))", "delta", "avg")):
        r = eng.query_range(q, start, end, step)
        assert r.exec_path.startswith("local-fused[") == fused, r.exec_path
        x = np.array([eval_range_fn(fn, t[i][there[i]], v[i][there[i]],
                                    out_ts, WINDOW) for i in range(RUN_S)])
        want = aggregate(agg, x, np.arange(RUN_S) % 4, 4)
        vals = np.asarray(r.matrix.values, np.float64)
        for j, k in enumerate(r.matrix.keys):
            g = int(k.as_dict()["g"][1:])
            assert err(vals[j][None], want[g][None]) < 1.0, (q, g)
    for q, of in (("m", lambda i, k: v[i, k]),
                  ("timestamp(m)", lambda i, k: t[i, k] / 1000.0)):
        _r, got = by_host(q)
        for i in range(RUN_S):
            held = instant(t, miss, gone, i, out_ts)
            want = [of(i, k) if k >= 0 else np.nan for k in held]
            np.testing.assert_array_equal(got[f"h{i}"], want,
                                          err_msg=f"{q} {i}")


@pytest.mark.parametrize("batches", ("a batch a scrape", "one batch"))
@pytest.mark.parametrize("pattern", RUN_PATTERNS)
def test_the_bound_is_on_the_run_of_holes(pattern, batches, monkeypatch):
    """Four markers, a marker and three skipped cells, two and two: each a
    run of four holes, which the kernel's two shifts do not reach over, so
    each demotes its row (``reason="gap"``) whatever made the holes and
    however they came (a batch each, where the run is counted across
    batches, or one batch) — and the served answer is the reference's
    either way."""
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", 8)
    t, v, miss, gone = run_stream(pattern)
    st, eng = run_engine(t, v, miss, gone, batches)
    long = len(pattern) > HOLE_RUN_MAX
    assert st.stamp_form == "line"
    assert st.demoted == {"residual": 0, "gap": int(long), "interval": 0}
    assert int(st.off_line.sum()) == int(long)
    if not long:
        assert st.hole_cells == len(pattern) == st.holes_host.max()
        assert not st.tail_holes.any()
    check_served(eng, t, v, miss, gone, fused=True)


@pytest.mark.parametrize("pattern", ("mmm", "mmmm", "gmmm", "mggg"))
def test_a_row_that_ends_in_a_run_of_holes(pattern, monkeypatch):
    """The run a row ENDS in is held to the bound as it grows, marker by
    marker, with no sample after it to close it; skipped cells at a row's
    end are no cells at all."""
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", 8)
    t, v, miss, gone = run_stream(pattern, ends=True)
    gone[2] = miss[2] = False
    st, eng = run_engine(t, v, miss, gone, "a batch a scrape")
    markers = len(pattern.rstrip("g")) - pattern.rstrip("g").count("g")
    run = len(pattern.rstrip("g"))
    assert st.demoted["gap"] == int(run > HOLE_RUN_MAX)
    if run <= HOLE_RUN_MAX:
        assert sorted(st.tail_holes)[-2:] == [0, run]
        assert st.hole_cells == run and st.stats.stale_markers == markers
    check_served(eng, t, v, miss, gone, fused=True)


def test_a_marker_keeps_its_own_stamp():
    """A marker comes on schedule, its row's line may have started late:
    the step between the marker's stamp and its cell's line stamp has no
    sample already (Prometheus's rule goes by the marker's stamp)."""
    t, v, miss, gone = run_stream("m")
    i = 2
    line0 = t[i, 0]
    sched = t[i, RUN_AT]
    back = int(line0 + RUN_AT * IV - sched)
    assert 0 < back < 64, "the stream has row 2's line start late"
    st, eng = run_engine(t, v, miss, gone, "one batch")
    pid = int(np.flatnonzero(st.holes_host)[0])
    assert np.asarray(st.val)[pid, RUN_AT] == -back
    assert np.asarray(st.res)[pid, RUN_AT] == RES_HOLE
    out_ts = np.array([sched - 1, sched, sched + back - 1, sched + back])
    got = {}
    for x in out_ts:
        r = eng.query_range('m{host="h2"}', int(x), int(x), 1_000)
        vals = np.asarray(r.matrix.values)
        got[int(x)] = float(vals[0, 0]) if vals.size else np.nan
    want = [v[i, RUN_AT - 1], np.nan, np.nan, np.nan]
    np.testing.assert_array_equal([got[int(x)] for x in out_ts], want)
    assert instant(t, miss, gone, i, out_ts) == [RUN_AT - 1, -1, -1, -1]


def labels(i):
    return {"_metric_": "m", "host": f"h{i}", "g": f"g{i % 2}"}


def container(t, v, miss, gone, cols):
    b = RecordBuilder(GAUGE)
    for k in cols:
        for i in np.flatnonzero(~gone[:, k]):
            b.add(labels(i), int(t[i, k]),
                  STALE_NAN if miss[i, k] else float(v[i, k]))
    return b.build()


def test_recovery_keeps_holes(tmp_path):
    """Markers are rows of the log: a node that recovers from the sink and
    the bus holds the same holes."""
    from filodb_tpu.core.store import FileColumnStore
    from filodb_tpu.ingest.bus import FileBus
    t, v, miss, gone = small_stream()
    gone[5] = False                                  # no demotion here
    cfg = StoreConfig(max_series_per_shard=S8, samples_per_series=C32,
                      flush_batch_size=10**9, groups_per_shard=2)
    bus = FileBus(str(tmp_path / "bus.log"))
    sink = FileColumnStore(str(tmp_path / "chunks"))
    ms1 = TimeSeriesMemStore()
    sh1 = ms1.setup("prometheus", GAUGE, 0, cfg, sink=sink)
    for lo in range(0, K20, 5):
        c = container(t, v, miss, gone, range(lo, lo + 5))
        sh1.ingest(c, bus.publish(c))
        if lo == 5:
            sh1.flush_all_groups()                   # durable through here
    sh1.flush()
    ms2 = TimeSeriesMemStore()
    sh2 = ms2.setup("prometheus", GAUGE, 0, cfg, sink=sink)
    assert sh2.recover(bus, ms2.schemas) > 0
    sh2.flush()
    a, b = sh1.store, sh2.store
    assert b.stamp_form == "line" and b.hole_cells == a.hole_cells == 7
    # rows may sit in other slots: compare by their first stamp
    ta, tb = np.asarray(a.ts_block()), np.asarray(b.ts_block())
    rows_b = {int(tb[i, 0]): i for i in range(S8) if b.n_host[i]}
    for i in range(S8):
        j = rows_b[int(ta[i, 0])]
        assert ta[i].tolist() == tb[j].tolist()
    r1 = QueryEngine(ms1, "prometheus").query_range(
        "sum(count_over_time(m[2m]))", BASE + 150_000, BASE + 190_000, 10_000)
    r2 = QueryEngine(ms2, "prometheus").query_range(
        "sum(count_over_time(m[2m]))", BASE + 150_000, BASE + 190_000, 10_000)
    assert np.asarray(r1.matrix.values).tolist() \
        == np.asarray(r2.matrix.values).tolist()


# -- the engine and the served path -------------------------------------------

def mk_engine(rows, cap):
    ms = TimeSeriesMemStore()
    shard = ms.setup("prometheus", GAUGE, 0, StoreConfig(
        max_series_per_shard=rows, samples_per_series=cap,
        flush_batch_size=10**9, groups_per_shard=4))
    return ms, shard, QueryEngine(ms, "prometheus")


def instant(t, miss, gone, i, out_ts, stale_ms=300_000):
    """The scrape an instant selector holds of series ``i`` at each step,
    Prometheus's rule: the newest ROW that came at or before it, by the
    row's own stamp, inside the lookback; -1 where that row is a
    staleness marker or there is none. A scrape that sent nothing
    (``gone``) is no row: the sample before it is served on."""
    came = np.flatnonzero(~gone[i])
    held = []
    for x in out_ts:
        k = came[t[i, came] <= x]
        k = int(k[-1]) if len(k) else -1
        held.append(k if k >= 0 and not miss[i, k]
                    and x - t[i, k] <= stale_ms else -1)
    return held


def test_the_general_path_reads_around_holes(monkeypatch):
    """Narrow selections (gathered rows): range functions over the samples
    that exist, and the instant selector ABSENT at a step whose newest
    row is a marker — while a cell the row skipped without one serves the
    sample before it, as Prometheus does inside the lookback."""
    t, v, miss, gone = small_stream()
    ms, shard, eng = mk_engine(S8, C32)
    shard.ingest(container(t, v, miss, gone, range(K20)))
    shard.flush()
    assert shard.store.hole_cells == 7
    out_ts = np.arange(BASE + 30_000, BASE + 199_001, 5_000)
    for fn in ("rate", "sum_over_time", "count_over_time", "max_over_time",
               "changes"):
        r = eng.query_range(f"{fn}(m[1m])", int(out_ts[0]), int(out_ts[-1]),
                            5_000)
        assert "fused" not in r.exec_path
        got = {k.as_dict()["host"]: np.asarray(r.matrix.values)[j]
               for j, k in enumerate(r.matrix.keys)}
        for i in range(S8):
            there = ~(miss[i] | gone[i])
            want = eval_range_fn(fn, t[i][there], v[i][there], out_ts, 60_000)
            assert err(got[f"h{i}"][None], want[None]) < 1.0, (fn, i)
    for q, of in (("m", lambda i, k: v[i, k]),
                  ("timestamp(m)", lambda i, k: t[i, k] / 1000.0)):
        r = eng.query_range(q, int(out_ts[0]), int(out_ts[-1]), 5_000)
        got = {k.as_dict()["host"]: np.asarray(r.matrix.values)[j]
               for j, k in enumerate(r.matrix.keys)}
        for i in range(S8):
            held = instant(t, miss, gone, i, out_ts)
            want = [of(i, k) if k >= 0 else np.nan for k in held]
            np.testing.assert_array_equal(got[f"h{i}"], want, err_msg=f"{q} {i}")
    # row 1 (a marker) is absent at some step; row 4 (skipped cells) holds
    # scrape 5 through the two it skipped, on its line; and so does row 5,
    # off its line
    assert -1 in instant(t, miss, gone, 1, out_ts)
    for i in (4, 5):
        held = instant(t, miss, gone, i, out_ts)
        assert -1 not in held and max(np.bincount(held)) > 3


@pytest.mark.parametrize("mode", ("pallas", "xla"))
def test_the_engine_answers_a_store_with_holes_on_the_fused_path(mode,
                                                                 monkeypatch):
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", 8)
    size = "1024x128"
    S, C, K, _ = SIZES[size]
    t, v, miss, gone, rows, gids, steps = the_stream(size)
    R = len(t)
    ms, shard, eng = mk_engine(R, C)
    b = RecordBuilder(GAUGE)
    for k in range(K):
        for i in np.flatnonzero(~gone[:, k]):
            b.add({"_metric_": "m", "host": f"h{i}", "g": f"g{i % 4}"},
                  int(t[i, k]), STALE_NAN if miss[i, k] else float(v[i, k]))
    shard.ingest(b.build())
    tracer.drain()
    shard.flush()
    st = shard.store
    assert st.stamp_form == "line" and not any(st.demoted.values())
    assert st.hole_cells == int((miss | gone).sum()) - trailing(gone)
    start, end, step = BASE + 400_007, BASE + 940_007, 9_000
    out_ts = np.arange(start, end + 1, step)
    there = ~(miss | gone)
    with fused_mode(mode):
        for q, fn, agg, by in (
                ("sum(rate(m[5m]))", "rate", "sum", False),
                ("avg by (g)(avg_over_time(m[5m]))", "avg_over_time", "avg",
                 True),
                ("stddev(sum_over_time(m[5m]))", "sum_over_time", "stddev",
                 False),
                ("sum by (g)(count_over_time(m[5m]))", "count_over_time",
                 "sum", True)):
            r = eng.query_range(q, start, end, step)
            assert r.exec_path == f"local-fused[{fusedgrid.kernel_tag(mode)}]"
            x = np.array([eval_range_fn(fn, t[i][there[i]], v[i][there[i]],
                                        out_ts, WINDOW) for i in range(R)])
            g = np.arange(R) % 4 if by else np.zeros(R, int)
            want = aggregate(agg, x, g, 4 if by else 1)
            vals = np.asarray(r.matrix.values, np.float64)
            got = {k.as_dict().get("g", ""): vals[i]
                   for i, k in enumerate(r.matrix.keys)}
            for j in range(4 if by else 1):
                assert err(got[f"g{j}" if by else ""][None], want[j][None]) < 1.0
    spans = tracer.drain()
    kernels = [s.tags for s in spans if s.name == SPAN_QUERY_KERNEL
               and s.tags.get("phase") == "dispatch"]
    assert len(kernels) == 4 and all(
        (s["stamps"], s["holes"]) == ("line", 1) for s in kernels)
    selects = [s.tags for s in spans if s.name == SPAN_QUERY_SELECT]
    assert selects and all(
        (s["hole_cells"], s["used_cells"], s["demoted"])
        == (st.hole_cells, int(st.n_host.sum()), 0) for s in selects)
    flushes = [s.tags for s in spans if s.name == SPAN_INGEST_FLUSH]
    assert sum(s["holes"] for s in flushes) == st.hole_cells


def trailing(gone):
    """Cells a row never reached (it had ended): no holes, no cells."""
    K = gone.shape[1]
    ended = np.array([K - (np.flatnonzero(~g)[-1] + 1) for g in gone])
    return int(ended.sum())


def test_the_served_path_with_missed_scrapes(monkeypatch):
    """HTTP: a fused aggregate over holes, ``timestamp()``, the absent
    step as a count, /metrics."""
    from filodb_tpu.config import Config
    from filodb_tpu.standalone import FiloServer
    monkeypatch.setattr(qexec, "GATHER_THRESHOLD", 8)
    S, K = 64, 60
    rng = np.random.default_rng(5)
    t = BASE + rng.integers(0, IV, S)[:, None] + np.arange(K)[None] * IV
    v = np.cumsum(rng.integers(1, 50, (S, K)), 1).astype(np.float64)
    miss = rng.random((S, K)) < 1 / 16
    for k in range(HOLE_RUN_MAX, K):
        miss[:, k] &= ~miss[:, k - HOLE_RUN_MAX:k].all(axis=1)
    miss[:, 0] = False
    miss[7, 40], miss[7, 39], miss[7, 41] = True, False, False
    gone = np.zeros((S, K), bool)
    srv = FiloServer(Config({
        "num_shards": 1, "http": {"port": 0},
        "store": {"max_series_per_shard": S, "samples_per_series": 128,
                  "flush_batch_size": 10**9}})).start()

    def get(path, **params):
        url = f"http://127.0.0.1:{srv.http.port}{path}"
        if params:
            url += "?" + urllib.parse.urlencode(params)
        with urllib.request.urlopen(url, timeout=120) as r:
            return r.read().decode()

    try:
        shard = srv.memstore.shards_of("prometheus")[0]
        b = RecordBuilder(GAUGE)
        for k in range(K):
            for i in range(S):
                b.add({"_metric_": "m", "host": f"h{i}", "g": f"g{i % 4}",
                       "rack": f"r{i // 4}"}, int(t[i, k]),
                      STALE_NAN if miss[i, k] else float(v[i, k]))
        shard.ingest(b.build())
        shard.flush()
        api = "/promql/prometheus/api/v1/query_range"
        rate = json.loads(get(api, query="sum by (g)(rate(m[5m]))",
                              start=BASE / 1000 + 330.007,
                              end=BASE / 1000 + 580.007, step=10))
        # row 7's scrape 40 failed: at the steps between its stamp and
        # scrape 41's the rack counts three, and h7 has no stamp
        lo, hi = int(t[7, 40]) + 1, int(t[7, 41]) - 1
        steps = dict(start=(lo - 10_000) / 1000, end=(hi + 10_000) / 1000,
                     step=10)
        count = json.loads(get(api, query='count by (rack)(m{rack="r1"})',
                               **steps))
        stamp = json.loads(get(api, query='timestamp(m{rack="r1"})', **steps))
        text = get("/metrics")
    finally:
        srv.shutdown()
    assert rate["stats"]["exec_path"].startswith("local-fused[")
    out_ts = np.arange(BASE + 330_007, BASE + 580_008, 10_000)
    x = np.array([eval_range_fn("rate", t[i][~miss[i]], v[i][~miss[i]],
                                out_ts, WINDOW) for i in range(S)])
    want = aggregate("sum", x, np.arange(S) % 4, 4)
    for s in rate["data"]["result"]:
        got = np.array([float(y) for _, y in s["values"]])
        assert err(got[None], want[int(s["metric"]["g"][1:])][None]) < 1.0
    out_ts = np.arange(lo - 10_000, hi + 10_001, 10_000)
    held = {i: instant(t, miss, gone, i, out_ts) for i in range(4, 8)}
    (series,) = count["data"]["result"]
    got = {int(round(float(x) * 1000)): float(y) for x, y in series["values"]}
    assert [got[int(x)] for x in out_ts] == [
        sum(held[i][j] >= 0 for i in held) for j in range(len(out_ts))]
    assert 3.0 in got.values() and held[7].count(-1) >= 1
    for s in stamp["data"]["result"]:
        i = int(s["metric"]["host"][1:])
        got = {int(round(float(x) * 1000)): float(y) for x, y in s["values"]}
        for j, x in enumerate(out_ts):
            if held[i][j] < 0:
                assert int(x) not in got
            else:
                assert got[int(x)] == t[i, held[i][j]] / 1000.0
    lines = text.splitlines()
    assert f'filodb_store_hole_cells{{shard="0"}} {float(miss.sum())}' in lines \
        or f'filodb_store_hole_cells{{shard="0"}} {int(miss.sum())}' in lines
    assert any(ln.startswith('filodb_ingest_stale_markers_total{shard="0"} ')
               and float(ln.split()[-1]) == miss.sum() for ln in lines)
    assert 'filodb_store_rows_demoted_total{reason="gap",shard="0"} 0' in lines


def test_a_run_past_the_bound_still_counts_as_a_gap_on_the_metrics_page():
    t, v, miss, gone = small_stream()
    st = feed(SeriesStore(S8, C32), t, v, miss, gone, range(K20))
    assert st.demoted["gap"] == 1
    assert chunkstore.DEMOTE_REASONS == ("residual", "gap", "interval")


def test_the_selection_memo_keeps_the_cells_with_the_selection():
    """``hole_cells`` / ``used_cells`` of a selection: one pass per state of
    the store, shared by every query until the next flush."""
    from filodb_tpu.core.selection import ShardSelection

    class Shard:
        _release_epoch = 0
        index = range(S8)

    t, v, miss, gone = small_stream()
    st = feed(SeriesStore(S8, C32), t, v, miss, gone, range(K20 - 1))
    whole = ShardSelection(Shard, np.arange(S8))
    part = ShardSelection(Shard, np.array([1, 2, 4]))
    assert whole.is_all and not part.is_all
    assert whole.cells(st) == (st.hole_cells, int(st.n_host.sum()))
    assert part.cells(st) == (6, 3 * (K20 - 1))
    kept = part._cells
    assert part.cells(st) == (6, 3 * (K20 - 1)) and part._cells is kept
    feed(st, t, v, miss, gone, [K20 - 1])
    assert part.cells(st) == (6, 3 * K20) and part._cells is not kept
    assert whole.cells(st) == (7, int(st.n_host.sum()))


def test_the_mesh_answers_stores_with_holes_through_their_closed_views():
    """The mesh programs have no line form: on line stores they take the
    general programs, which read every shard's ``closed_arrays()`` — the
    holes taken out. Eight shards, markers and skipped cells in each."""
    from filodb_tpu.parallel.distributed import make_mesh
    mesh = make_mesh()
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=16, samples_per_series=64,
                      flush_batch_size=10**9)
    devs = mesh.devices.ravel()
    for i, dev in enumerate(devs):
        ms.setup("prometheus", GAUGE, i, cfg, device=dev)
    rng = np.random.default_rng(3)
    S, K = 3 * len(devs), 50
    t = BASE + rng.integers(0, IV, S)[:, None] + np.arange(K)[None] * IV
    v = np.cumsum(rng.integers(1, 50, (S, K)), 1).astype(np.float64)
    miss = rng.random((S, K)) < 1 / 12
    for k in range(HOLE_RUN_MAX, K):
        miss[:, k] &= ~miss[:, k - HOLE_RUN_MAX:k].all(axis=1)
    miss[:, 0] = False
    gone = np.zeros((S, K), bool)
    gone[::5, 20:22] = True
    gone &= ~miss
    for i in range(S):
        b = RecordBuilder(GAUGE)
        for k in np.flatnonzero(~gone[i]):
            b.add({"_metric_": "m", "host": f"h{i}", "g": f"g{i % 4}"},
                  int(t[i, k]), STALE_NAN if miss[i, k] else float(v[i, k]))
        ms.ingest("prometheus", i % len(devs), b.build())
    ms.flush_all()
    stores = [sh.store for sh in ms.shards_of("prometheus")]
    assert all(st.stamp_form == "line" and st.hole_cells for st in stores)
    assert sum(st.hole_cells for st in stores) == (miss | gone).sum()
    eng = QueryEngine(ms, "prometheus", mesh=mesh)
    start, end, step = BASE + 200_000, BASE + 480_000, 20_000
    out_ts = np.arange(start, end + 1, step)
    there = ~(miss | gone)
    for q, fn, agg in (("sum by (g)(rate(m[2m]))", "rate", "sum"),
                       ("sum by (g)(count_over_time(m[2m]))",
                        "count_over_time", "sum")):
        r = eng.query_range(q, start, end, step)
        assert r.exec_path.startswith("mesh["), r.exec_path
        x = np.array([eval_range_fn(fn, t[i][there[i]], v[i][there[i]],
                                    out_ts, 120_000) for i in range(S)])
        want = aggregate(agg, x, np.arange(S) % 4, 4)
        vals = np.asarray(r.matrix.values, np.float64)
        got = {k.as_dict()["g"]: vals[i] for i, k in enumerate(r.matrix.keys)}
        for j in range(4):
            assert err(got[f"g{j}"][None], want[j][None]) < 1.0, (q, j)
