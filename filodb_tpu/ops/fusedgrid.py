"""Fused single-pass grid kernel: rate + cross-series aggregation in one read.

The north-star query ``sum(rate(metric[5m]))`` over a grid-aligned shard is
HBM-bound: the value store ([S, C] f32, gigabytes) dwarfs every other operand.
The two-step path (ops/gridfns.py ``_grid_kernel`` then
ops/aggregators.partial_aggregate) costs ~2.3 passes over HBM because XLA
materializes the per-cell increments and the [S, T] rate matrix between the
elementwise stage and the band matmuls.

This Pallas kernel streams the store once: for each [Sb, C] row tile it
  1. computes counter-corrected increments in VMEM (relu of adjacent diffs —
     a reset cell contributes 0, ref RateFunctions.scala extrapolatedRate),
  2. runs BOTH band products on the MXU while the tile is resident
     (``inc @ band_open`` for window deltas, ``v @ onehot_lo`` for the raw
     first-sample values needed by the counter zero-clamp),
  3. applies the Prometheus extrapolation algebra elementwise [Sb, T],
  4. folds the tile straight into per-group partial state ([G, T] sum/count
     via a one-hot MXU matmul) accumulated across the sequential row grid —
     the [S, T] rate matrix never exists in HBM.

Partial-state layout matches ops.aggregators.partial_aggregate so results
combine across shards/batches with combine_partials / the mesh psum path.

Numerics are identical to the two-step f32 path: same masks, same band
operands, same extrapolation expressions, f32 accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.diagnostics import dispatching
from ..utils.metrics import FILODB_QUERY_FUSED_FALL_TILES, registry
from . import decodereg, gridfns

FUSED_FNS = {"rate", "increase", "delta"}
# window-aggregation shapes of the fused tier (ISSUE 9): the same one-pass
# select+decode+window+fold plan serves avg_over_time/sum_over_time-into-
# reduce dashboards — closed band instead of the open one, cnt >= 1 presence
FUSED_WINDOW_FNS = {"sum_over_time", "avg_over_time", "count_over_time"}
FUSED_OPS = {"sum", "avg", "count", "group", "stddev", "stdvar"}


def pallas_interpret() -> bool:
    """THE decision whether a Pallas kernel is compiled or interpreted:
    Mosaic compiles on a TPU backend, anything else (the CPU of the tests)
    runs the kernel body under ``interpret=True``. Every call site asks
    here, and :func:`kernel_tag` carries the answer into plan-cache keys
    and exec paths, so an interpreted kernel never passes for a compiled
    one in any output."""
    return jax.default_backend() != "tpu"


def kernel_tag(variant: str) -> str:
    """Name of the program a fused-tier backend ``variant`` ("pallas" |
    "xla") runs as HERE: "pallas" means compiled by Mosaic and nothing
    else; the interpreted kernel is "pallas-interpret"."""
    if variant == "pallas" and pallas_interpret():
        return "pallas-interpret"
    return variant


def _roundup(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# The line form's six edge cells a step, in the order of their one-hot slots
# in ``ohe``: the two cells below the sure range [lo, hi], the two above it
# (a row holds each or not, by its own stamps), then the sure range's first
# and last cell (whose residuals the durations need). The window functions
# read the first four only. A slot is a block of Tp lanes, ``ohe [Ca, 6
# Tp]`` — or, where the steps fit half a block (:func:`slots_per_block`),
# half of one: slots 2p and 2p + 1 share block p of ``ohe [Ca, 3 x 128]``,
# step t on lane t and on lane 64 + t (raw_hist_weights packs its halves
# so). ``ohe`` is int8 and holds PICKS only — every column one-hot or all
# zeros (:func:`pick_exact`); a band adds cells and rides in ``band``.
EDGE_SLOTS = 6
EDGE_SLOTS_WINDOW = 4
_NEVER = 1 << 30        # an edge bound no start + residual reaches


def slots_per_block(T: int) -> int:
    """Edge slots a 128-lane block of a line program over ``T`` steps: the
    MXU's passes are counted in blocks, and up to 64 steps use half of
    one."""
    return 2 if T <= 64 else 1


def line_spread(interval_ms: int) -> tuple[int, int]:
    """(dmin, dmax) of ``start + residual`` over a line store's majority
    rows (core/chunkstore.py ``line_info``): what ``grid_edges`` needs to
    say which cells every row holds in a window."""
    from ..core.chunkstore import RES_MAX
    return -RES_MAX, interval_ms + 2 * RES_MAX


# A row's start rides in the high bits of its sample count: one per-row
# operand (one [1, Sb] block a tile, one turn to a column in the kernel)
# instead of two, and a count needs 11 bits (C <= MAX_CAPACITY = 1024)
_COUNT_BITS = 11


def pack_start(n, start):
    """i32 [S]: ``n`` (0..1024) below, ``start`` (-1..2^20) above."""
    return n.astype(jnp.int32) | (start.astype(jnp.int32) << _COUNT_BITS)


def unpack_start(packed):
    return packed & ((1 << _COUNT_BITS) - 1), packed >> _COUNT_BITS


def line_fusable(window_ms: int, interval_ms: int) -> bool:
    """Can the line kernel answer? Its two edge cells a side must be apart
    (a window of at least three intervals), stamps must rise along a row
    whatever the residuals (an interval well above their width), and a
    start must fit beside the row's count (:func:`pack_start`)."""
    from ..core.chunkstore import RES_MAX
    return (interval_ms >= 8 * RES_MAX
            and window_ms >= 3 * interval_ms + 6 * RES_MAX
            and interval_ms + 2 * RES_MAX < 1 << (31 - _COUNT_BITS))


def dot_exact01(x, w, left: bool = False):
    """``x [M, K] f32 @ w [K, N]`` for a ``w`` of -1, 0 and 1 held in bf16,
    exact to f32: ``x`` splits into three bf16 pieces (8 mantissa bits
    each, the rest taken off in f32 without rounding), each piece times
    such a weight is exact and the MXU accumulates in f32. HIGHEST would
    split BOTH sides and run six passes; the three that multiply the
    weight's (zero) low pieces add nothing. Integers below 2^24 come out
    exact in any order. ``left``: the weight stands on the LEFT, ``w [M, K]
    @ x [K, N]`` — what :func:`group_fold` multiplies."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = x.astype(bf16)
    r = x - hi.astype(f32)
    mid = r.astype(bf16)
    lo = (r - mid.astype(f32)).astype(bf16)

    def dot(a):
        # DEFAULT, spelled out: one pass a piece (and the package-wide
        # "highest" would ask Mosaic for an fp32 contraction of bf16)
        return jnp.dot(*((w, a) if left else (a, w)),
                       precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=f32)
    return dot(hi) + dot(mid) + dot(lo)


def _dot_i8(x, w):
    """``x [M, K] int8 @ w [K, N] int8 -> int32``: ONE MXU pass at the int8
    rate, exact (DEFAULT spelled out, as :func:`dot_exact01` does)."""
    return jnp.dot(x, w, precision=jax.lax.Precision.DEFAULT,
                   preferred_element_type=jnp.int32)


def pick_exact(x, w):
    """``x [M, K] f32`` PICKED by ``w [K, N]`` int8, every column one-hot or
    all zeros: ``out[m, j] = x[m, k]`` where ``w[k, j]`` is the column's
    one 1. A pick copies and never adds, so it needs no floating point: the
    four BYTES of each f32 go through the MXU as int8, ``int8 x int8 ->
    int32`` (four passes at twice the bf16 rate, where the three-piece
    split of :func:`dot_exact01` runs three, and none of the split's vector
    work over ``[M, K]``). The tile is read as its own bytes — ``[4 M, K]``
    int8, row ``4 m + b`` byte ``b`` of row ``m``: a relabelling of the
    registers it lies in, no operation — one product picks all four, and
    the ``[4 M, N]`` int32 result narrowed to int8 IS the picked f32s,
    relabelled back. Exact for EVERY bit pattern — denormals, -0.0,
    infinities and NaN payloads, which the split loses. A byte travels as
    the SIGNED int8 of its bits and the narrowing keeps the low eight, so
    no offset has to be undone; a column of zeros (a padded step, a cell
    below 0 or at / above ``C``, the unused half of a packed block) sums
    nothing and reads +0.0 to the bit, as it did."""
    picked = _dot_i8(pltpu.bitcast(x, jnp.int8), w)
    return pltpu.bitcast(picked.astype(jnp.int8), jnp.float32)


def lane_major(x, Sb: int):
    """A per-row operand ``[S]`` as the fused program takes it: ``[S / Sb,
    1, Sb]``, tile i's rows along the lanes of block i. The reshape moves no
    byte; an ``[S, 1]`` column is tiled (8, 128) on the chip, 512 MB written
    and read back a query at 2^20 rows."""
    return x.reshape(-1, 1, Sb)


def _column(row):
    """A tile's ``[1, Sb]`` block of a per-row operand down the sublanes,
    ``[Sb, 1]``: ``Sb`` elements inside the tile, never ``S``. Spelled as
    a reshape on both backends: of the forms Mosaic takes (this, ``row.T``,
    a sublane broadcast to 8 or 128 rows and a 32-bit transpose) it is the
    cheapest on the v5e by 0.9-2.6 ms a query at 2^20 rows (PERF.md §6,
    PR 38)."""
    return row.reshape(row.shape[1], 1)


def group_fold(gid, G: int, contrib, okf, needs_sumsq: bool):
    """A tile's per-group partial state on the MXU: the one-hot of ``gid
    [1, Sb]`` built transposed, ``oh_t [G, Sb]`` (bf16; the row broadcast
    down the sublanes), times ``contrib`` / ``okf`` / the squares ``[Sb,
    Tp]`` -> ``(sum, count[, sumsq])``, each ``[G, Tp]``. The f32 sides go
    through :func:`dot_exact01`; ``okf`` is 0/1 itself and exact in ONE
    pass."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    grow = jax.lax.broadcasted_iota(jnp.int32, (G, gid.shape[1]), 0)
    oh_t = (grow == gid).astype(f32).astype(bf16)
    out = (dot_exact01(contrib, oh_t, left=True),
           jnp.dot(oh_t, okf.astype(bf16),
                   precision=jax.lax.Precision.DEFAULT,
                   preferred_element_type=f32))
    if needs_sumsq:
        out += (dot_exact01(contrib * contrib, oh_t, left=True),)
    return out


def counts_falls(fn: str, line) -> bool:
    """Does the program telescope its sure range's delta, and so return
    its fallen tiles beside its partial state (:func:`tile_fell`)? The
    rate family on a line store."""
    return bool(line) and fn in FUSED_FNS


def count_fall_tiles(falls, kernel: str, mode: str) -> int:
    """A fetched ``[1]`` count of the tiles that fell (``kernel``: "line",
    this module's rate programs on a line store; "hist", the raw hist
    tier's correction matmul), added to the registry and returned."""
    k = int(np.asarray(falls)[0])
    registry.counter(FILODB_QUERY_FUSED_FALL_TILES,
                     {"kernel": kernel, "mode": mode}).increment(k)
    return k


def sublane_max(a):
    """``[M, Ca]`` -> ``[8, Ca]``: the maximum over whole sublane tiles, all
    elementwise (M a multiple of 8)."""
    return functools.reduce(jnp.maximum,
                            [a[i:i + 8] for i in range(0, a.shape[0], 8)])


def tile_fell(drops, c0: int, first, last, ends):
    """Must a rate tile of the line form sum its increments cell by cell?
    One scalar. The sure range's delta is the difference of the range's
    last and first sample (Prometheus's own ``last - first``) unless,
    among the cells some window sums, a counter fell — ``drops [Sb, Ca]``
    is above 0 where a sample lies below the one before it (None: the
    function does not clip), and the cells that count are those from the
    least of ``first [1, Tp]`` to the most of ``last [1, Tp]`` over the
    steps whose range holds one, the tile's first column never (its
    neighbour is the roll's wrap) — or a row ends under a window (``ends
    [Sb, Tp]``: the picked last value is then not the row's last sample).
    ``drops`` comes of the MASKED values, plainly: it also reads above 0
    in the cell after a row's last sample, which is a row that ends under
    a window if a window sums that cell. A fall reported where no window
    sums it costs the tile the band product and no answer. A difference,
    an elementwise sublane maximum and two reduces: it rides beside the
    picks' matmul (fusedresident.raw_hist_drops)."""
    f32, i32 = jnp.float32, jnp.int32
    fell = jnp.max(jnp.where(ends, 1.0, 0.0)) > 0.0
    if drops is None:
        return fell
    some = last >= first
    c_lo = jnp.min(jnp.where(some, first, _NEVER))
    c_hi = jnp.max(jnp.where(some, last, -1))
    d8 = sublane_max(drops)
    col = jax.lax.broadcasted_iota(i32, d8.shape, 1) + c0
    used = (col >= c_lo) & (col <= c_hi) & (col > c0)
    return fell | (jnp.max(jnp.where(used, d8, f32(0.0))) > 0.0)


def fallen_fold(need, fold, zeros):
    """A line rate tile's partial state by the form the tile needs, for
    both backends: ``fold(form) -> (parts, fell)`` is the whole tile — its
    operands read, :func:`tile_contrib` in that form, :func:`group_fold` —
    and ``need`` says that the tile BEFORE this one fell. Then the tile
    runs in the band form at once; else telescoped, and again in the band
    form if it fell (the telescoped answer is dropped). Where counters
    fall tile after tile the program so costs what it cost before the
    delta was telescoped, the test beside it; where none falls, the
    telescoped tile alone, ONE basic block with no branch inside it (a
    branch between a tile's matmuls and its ``[Sb, Tp]`` algebra costs
    what telescoping saves: the two no longer overlap). Returns ``(parts,
    ran_band, fell)``: the state to add, whether the band form ran (what
    ``fall_tiles`` counts) and the next tile's ``need``."""
    def telescoped():
        parts, fell = fold("tel")
        return tuple(jnp.where(fell, 0.0, p) for p in parts), fell

    first, ran_band = jax.lax.cond(need, lambda: (zeros, jnp.bool_(True)),
                                   telescoped)
    second, fell = jax.lax.cond(ran_band, lambda: fold("band"),
                                lambda: (zeros, jnp.bool_(False)))
    return tuple(a + b for a, b in zip(first, second)), ran_band, fell


def _line_contrib(fn: str, window_ms: int, interval_ms: int, c0: int,
                  v, n, band, ohe, lo, hi, rel, roll, start, res, eb,
                  form: str = "band"):
    """:func:`tile_contrib` on a line store: window membership and the
    extrapolation's durations from each row's TRUE stamps, ``start[s] + c
    * interval + res[s, c]`` (relative to the selection's base).

    ``[lo, hi]`` are the cells EVERY row holds in the window
    (gridfns.grid_edges with the line's spread): the band matmul sums
    them, as on the grid. The two cells below ``lo`` and the two above
    ``hi`` are in the window for some rows and not for others: their
    values and residuals are picked by one-hot products (``ohe``, int8:
    the values byte by byte, :func:`pick_exact`; the residuals as the int8
    they are, one product) and each row decides them by comparing ``start
    + residual`` with the step's bound for that cell (``eb``, small
    integers: differences of stamps, never stamps). Stamps rise along a
    row, so the cells a row holds stay one contiguous run ``[f_idx,
    l_idx]``.

    ``ohe`` says by its width how its slots lie (see ``EDGE_SLOTS``): a
    block each, or two a block. A packed slot's plane comes out with the
    other half's numbers in lanes 64 on; no step lives there (``hi`` is -1
    and ``eb`` never met), so they are masked like any padded step.

    The rate family has two ``form``s of one tile and returns a third
    value, a scalar: whether the tile FELL (:func:`tile_fell`). "band" is
    the tile as it always was: the sure range's delta a band product over
    the increments. "tel" TELESCOPES it, ``v[hi] - v[max(lo, 0)]``, both
    picked anyway — no increment plane, no split of it, no band product,
    and nothing of a row that ends under the window (its last cell's
    residual is a reduce along the row) — and is right wherever the tile
    did not fall; where it did, the caller runs "band" over the same tile
    and drops this answer. The window functions have one form and return
    two values."""
    f32, i32 = jnp.float32, jnp.int32
    Sb, Ca = v.shape
    Tp = lo.shape[1]
    window = fn in FUSED_WINDOW_FNS
    per = EDGE_SLOTS * Tp // ohe.shape[1]         # edge slots a block
    lcol = jax.lax.broadcasted_iota(i32, (Sb, Ca), 1)
    col = lcol + c0
    valid = col < n
    v = jnp.where(valid, v, 0.0)

    # the blocks that hold the function's slots: values and residuals are
    # picked from the same
    slots = EDGE_SLOTS_WINDOW if window else EDGE_SLOTS
    w = ohe[:, :slots // per * Tp]
    # the residuals ARE int8: the tile goes to the MXU as the int8 it is,
    # i32 [Sb, slots/per Tp]; a cell at or past the row's count reads 0
    # (and every reader below asks has(), or hi <= n - 1, first)
    rp = _dot_i8(jnp.where(valid, res, jnp.int8(0)), w)

    def pick(x, j):       # slot j's plane, step t on lane t
        blk = x[:, j // per * Tp:(j // per + 1) * Tp]
        return roll(blk, Tp // 2) if j % per else blk

    # a2 < a1 < lo <= hi < b1 < b2; a cell the row does not have is out.
    # Each low cell is decided on its own: a row that ENDED in a2 (n == lo
    # - 1) has no a1 and may still hold a2 in the window. A row's cells
    # are a prefix, so above the sure range b2 needs b1. Stamps' differences
    # in integers: a start is under 2^20, a bound at most _NEVER
    def has(cell):
        return (cell >= 0) & (cell < n)

    m_a2 = has(lo - 2) & (start + pick(rp, 0) >= eb[0:1])
    m_a1 = has(lo - 1) & (start + pick(rp, 1) >= eb[1:2])
    m_b1 = has(hi + 1) & (start + pick(rp, 2) <= eb[2:3])
    m_b2 = m_b1 & (hi + 2 < n) & (start + pick(rp, 3) <= eb[3:4])

    f_sure = jnp.maximum(lo, 0)                               # [1, Tp]
    l_sure = jnp.minimum(hi, n - 1)                           # [Sb, Tp]
    f_idx = jnp.where(m_a2, lo - 2, jnp.where(m_a1, lo - 1, f_sure))
    l_idx = l_sure + m_b1.astype(i32) + m_b2.astype(i32)
    cnt = jnp.maximum(l_idx - f_idx + 1, 0)
    cnt_f = cnt.astype(f32)

    if window:
        ok = cnt >= 1
        if fn == "count_over_time":
            return jnp.where(ok, cnt_f, 0.0), ok.astype(f32)
        vp = pick_exact(v, w)
        s = dot_exact01(v, band)          # a band ADDS cells: three pieces
        for j, m in enumerate((m_a2, m_a1, m_b1, m_b2)):
            s = s + jnp.where(m, pick(vp, j), 0.0)
        if fn == "avg_over_time":
            s = s / cnt_f
        return jnp.where(ok, s, 0.0), ok.astype(f32)

    is_counter = fn != "delta"

    def step(x):              # one increment, counter-corrected like inc
        return jnp.maximum(x, 0.0) if is_counter else x

    vp = pick_exact(v, w)         # a lane no column feeds: 0.0, as it was
    v_a2, v_a1, v_b1, v_b2, v_lo, v_hi = (pick(vp, j) for j in range(6))
    prev = roll(v, 1)
    # after the picks' matmul, so that the test runs while the MXU does. A
    # row that ends under the window, in a cell the window may hold: its
    # v_hi is the masked 0, and its last stamp is its own cell's
    fell = tile_fell(prev - v if is_counter else None, c0, f_sure + 1, hi,
                     (n > jnp.maximum(lo - 2, 0)) & (n <= hi))
    if form == "tel":
        # a padded step has hi = -1, and an empty range would pick garbage
        delta = jnp.where(hi > f_sure, v_hi - v_lo, 0.0)
        r_end = 0             # no row of an unfallen tile ends under hi
    else:
        mask = valid & (col > 0)
        if c0:
            mask &= lcol > 0
        inc = jnp.where(mask, step(v - prev), 0.0)
        delta = dot_exact01(inc, band)                        # (lo, hi]
        # the residual of the row's own last cell, where it ends under the
        # window
        r_end = jnp.sum(jnp.where(col == n - 1, res.astype(i32), 0),
                        axis=1, keepdims=True)
    delta = (delta
             + jnp.where(m_a1 & (lo < n), step(v_lo - v_a1), 0.0)
             + jnp.where(m_a2 & m_a1, step(v_a1 - v_a2), 0.0)
             + jnp.where(m_b1 & (hi >= 0), step(v_b1 - v_hi), 0.0)
             + jnp.where(m_b2, step(v_b2 - v_b1), 0.0))
    f_v = jnp.where(m_a2, v_a2, jnp.where(m_a1, v_a1, v_lo))
    r_f = jnp.where(m_a2, pick(rp, 0), jnp.where(m_a1, pick(rp, 1),
                                                 pick(rp, 4)))
    r_l = jnp.where(m_b2, pick(rp, 3), jnp.where(
        m_b1, pick(rp, 2), jnp.where(hi <= n - 1, pick(rp, 5), r_end)))
    # stamps relative to the base, in integers until they are differences
    t_f = f_idx * interval_ms + start + r_f
    t_l = l_idx * interval_ms + start + r_l
    dur_start = (t_f - (rel - window_ms)).astype(f32) / 1000.0
    dur_end = (rel - t_l).astype(f32) / 1000.0
    sampled = (t_l - t_f).astype(f32) / 1000.0
    return _extrapolate(fn, window_ms, delta, f_v, dur_start, dur_end,
                        sampled, cnt, cnt_f) + (fell,)


def _hole_contrib(fn: str, window_ms: int, interval_ms: int, c0: int,
                  v, n, band, ohe, lo, hi, rel, roll, start, res, eb,
                  form: str = "band"):
    """:func:`_line_contrib` on a line store that has HOLES: a cell of a
    row's line may hold no sample (core/chunkstore.py, the text at
    ``RES_DTYPE``: its residual reads ``RES_HOLE``), in runs of up to
    ``HOLE_RUN_MAX``. A hole is no sample: the count is a sum of validity,
    an increment runs from a sample to the NEXT sample, and a window's
    first and last samples are the first and last that exist in it.

    Operands as :func:`_line_contrib` has them, but for the rate family's
    ``band``: the cells ``[lo, hi - 1]`` (an increment is laid in its
    EARLIER sample's cell, so the band sums the pairs that start in the
    sure range; the one that leaves it over a hole at ``hi`` is taken out
    again, by picks). The samples a window can hold beside the sure
    range's are, as there, the cells lo-2, lo-1, hi+1 and hi+2, each
    decided by its own stamp and now by whether it holds a sample; the
    first sample at or after ``lo`` and the last at or before ``hi`` are
    read from FILLED planes — a hole takes the next (the previous)
    sample's value, residual and distance, two shifts by one and two cells
    reaching over a run of three — picked at ``lo`` and ``hi``. Every pick
    is an int8 product: values byte by byte (:func:`pick_exact`),
    residuals and distances as the int8 they are; what ADDS cells stays
    bf16 against a 0/1 band: the pairs' increments in three passes,
    validity in one.

    The two ``form``s of :func:`_line_contrib`: "tel" takes the sure
    range's delta as its last sample's value less its first's (the two
    filled planes picked at ``hi`` and ``lo``), with no pair plane, no
    band product over it and nothing of a row's own last sample (two
    reduces along the row); the tile FELL where a pair that starts in
    some sure range fell, or a row's last sample lies beyond the fills'
    reach before some ``hi``."""
    from ..core.chunkstore import HOLE_RUN_MAX, RES_HOLE
    assert HOLE_RUN_MAX == 3, "two shifts reach over a run of three"
    f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16
    Sb, Ca = v.shape
    Tp = lo.shape[1]
    window = fn in FUSED_WINDOW_FNS
    per = EDGE_SLOTS * Tp // ohe.shape[1]         # edge slots a block
    packed = per == 2
    lcol = jax.lax.broadcasted_iota(i32, (Sb, Ca), 1)
    col = lcol + c0
    ri = res.astype(i32)
    valid = (col < n) & (ri != RES_HOLE)
    v = jnp.where(valid, v, 0.0)
    okf = valid.astype(f32).astype(bf16)

    def count(band):          # validity x band: a SUM, one bf16 pass
        return jnp.dot(okf, band, precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=f32)

    def pick(x, j):           # slot j's plane, step t on lane t
        blk = x[:, j // per * Tp:(j // per + 1) * Tp]
        return roll(blk, Tp // 2) if j % per else blk

    # the four edge cells' residuals as the int8 they are: RES_HOLE where
    # the cell has no sample, and for a cell at or past the row's count
    # (which fails holds() by its count too); i32 [Sb, 4/per Tp]
    w4 = ohe[:, :EDGE_SLOTS_WINDOW // per * Tp]
    rp = _dot_i8(jnp.where(col < n, res, jnp.int8(RES_HOLE)), w4)

    def holds(cell, j):       # the cell exists, is the row's, has a sample
        return (cell >= 0) & (cell < n) & (pick(rp, j) != RES_HOLE)

    m_a2 = holds(lo - 2, 0) & (start + pick(rp, 0) >= eb[0:1])
    m_a1 = holds(lo - 1, 1) & (start + pick(rp, 1) >= eb[1:2])
    m_b1 = holds(hi + 1, 2) & (start + pick(rp, 2) <= eb[2:3])
    m_b2 = holds(hi + 2, 3) & (start + pick(rp, 3) <= eb[3:4])
    edges = (m_a2.astype(f32) + m_a1.astype(f32) + m_b1.astype(f32)
             + m_b2.astype(f32))

    if window:
        # the closed band counts the sure range's samples
        cnt_f = count(band) + edges
        ok = cnt_f >= 1.0
        if fn == "count_over_time":
            return jnp.where(ok, cnt_f, 0.0), ok.astype(f32)
        vp = pick_exact(v, w4)
        s = dot_exact01(v, band)          # a band ADDS cells: three pieces
        for j, m in enumerate((m_a2, m_a1, m_b1, m_b2)):
            s = s + jnp.where(m, pick(vp, j), 0.0)
        if fn == "avg_over_time":
            s = s / cnt_f
        return jnp.where(ok, s, 0.0), ok.astype(f32)

    # filled planes. ``meta``: a sample's residual + 128 (1..255), 0 for
    # none, and 256 a cell of distance once filled from a neighbour
    shift = 8
    step1, step2 = 1 << shift, 2 << shift
    meta = jnp.where(valid, ri + 128, 0)

    def fill(m0, x0, back: bool):
        """(meta, values) with every hole taking its next (``back``) or
        previous sample's, up to three cells away; 0 where there is none.
        The shift's wrapped columns take nothing."""
        def shifted(x, k):
            if back:
                return roll(x, Ca - k), lcol < Ca - k
            return roll(x, k), lcol >= k
        out_m, out_x = m0, x0
        for k, d in ((1, step1), (2, step2)):
            sm, inside = shifted(out_m, k)
            sx, _ = shifted(out_x, k)
            take = (out_m == 0) & inside & (sm > 0)
            out_x = jnp.where(take, sx, out_x)
            out_m = jnp.where(take, sm + d, out_m)
        return out_m, out_x

    # the NEXT sample after each cell (strictly), then with the cell's own
    nxt_m0, inside = roll(meta, Ca - 1), lcol < Ca - 1
    nxt_m0 = jnp.where(inside, nxt_m0, 0)
    nxt_m, nxt_v = fill(nxt_m0, jnp.where(inside, roll(v, Ca - 1), 0.0), True)
    mb = jnp.where(valid, meta, jnp.where(nxt_m > 0, nxt_m + step1, 0))
    vb = jnp.where(valid, v, nxt_v)
    mf, vf = fill(meta, v, False)

    is_counter = fn != "delta"

    def step(x):              # one increment, counter-corrected like inc
        return jnp.maximum(x, 0.0) if is_counter else x

    mid_cnt = count(band)                                     # [lo, hi - 1]

    def parts(m):     # (distance in cells, residual: RES_HOLE for none)
        return ((m >> shift).astype(jnp.int8),
                ((m & (step1 - 1)) - 128).astype(jnp.int8))

    if packed:        # lo and hi share block 2: hi's half comes down
        blk_lo = blk_hi = ohe[:, 2 * Tp:3 * Tp]
        vbp = pick_exact(vb, ohe)

        def down(x):
            return roll(x, Tp // 2)
    else:
        blk_lo, blk_hi = ohe[:, 4 * Tp:5 * Tp], ohe[:, 5 * Tp:]
        vbp = pick_exact(vb, ohe[:, :5 * Tp])

        def down(x):
            return x
    # distances (0..3) and residuals of the filled planes at lo / hi: i32
    (kb, rb), (kf, rf) = parts(mb), parts(mf)
    kl, rl = _dot_i8(kb, blk_lo), _dot_i8(rb, blk_lo)
    kh, rhi = down(_dot_i8(kf, blk_hi)), down(_dot_i8(rf, blk_hi))
    v_hi = down(pick_exact(vf, blk_hi))
    v_a2, v_a1, v_b1, v_b2, v_lo = (pick(vbp, j) for j in range(5))

    f_sure = jnp.maximum(lo, 0)                               # [1, Tp]
    some = hi >= f_sure                 # the sure range holds a cell
    # hi itself holds a sample: the band [lo, hi - 1] leaves it out
    at_hi = some & (hi < n) & (rhi != RES_HOLE) & (kh == 0)
    cnt_f = mid_cnt + at_hi.astype(f32) + edges
    mid = mid_cnt + at_hi.astype(f32) >= 1.0
    cnt = cnt_f.astype(i32)

    reach = rhi != RES_HOLE             # a sample within three cells of hi
    nin = m_b1 | m_b2
    v_next = jnp.where(m_b1, v_b1, v_b2)
    pin = m_a1 | m_a2
    v_prev = jnp.where(m_a1, v_a1, v_a2)
    # after the picks' matmuls, so that the test runs while the MXU does. A
    # sample less its next one's value (0 for none: a row's last sample
    # reads as a fall, where a window sums the pair it would start); and a
    # row whose last sample the fill from hi does not reach, while the
    # window holds a sample of it that would be its last
    fell = tile_fell(v - nxt_v if is_counter else None, c0, f_sure, hi - 1,
                     ~reach & (mid | (pin & ~nin)))
    if form == "tel":
        delta_mid = jnp.where(mid, v_hi - v_lo, 0.0)
        last = r_end = 0      # no row of an unfallen tile ends out of reach
    else:
        # a pair's increment in its EARLIER sample's cell, cells [lo, hi -
        # 1]; the row's own last sample (the tile's: a window's cells lie
        # in it)
        g = jnp.where(valid & (nxt_m > 0), step(nxt_v - v), 0.0)
        last = jnp.max(jnp.where(valid, col, -1), axis=1, keepdims=True)
        r_end = jnp.sum(jnp.where(col == last, jnp.where(valid, ri, 0), 0),
                        axis=1, keepdims=True)
        # the pair that leaves the sure range's last sample H: inside the
        # band when hi is a hole (H < hi) and H has a next sample, and in
        # the window only if that sample is (b1, else b2: vb at hi + 1 is
        # its value)
        over = mid & (kh > 0) & reach & (hi < last)
        delta_mid = dot_exact01(g, band) - jnp.where(
            over, step(v_b1 - v_hi), 0.0)
    delta = (delta_mid
             + jnp.where(mid & nin, step(v_next - v_hi), 0.0)
             + jnp.where(mid & pin, step(v_lo - v_prev), 0.0)
             + jnp.where(m_a2 & m_a1, step(v_a1 - v_a2), 0.0)
             + jnp.where(m_b1 & m_b2, step(v_b2 - v_b1), 0.0)
             + jnp.where(~mid & pin & nin, step(v_next - v_prev), 0.0))
    f_v = jnp.where(m_a2, v_a2, jnp.where(m_a1, v_a1, v_lo))

    # stamps relative to the base, in integers until they are differences.
    # A row that ended under the window: its own last sample's cell and
    # residual, where the fill from hi does not reach it
    c_f = jnp.where(m_a2, lo - 2, jnp.where(m_a1, lo - 1,
                                            f_sure + kl))
    r_f = jnp.where(m_a2, pick(rp, 0), jnp.where(m_a1, pick(rp, 1), rl))
    c_l = jnp.where(m_b2, hi + 2, jnp.where(m_b1, hi + 1, jnp.where(
        reach, hi - kh, last)))
    r_l = jnp.where(m_b2, pick(rp, 3), jnp.where(m_b1, pick(rp, 2), jnp.where(
        reach, rhi, r_end)))
    t_f = c_f * interval_ms + start + r_f
    t_l = c_l * interval_ms + start + r_l
    dur_start = (t_f - (rel - window_ms)).astype(f32) / 1000.0
    dur_end = (rel - t_l).astype(f32) / 1000.0
    sampled = (t_l - t_f).astype(f32) / 1000.0
    return _extrapolate(fn, window_ms, delta, f_v, dur_start, dur_end,
                        sampled, cnt, cnt_f) + (fell,)


def _extrapolate(fn, window_ms, delta, f_v, dur_start, dur_end, sampled,
                 cnt, cnt_f):
    """Prometheus' extrapolatedRate from a window's delta, first value and
    three durations (seconds): ``(contrib, okf)`` of :func:`tile_contrib`."""
    f32 = jnp.float32
    avg_dur = sampled / (cnt_f - 1.0)
    if fn != "delta":
        safe = jnp.where(delta > 0, delta, 1.0)
        dur_zero = jnp.where(delta > 0, sampled * (f_v / safe), jnp.inf)
        dur_start = jnp.where((delta > 0) & (f_v >= 0) & (dur_zero < dur_start),
                              dur_zero, dur_start)
    thresh = avg_dur * 1.1
    extrap = sampled
    extrap = extrap + jnp.where(dur_start < thresh, dur_start, avg_dur / 2)
    extrap = extrap + jnp.where(dur_end < thresh, dur_end, avg_dur / 2)
    scaled = delta * (extrap / sampled)
    if fn == "rate":
        scaled = scaled * (1000.0 / window_ms)

    ok = cnt >= 2
    return jnp.where(ok, scaled, 0.0), ok.astype(f32)


def tile_contrib(fn: str, window_ms: int, interval_ms: int, c0: int,
                 v, n, band, ohlo, lo, hi, rel, roll, line=None,
                 holes: bool = False, form: str = "band", born=None):
    """Shared per-tile window math of the fused tier: decoded values
    ``v [Sb, Ca]`` -> ``(contrib [Sb, Tp]`` with absent cells zeroed,
    ``okf [Sb, Tp]`` presence as f32). ONE definition per tiling plan for
    BOTH backends: the Pallas kernel body reads its VMEM refs and calls
    this; the XLA-fused twin (ops/fusedresident.py) scans the same row
    tiles through it — variant parity is by construction, not discipline.
    ``roll(x, k)`` abstracts the backend's lane shift (pltpu.roll in-kernel,
    jnp.roll in the scan); the wrapped column's garbage is masked either
    way. ``band`` is the OPEN band for the rate family and the CLOSED band
    for the window-aggregation fns (host_operands builds the right one).
    ``band`` and ``ohlo`` are 0/1 in bf16 and every product with them is
    :func:`dot_exact01`'s: three MXU passes, exact, whatever the default
    matmul precision. ``line = (start, res, eb)`` is a line store's tile
    (see :func:`_line_contrib`; ``ohlo`` is then ``ohe``, int8, and its
    products :func:`pick_exact`'s); None is the grid, where column c IS
    cell c of every row. ``holes``: the line store
    has cells without a sample (:func:`_hole_contrib`). The rate family
    on a line store (:func:`counts_falls`) has two ``form``s of a tile,
    "tel" and "band", and returns a third value, a scalar: whether the
    tile fell (:func:`tile_fell`; :func:`fallen_fold` runs the two).
    ``born [Sb, 1]`` i32 is the grid program's BIRTHS mode (a store in
    time-aligned cells that holds a row born late, core/chunkstore.py):
    a row's samples are its cells ``born <= col < n``, its first cell in a
    window ``max(lo, born)``, the cell AT its birth has no increment, and
    where it is born inside a window the window's first value is the
    row's own first (one masked reduce along the row: ``ohlo`` picks a
    step's cell, not a row's). None is the program as it ever was."""
    if line is not None:
        contrib = _hole_contrib if holes else _line_contrib
        return contrib(fn, window_ms, interval_ms, c0, v, n, band,
                       ohlo, lo, hi, rel, roll, *line, form=form)
    f32 = jnp.float32
    Sb, Ca = v.shape
    lcol = jax.lax.broadcasted_iota(jnp.int32, (Sb, Ca), 1)
    col = lcol + c0                                           # global cell
    valid = col < n
    if born is not None:
        valid &= col >= born
    v = jnp.where(valid, v, 0.0)

    last_cell = n - 1                                         # [Sb, 1]
    f_idx = jnp.maximum(lo, 0)                                # [1, Tp]
    if born is not None:
        f_idx = jnp.maximum(f_idx, born)                      # [Sb, Tp]
    l_idx = jnp.minimum(hi, last_cell)                        # [Sb, Tp]
    cnt = jnp.maximum(l_idx - f_idx + 1, 0)
    cnt_f = cnt.astype(f32)

    if fn in FUSED_WINDOW_FNS:
        ok = cnt >= 1
        if fn == "count_over_time":
            return jnp.where(ok, cnt_f, 0.0), ok.astype(f32)
        s = dot_exact01(v, band)                              # closed band
        if fn == "avg_over_time":
            s = s / cnt_f
        return jnp.where(ok, s, 0.0), ok.astype(f32)

    is_counter = fn != "delta"
    # increments: valid cells are a prefix of each row, so cell c has a valid
    # predecessor exactly when c > 0 and c is valid; roll's column-0 wraparound
    # is masked out by that same condition. With a column offset the local
    # column 0 wraps to the slice's LAST column — its increment is garbage but
    # never consumed (band rows at/below the first window edge are zero);
    # zero it anyway so no value-dependent surprise can leak
    prev = roll(v, 1)
    raw = v - prev
    inc = jnp.maximum(raw, 0.0) if is_counter else raw
    mask = valid & (col > (0 if born is None else born))
    if c0:
        mask &= lcol > 0
    inc = jnp.where(mask, inc, 0.0)

    delta = dot_exact01(inc, band)                            # [Sb, Tp]
    f_v = dot_exact01(v, ohlo)
    if born is not None:
        # born inside the window: the row's own first value (its birth
        # cell lies among the tile's columns wherever ``cnt`` is not 0)
        own = jnp.sum(jnp.where(col == born, v, 0.0), axis=1, keepdims=True)
        f_v = jnp.where(born > lo, own, f_v)

    relf = rel.astype(f32)                                    # [1, Tp]
    f_rel = (f_idx * interval_ms).astype(f32)
    l_rel = (l_idx * interval_ms).astype(f32)
    dur_start = (f_rel - (relf - window_ms)) / 1000.0
    dur_end = (relf - l_rel) / 1000.0
    sampled = (l_rel - f_rel) / 1000.0
    return _extrapolate(fn, window_ms, delta, f_v, dur_start, dur_end,
                        sampled, cnt, cnt_f)


# back-compat alias: the quant16 decode now lives in the shared decode-
# variant registry (ops/decodereg.py) next to its delta/hist siblings
decode_narrow_tile = decodereg.decode_quant16


def _kernel_body(fn: str, needs_sumsq: bool, window_ms: int, interval_ms: int,
                 Sb: int, Ca: int, Tp: int, G: int, residency: str, c0: int,
                 line: int, holes: bool, births: bool, *refs):
    """``Ca`` is the streamed column width and ``c0`` its global offset into
    the store: a sub-range query streams (and matmuls) only its active
    columns (see active_columns); full-range queries have c0=0, Ca=C.
    ``residency`` names the decode variant (ops/decodereg.py) — the value
    block plus its per-row operands decode to f32 in VMEM per tile."""
    var = decodereg.variant(residency)
    R = var.row_operands
    val_ref = refs[0]
    rowrefs = refs[1:1 + R]
    rest = refs[1 + R:]
    # the per-row operands arrive lane-major, [1, Sb] (lane_major): the
    # tile math wants them down the sublanes, the fold takes gid as it is
    n_ref, gid_ref = rest[:2]
    if line:        # res [Sb, Ca] int8 ... eb [8, Tp] i32; start rides in n
        (res_ref, band_ref, ohlo_ref, lo_ref, hi_ref, rel_ref,
         eb_ref, *outs) = rest[2:]
    else:
        band_ref, ohlo_ref, lo_ref, hi_ref, rel_ref, *outs = rest[2:]
    falls = counts_falls(fn, line)
    # a line rate program's last output: the tiles that ran the band form,
    # [1] i32 in SMEM; and whether the tile before this one fell, a scratch
    *accs, falls_ref, need_ref = outs if falls else (*outs, None, None)

    i = pl.program_id(0)

    def tile(form="band"):
        """The tile, every ref read here: inside the branch that runs it."""
        n, on_line, born = _column(n_ref[:]), None, None      # [Sb, 1] i32
        if line:
            n, start = unpack_start(n)
            on_line = (start, res_ref[:], eb_ref[:])
        elif births:        # the birth cell rides where a line's start does
            n, born = unpack_start(n)
        # decode in VMEM: the registered pallas twin of the residency
        # variant
        v = var.pallas(val_ref[:], *(_column(r[:]) for r in rowrefs))
        # i32 shift: x64 mode would lower an i64 operand, which
        # tpu.dynamic_rotate rejects
        return tile_contrib(
            fn, window_ms, interval_ms, c0, v, n, band_ref[:], ohlo_ref[:],
            lo_ref[:], hi_ref[:], rel_ref[:],
            roll=lambda x, k: pltpu.roll(x, jnp.int32(k), 1), line=on_line,
            holes=holes, form=form, born=born)

    def fold(contrib, okf):
        # per-group fold on the MXU: [G, Sb] one-hot x [Sb, Tp]
        return group_fold(gid_ref[:], G, contrib, okf, needs_sumsq)

    def start_at_zero():
        @pl.when(i == 0)
        def _():
            for acc in accs:
                acc[:] = jnp.zeros_like(acc)
            if falls:
                falls_ref[0] = 0
                need_ref[0] = 0

    if falls:
        start_at_zero()

        def whole(form):
            contrib, okf, fell = tile(form)
            return fold(contrib, okf), fell

        parts, ran_band, fell = fallen_fold(
            need_ref[0] != 0, whole, tuple(jnp.zeros_like(a) for a in accs))
        falls_ref[0] += ran_band.astype(jnp.int32)
        need_ref[0] = fell.astype(jnp.int32)
    else:           # as it was: the tile, the first step's zeros, the fold
        contrib, okf = tile()
        start_at_zero()
        parts = fold(contrib, okf)
    for acc, part in zip(accs, parts):
        acc[:] += part


@functools.lru_cache(maxsize=64)
def build_pallas(fn: str, needs_sumsq: bool, window_ms: int, interval_ms: int,
                 S: int, Sb: int, C: int, Tp: int, G: int, interpret: bool,
                 residency: str = "raw", c0: int = 0, Ck: int = 0,
                 line: int = 0, holes: bool = False, births: bool = False):
    """The raw (traceable) fused-kernel pallas_call — also invoked inside
    ``shard_map`` by the mesh executor (parallel/distributed.py), where each
    shard runs this same map phase on its resident block and the partial
    state crosses the ICI collective (ref: AggrOverRangeVectors.scala:62 —
    the identical map phase runs on every data node). ``residency`` names
    the decode variant (ops/decodereg.py): the value operand is that
    variant's narrow block plus its per-row operands (quant16: vmin/scale;
    delta16/delta8: anchor), decoded to f32 in VMEM per tile. Every per-row
    operand — those, then ``n`` and ``gids`` — is lane-major, ``[S / Sb, 1,
    Sb]`` (:func:`lane_major`), one ``[1, Sb]`` block a grid step.

    ``(c0, Ca)`` describe the active column range (see active_columns): when
    it covers less than the full store, the kernel's value block starts at
    column ``c0`` and spans only ``Ca`` columns — HBM bytes and MXU MACs
    scale with the query's range, not the store's retention — and the band
    operands arrive pre-sliced to [Ca, Tp], 0/1 in bf16. full_columns
    variants (the delta cumsum telescopes from cell 0) require c0=0.
    ``line``: 0 on a grid store; where the store keeps stamps as line +
    residual, the edge slots a block of ``ohe`` (:func:`slots_per_block`) —
    the kernel then takes each row's start packed above its count
    (:func:`pack_start`), the residual block beside the values, ``ohe`` in
    place of ``ohlo`` and the edge bounds ``eb`` last
    (:func:`_line_contrib`). ``holes``: the line store has cells without a
    sample; the same operands, read by :func:`_hole_contrib`. A line
    program of the rate family (:func:`counts_falls`) returns one output
    more, last: the tiles that ran the band form (:func:`fallen_fold`),
    ``[1]`` i32 in SMEM. ``births``: the grid program's births mode
    (:func:`tile_contrib`): the same operands, each row's birth cell
    packed above its count as a line's start is."""
    var = decodereg.variant(residency)
    assert not var.full_columns or c0 == 0, (residency, c0)
    assert not line or residency == "raw", residency
    assert not (line and births)
    n_out = 3 if needs_sumsq else 2
    Ca = Ck if Ck else C
    out_shape = tuple(jax.ShapeDtypeStruct((G, Tp), jnp.float32)
                      for _ in range(n_out))
    out_specs = tuple(pl.BlockSpec((G, Tp), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM)
                      for _ in range(n_out))
    if counts_falls(fn, line):
        out_shape += (jax.ShapeDtypeStruct((1,), jnp.int32),)
        out_specs += (pl.BlockSpec((1,), lambda i: (0,),
                                   memory_space=pltpu.SMEM),)
        scratch = [pltpu.SMEM((1,), jnp.int32)]     # did the last tile fall
    else:
        scratch = []
    body = functools.partial(_kernel_body, fn, needs_sumsq, window_ms,
                             interval_ms, Sb, Ca, Tp, G, residency, c0, line,
                             holes, births)
    const = functools.partial(pl.BlockSpec, index_map=lambda i: (0, 0),
                              memory_space=pltpu.VMEM)
    # a per-row operand, [S / Sb, 1, Sb] (lane_major): tile i's [1, Sb]
    row = pl.BlockSpec((None, 1, Sb), lambda i: (i, 0, 0),
                       memory_space=pltpu.VMEM)
    kcol = c0 // Ca                       # active_columns guarantees c0 % Ca == 0
    in_specs = [pl.BlockSpec((Sb, Ca), lambda i: (i, kcol),
                             memory_space=pltpu.VMEM)]
    in_specs += [row] * var.row_operands            # vmin/scale or anchor
    in_specs += [row, row]                          # n (with start), gids
    if line:
        in_specs += [in_specs[0]]                   # the residual tile
    We = EDGE_SLOTS // line * Tp if line else Tp    # ohe's (ohlo's) lanes
    in_specs += [
        const((Ca, Tp)), const((Ca, We)),
        const((1, Tp)), const((1, Tp)), const((1, Tp)),
    ]
    if line:
        in_specs += [const((8, Tp))]
    # scoped VMEM, stated from the footprint instead of the 16 MiB default:
    # the value tile and both 0/1 operands double-buffered (``band`` /
    # ``ohlo`` bf16; a line plan's ``ohe`` int8), the accumulators, and the
    # working set of tile_contrib (decoded tile, shifted copy, increments,
    # and of v and the increments each the f32 remainder and three bf16
    # pieces; a dozen [Sb, Tp] planes). At the caps (C=1024, Tp=512, G=64)
    # the default runs out ("Ran out of memory in memory space vmem",
    # compiled for v5e)
    footprint = (2 * (Sb * Ca * jnp.dtype(var.block_dtype).itemsize
                      + Ca * Tp * 2 + Ca * We * (1 if line else 2))
                 + 2 * n_out * G * Tp * 4
                 + 9 * Sb * Ca * 4 + 12 * Sb * Tp * 4)
    if line:
        # the residual tile (int8, double-buffered); of pick_exact the
        # values read as their bytes [4 Sb, Ca] int8, the product [4 Sb,
        # We] int32 and its narrowing, the picked f32 planes [Sb, We]; the
        # residuals' own picks [Sb, We] int32
        footprint += 2 * Sb * Ca + Sb * Ca * 4 + 6 * Sb * We * 4
    if holes:
        # the filled planes and their pieces; the distances and residuals
        # of both as int8 [Sb, Ca] and their four picks [Sb, Tp] int32; the
        # plane filled forward picked at hi (bytes, product, narrowing)
        footprint += (14 * Sb * Ca * 4 + 4 * Sb * Ca + 4 * Sb * Tp * 4
                      + Sb * Ca * 4 + 5 * Sb * Tp * 4)
    if births:
        # the birth cell's mask and the masked values it reduces, and the
        # first cell a row, [Sb, Tp], where it was a [1, Tp] row
        footprint += 2 * Sb * Ca * 4 + 2 * Sb * Tp * 4
    return pl.pallas_call(
        body,
        grid=(S // Sb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(VMEM_CAP, max(32 << 20, 2 * footprint))),
        interpret=interpret,
    )


def active_columns(C: int, lo: np.ndarray, hi: np.ndarray) -> tuple[int, int]:
    """(c0, Ca): the aligned store-column range the query actually reads —
    first-sample selects need cell max(0, lo.min()); window sums need cells
    (lo, hi]. Everything outside contributes nothing, so a sub-range query
    (a "last 30m" dashboard panel over hours of retention) streams and
    matmuls only its own columns. Constraint: the value block's offset must
    be a multiple of its width (Pallas block indexing), so Ca grows in
    128-steps until an aligned start covers the range — worst case the full
    store (c0=0, Ca=C), typical dashboards a small suffix of it. C must be
    a multiple of 128; callers get (0, C) otherwise."""
    if C % 128 != 0 or len(lo) == 0:
        return 0, C
    first = max(0, int(lo.min()))
    last = min(C - 1, int(hi.max()))
    if last < first:                      # empty windows: minimal block
        last = first
    c1 = _roundup(last + 1, 128)
    Ca = c1 - (first // 128) * 128
    while Ca < C:
        c0 = (first // Ca) * Ca
        # the block must cover [c0, c1) AND stay inside the store: for a
        # non-power-of-two C the last aligned block start can overhang the
        # store edge (e.g. C=640, Ca=384 -> c0=384, c0+Ca=768), which would
        # under-slice the band operand and read value columns past C
        if c0 + Ca >= c1 and c0 + Ca <= C:
            return c0, Ca
        Ca += 128
    return 0, C


def build_xla_tiles(fn: str, needs_sumsq: bool, window_ms: int,
                    interval_ms: int, S: int, Sb: int, C: int, Tp: int,
                    G: int, residency: str = "raw", c0: int = 0, Ck: int = 0,
                    line: int = 0, holes: bool = False, births: bool = False):
    """XLA-fused twin of :func:`build_pallas`, built from the SAME tiling
    plan: one ``lax.scan`` walks the identical [Sb, Ca] row tiles through
    the identical :func:`tile_contrib` math and accumulates the same [G, Tp]
    partial state — one compiled program, intermediates bounded by one tile,
    the [S, T] matrix never materializes in HBM. Selected per
    ``query.fused_kernels`` (ops/fusedresident.py); signature-compatible
    with build_pallas's returned call so the mesh route swaps them freely.
    ``residency`` picks the registered xla decode twin (ops/decodereg.py)
    applied per tile — the full [S, C] f32 block never materializes on
    this variant either."""
    f32 = jnp.float32
    var = decodereg.variant(residency)
    assert not var.full_columns or c0 == 0, (residency, c0)
    R = var.row_operands
    Ca = Ck if Ck else C
    nt = S // Sb
    roll = lambda x, k: jnp.roll(x, k, axis=1)  # noqa: E731 — tile-local
    # wrap, masked in tile_contrib exactly like pltpu.roll's

    falls = counts_falls(fn, line)

    def fold(carry, xs, band, ohlo, lo, hi, rel, *eb):
        blk_t, *rest = xs
        rows_t = [_column(r) for r in rest[:R + 1]]
        n_t, g_t, tile, born_t = rows_t[R], rest[R + 1], None, None
        if line:
            n_t, start_t = unpack_start(n_t)
            tile = (start_t, rest[R + 2], eb[0])
        elif births:
            n_t, born_t = unpack_start(n_t)

        def one(form="band"):
            v = var.xla(blk_t, *rows_t[:R])
            contrib, okf, *fell = tile_contrib(
                fn, window_ms, interval_ms, c0, v, n_t, band, ohlo, lo, hi,
                rel, roll, line=tile, holes=holes, form=form, born=born_t)
            return group_fold(g_t, G, contrib, okf, needs_sumsq), *fell

        if not falls:
            parts, = one()
            return tuple(c + p for c, p in zip(carry, parts)), None
        # the tiles that ran the band form and whether the last one fell
        # ride last, as the Pallas program's SMEM output and scratch
        *acc, count, need = carry
        parts, ran_band, fell = fallen_fold(
            need, one, tuple(jnp.zeros_like(a) for a in acc))
        return (*(c + p for c, p in zip(acc, parts)),
                count + ran_band.astype(jnp.int32), fell), None

    def run_tiles(tiles, *ops):
        init = tuple(jnp.zeros((G, Tp), f32)
                     for _ in range(3 if needs_sumsq else 2))
        if falls:
            init += (jnp.zeros((1,), jnp.int32), jnp.bool_(False))
        outs, _ = jax.lax.scan(
            lambda c, xs: fold(c, xs, *ops), init, tiles)
        return outs[:-1] if falls else outs

    def call(blk, *rest):
        # rest: R per-row decode operands, n and gids, each [nt, 1, Sb]
        # (lane_major) and scanned as it is, (a line store's residual
        # block,) then the band/edge ops; active columns sliced like the
        # pallas block index map
        k = R + 2
        tiles = (blk[:, c0:c0 + Ca].reshape(nt, Sb, Ca),) + rest[:k]
        if line:
            tiles += (rest[k][:, c0:c0 + Ca].reshape(nt, Sb, Ca),)
            k += 1
        return run_tiles(tiles, *rest[k:])
    return call


def fused_program(fn: str, needs_sumsq: bool, window_ms: int,
                  interval_ms: int, S: int, Sb: int, C: int, Tp: int, G: int,
                  residency: str = "raw", c0: int = 0, Ck: int = 0,
                  variant: str = "pallas", line: int = 0,
                  holes: bool = False, births: bool = False):
    """The whole fused program of one query as a traceable function of the
    store's own arrays — ``n``, ``gids``, a line store's ``start`` and the
    narrow variants' per-row operands all ``[S]``: the casts, the pack of
    start above count and the ``[S] -> [S / Sb, 1, Sb]`` reshapes
    (:func:`lane_major`, no byte moves) live inside the one jit, so a query
    is one dispatch and no relayout. ``residency`` .. ``births`` as
    :func:`build_pallas` and :func:`_build_call` have them; in the births
    mode the store's ``born [S]`` follows ``gids``."""
    R = decodereg.variant(residency).row_operands
    if variant == "xla":
        call = build_xla_tiles(fn, needs_sumsq, window_ms, interval_ms,
                               S, Sb, C, Tp, G, residency, c0, Ck, line,
                               holes, births)
    else:
        call = build_pallas(fn, needs_sumsq, window_ms, interval_ms,
                            S, Sb, C, Tp, G, variant != "pallas",
                            residency, c0, Ck, line, holes, births)

    def rows(n, gids):
        return (lane_major(n.astype(jnp.int32), Sb),
                lane_major(gids.astype(jnp.int32), Sb))

    if births:
        def wrapped(blk, *rest):
            if residency == "raw":
                blk = blk.astype(jnp.float32)
            n, gids, born = rest[R:R + 3]
            return call(blk, *(lane_major(r, Sb) for r in rest[:R]),
                        *rows(pack_start(n, born), gids), *rest[R + 3:])
    elif residency != "raw":
        def wrapped(blk, *rest):
            return call(blk, *(lane_major(r, Sb) for r in rest[:R]),
                        *rows(rest[R], rest[R + 1]), *rest[R + 2:])
    elif line:
        def wrapped(val, n, gids, start, res, *ops):
            return call(val.astype(jnp.float32),
                        *rows(pack_start(n, start), gids), res, *ops)
    else:
        def wrapped(val, n, gids, *ops):
            return call(val.astype(jnp.float32), *rows(n, gids), *ops)
    return wrapped


def _build_call(*statics):
    """The compiled fused program via the explicit plan cache
    (query/plancache.py). ``statics`` are :func:`fused_program`'s sixteen
    arguments in its order, and the key IS them: fn/op statics, the padded
    [S, C, Tp, G] shape buckets, the ``residency`` decode variant ("raw" |
    "quant16" | "delta16" | "delta8"), and the backend ``variant`` as
    :func:`kernel_tag` names it ("pallas" | "pallas-interpret" | "xla") —
    every (residency, backend) pair is a distinct program and caches as a
    distinct kernel variant. ``line`` as :func:`build_pallas` has it: Tp is
    128 for 1..128 steps, so a packed line program is told apart here; so
    is the mode that reads around ``holes``, and the grid program's
    ``births`` mode."""
    from ..query.plancache import plan_cache
    # a grid program's key is what it was before there was a line form, and
    # an unpacked line program's what it was before there was a packed one
    *key, line, holes, births = statics
    if line:
        key += ("line",) if line == 1 else ("line", line)
    if holes:
        key += ("holes",)
    if births:
        key += ("births",)
    return plan_cache.program("fused-grid", tuple(key),
                              lambda: fused_program(*statics))


def pad_edges(lo: np.ndarray, hi: np.ndarray, rel: np.ndarray,
              window_ms: int, Tp: int):
    """Step-edge operands padded to the kernel's Tp grid as [1, Tp] i32:
    lo zero-padded, hi padded with -1 (an empty window — cnt clamps to 0
    so padded steps contribute nothing), rel zero-padded. One definition
    for every fused tier (scalar here, hist in ops/fusedresident.py) —
    the sentinel values are kernel contracts, not formatting."""
    T = len(rel)
    assert abs(rel).max(initial=0) < 2**31 and window_ms < 2**31
    lo_p = np.zeros(Tp, np.int32); lo_p[:T] = lo
    hi_p = np.full(Tp, -1, np.int32); hi_p[:T] = hi
    rel_p = np.zeros(Tp, np.int32); rel_p[:T] = rel
    return (lo_p.reshape(1, Tp), hi_p.reshape(1, Tp), rel_p.reshape(1, Tp))


def host_operands(C: int, Tp: int, out_ts: np.ndarray, window_ms: int,
                  base_ts: int, interval_ms: int, fn_kind: str = "rate",
                  full_cols: bool = False, line: bool = False,
                  holes: bool = False):
    """Band/one-hot/edge operands as host arrays + active column range:
    (band, ohlo, lo[1,Tp], hi[1,Tp], rel[1,Tp], c0, Ck) — shared by the
    single-chip upload cache below and the mesh path (which replicates them
    across shard devices). ``band`` and ``ohlo`` are 0/1 and go up as bf16,
    rounded here (:func:`dot_exact01` multiplies them in three passes). For
    a sub-range query the band/ohlo rows are sliced to the active [c0,
    c0+Ck) columns (the tiled kernel streams only those store tiles);
    full-range queries keep [C, Tp] operands.
    ``fn_kind`` picks the band form: "rate" builds the OPEN band the
    increment matmul needs, "window" the CLOSED band of the *_over_time
    fns (tile_contrib consumes whichever matches its fn). ``full_cols``
    bypasses active-column slicing — required by full_columns decode
    variants whose per-tile decode telescopes from cell 0. ``line``: the
    operands of a line store (:func:`_line_contrib`) — ``[lo, hi]`` are the
    cells every row holds, ``ohe`` replaces ``ohlo`` (one-hot slots of the
    cells lo-2, lo-1, hi+1, hi+2, max(lo, 0), hi: ``[C, 6 Tp]``, or two
    slots a block, ``[C, 3 x 128]``, where :func:`slots_per_block` says
    so; INT8 and picks only, :func:`pick_exact` — the window fns' closed
    band is ``band``), and ``eb [8, Tp]`` i32 follows ``rel``: per step the
    least ``start + residual`` that puts cell lo-2 (row 0) or lo-1 (row 1)
    in the window and the most that puts hi+1 (row 2) or hi+2 (row 3) in
    it. ``holes``: the same operands for
    :func:`_hole_contrib`, but for the rate family's band, the cells ``[lo,
    hi - 1]``, and the active columns, three cells a side (a filled plane
    reaches over a run of holes)."""
    import ml_dtypes
    bf16 = ml_dtypes.bfloat16
    T = len(out_ts)
    lo, hi = gridfns.grid_edges(out_ts, window_ms, base_ts, interval_ms,
                                line_spread(interval_ms) if line else (0, 0))
    rel = out_ts - base_ts
    lo_p, hi_p, rel_p = pad_edges(lo, hi, rel, window_ms, Tp)
    band = np.zeros((C, Tp), bf16)
    if holes and fn_kind == "rate":
        band[:, :T] = gridfns.band_matrix(C, lo, hi - 1, False, np.float32)
    else:
        band[:, :T] = gridfns.band_matrix(C, lo, hi, fn_kind == "rate",
                                          np.float32)
    if not line:
        ohlo = np.zeros((C, Tp), bf16)
        ohlo[:, :T] = gridfns.onehot_matrix(C, np.maximum(lo, 0), np.float32)
        c0, Ca = (0, C) if full_cols else active_columns(C, lo, hi)
        if Ca < C:
            band = np.ascontiguousarray(band[c0:c0 + Ca])
            ohlo = np.ascontiguousarray(ohlo[c0:c0 + Ca])
        return (band, ohlo, lo_p, hi_p, rel_p, c0, Ca)
    cells = (lo - 2, lo - 1, hi + 1, hi + 2, np.maximum(lo, 0), hi)
    slot = Tp // slots_per_block(T)       # lanes from one slot to the next
    ohe = np.zeros((C, EDGE_SLOTS * slot), np.int8)
    steps = np.arange(T)
    for j, cell in enumerate(cells):
        real = (cell >= 0) & (cell < C)
        ohe[cell[real], j * slot + steps[real]] = 1
    eb = np.zeros((8, Tp), np.int64)
    eb[:2], eb[2:4] = _NEVER, -_NEVER
    for j, cell in enumerate(cells[:2]):          # start + res >= this
        eb[j, :T] = np.where(cell >= 0, rel - window_ms - cell * interval_ms,
                             _NEVER)
    for j, cell in enumerate(cells[2:4], 2):      # start + res <= this
        eb[j, :T] = np.where(cell >= 0, rel - cell * interval_ms, -_NEVER)
    eb = np.clip(eb, -_NEVER, _NEVER).astype(np.int32)
    reach = 3 if holes else 2
    c0, Ca = active_columns(C, lo - reach, hi + reach)
    if Ca < C:
        band = np.ascontiguousarray(band[c0:c0 + Ca])
        ohe = np.ascontiguousarray(ohe[c0:c0 + Ca])
    return (band, ohe, lo_p, hi_p, rel_p, eb, c0, Ca)


@functools.lru_cache(maxsize=32)
def _device_operands(C: int, Tp: int, out_ts_key: bytes, window_ms: int,
                     base_ts: int, interval_ms: int, fn_kind: str = "rate",
                     full_cols: bool = False, line: bool = False,
                     holes: bool = False):
    """Band/one-hot/edge operands on device, cached per query shape — the
    upload matters: repeated host->device transfers of the [C, Tp] bands per
    row-batch are megabytes per query that never change."""
    out_ts = np.frombuffer(out_ts_key, np.int64)
    *arrs, c0, Ck = host_operands(C, Tp, out_ts, window_ms, base_ts,
                                  interval_ms, fn_kind, full_cols, line, holes)
    return tuple(jnp.asarray(a) for a in arrs) + (c0, Ck)


# conservative VMEM-driven caps for the fused path; beyond them callers must
# take the two-step route (which switches to segment_sum for large G)
MAX_GROUPS = 64          # matches aggregators.MATMUL_GROUP_LIMIT
MAX_STEPS = 512          # Tp cap: resident [C, Tp] bands + [Sb, Tp] tiles
MAX_CAPACITY = 1024      # C cap: [Sb, C] row tile + bands
# ceiling of the scoped-VMEM limit a fused kernel asks Mosaic for (a v5e has
# 128 MiB of VMEM; each builder states its own footprint below this)
VMEM_CAP = 96 << 20


def fusable(S: int, C: int, T: int, num_groups: int) -> bool:
    """Shape gate: the kernel keeps its operands resident in VMEM."""
    return (C <= MAX_CAPACITY
            and _roundup(max(T, 1), 128) <= MAX_STEPS
            and num_groups <= MAX_GROUPS
            and (S % 512 == 0 or (S <= 512 and S % 8 == 0)))


def _line_fall_tags(tiles: int, variant: str, falls) -> dict:
    """A line rate program's fetch-span tags from its fetched count of
    fallen tiles (its LAST output) and its grid steps."""
    return {"tiles": tiles,
            "fall_tiles": count_fall_tiles(falls, "line", variant)}


def _padded_parts(op: str, num_groups: int, T: int, outs) -> dict:
    """The partial dict of a fused program's FETCHED padded outputs (less
    a line rate program's count of fallen tiles): what its handle
    (``diagnostics.Dispatched``) answers with."""
    s, c = outs[0][:num_groups, :T], outs[1][:num_groups, :T]
    if op in ("count", "group"):
        return {"count": c}
    parts = {"sum": s, "count": c}
    if len(outs) > 2:
        parts["sumsq"] = outs[2][:num_groups, :T]
    return parts


def fused_grid_aggregate(op: str, fn: str, val, n, gids, num_groups: int,
                         out_ts: np.ndarray, window_ms: int,
                         base_ts: int, interval_ms: int, fetch: bool = True,
                         narrow=None, variant: str = "pallas", line=None,
                         holes: bool = False, born=None, born_late: int = 0):
    """One-pass ``op(fn(metric[window]))`` partials over a grid-aligned block.

    val [S, C] f32 (S a multiple of 512 or a power of two), n [S] i32 valid
    counts, gids [S] i32 dense group ids (< num_groups). Returns the same
    partial-state dict as ``aggregators.partial_aggregate(op, ...)`` with
    [num_groups, T] arrays, combinable via ``combine_partials`` / psum.
    With ``fetch=False`` returns the dispatch's handle
    (``diagnostics.Dispatched``) whose ``resolve()`` does the (blocking) host
    fetch later, outside the leaf's shard lock. ``narrow=(kind, operands)``
    streams a registered narrow block (ops/decodereg.py) instead of ``val``:
    kind names the decode variant ("quant16" | "delta16" | "delta8") and
    ``operands = (block, *row_operands)`` its device arrays — 1/4 to 1/2 the
    HBM bytes; the caller must already have zeroed ``n`` for rows whose
    narrow encoding is not bit-exact. ``line = (start, res)`` says that
    ``val`` is a line store's block: device i32 [S] row starts relative to
    ``base_ts`` and the int8 [S, C] residual block (core/chunkstore.py
    ``line_info``); the caller checked :func:`line_fusable` and zeroed
    ``n`` for the rows off their line. ``holes`` says that the line store
    has HOLES (``line_info().holes``): the program that reads around them
    runs, and no other store's. ``born`` (device i32 [S], the store's
    ``born_dev``) says that the grid store holds a row born late: the
    program's births mode runs (:func:`tile_contrib`), and no other
    store's; ``born_late`` is the dispatch span's tag, the SELECTED rows
    whose birth cell is past the grid's first.
    """
    assert fn in FUSED_FNS | FUSED_WINDOW_FNS and op in FUSED_OPS
    if narrow is not None:
        kind, nops = narrow
        S, C = nops[0].shape
    else:
        kind, nops = "raw", None
        S, C = val.shape
    T = len(out_ts)
    assert fusable(S, C, T, num_groups), (S, C, T, num_groups)
    Tp = _roundup(max(T, 1), 128)
    Sb = 512 if S % 512 == 0 else (S if S <= 512 else None)
    G = _roundup(max(num_groups, 8), 8)
    per = slots_per_block(T) if line is not None else 0
    holes = line is not None and bool(holes)
    births = born is not None and line is None

    *ops, c0, Ck = _device_operands(
        C, Tp, np.ascontiguousarray(np.asarray(out_ts, np.int64)).tobytes(),
        int(window_ms), int(base_ts), int(interval_ms),
        "window" if fn in FUSED_WINDOW_FNS else "rate",
        decodereg.variant(kind).full_columns, line is not None, holes)

    needs_sumsq = op in ("stddev", "stdvar")
    call = _build_call(fn, needs_sumsq, int(window_ms), int(interval_ms),
                       S, Sb, C, Tp, G, kind, c0, Ck, kernel_tag(variant), per,
                       holes, births)
    # the framework runs with x64 on (int64 timestamps); Mosaic rejects the
    # i64 scalars x64 tracing injects (grid index maps, roll shifts), and the
    # kernel itself is pure f32/i32 — so trace the call with x64 off.
    # The span's tags are what ties a device event to its query and gives
    # the bytes the kernel streams from inside (rows x cols from c0 on);
    # ``packed``: edge slots a block of a line program (1 | 2)
    # ``holes``: which mode of the line program ran (0 | 1)
    # ``births``: which mode of the grid program ran (0 | 1), ``born_late``
    # the selected rows born past the grid's first cell
    tags = ({"stamps": "grid", "births": int(births),
             "born_late": int(born_late)} if line is None else {
        "stamps": "line", "packed": per, "holes": int(holes)})
    rows = (jnp.asarray(n), jnp.asarray(gids)) + ((born,) if births else ())
    with dispatching(kernel=kernel_tag(variant), rows=S, c0=c0, cols=Ck,
                     steps=T, groups=num_groups, **tags) as padded, \
            jax.enable_x64(False):
        if nops is not None:
            outs = call(*nops, *rows, *ops)
        elif line is not None:
            outs = call(val, *rows, *line, *ops)
        else:
            outs = call(val, *rows, *ops)
    # partial state is tiny ([G, Tp]): ONE host fetch finishes the query — the
    # slice/present/combine chain as device ops would cost a round-trip each.
    # A program that counts its fallen tiles (counts_falls) says so on its
    # fetch span, beside its grid steps, and in /metrics
    padded.holds(outs, functools.partial(_padded_parts, op, num_groups, T),
                 functools.partial(_line_fall_tags, S // Sb, variant)
                 if counts_falls(fn, per) else None)
    return padded.resolve() if fetch else padded


@functools.lru_cache(maxsize=8)
def zero_gids(S: int):
    """Cached device zeros for single-group (global) aggregation — uploading
    a fresh [S] int32 per query is a 4 MB host->device transfer for 1M
    series."""
    return jnp.zeros(S, jnp.int32)
