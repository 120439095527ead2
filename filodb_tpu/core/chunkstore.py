"""Device-resident columnar series store — the HBM equivalent of the off-heap chunk
substrate.

Reference mapping:
  - memory/.../BlockManager.scala + MemFactory.scala (off-heap blocks, reclaim)
      -> preallocated padded device arrays, amortized compaction instead of blocks
  - core/.../memstore/TimeSeriesPartition.scala (write buffers -> frozen chunks)
      -> host staging buffers -> one batched device scatter per flush group
  - memory/.../data/ChunkMap.scala (per-partition chunk index)
      -> not needed: each series is a contiguous sorted row [series, capacity]

Layout per (shard, schema): ``ts[S, C] int64`` (pad = +sentinel), ``val[S, C]``
(f32 by default; f64 for parity testing), ``n[S] int32`` valid counts. All query
kernels read these arrays directly; ingest appends via an out-of-bounds-dropping
scatter with donated buffers (in-place HBM update, no realloc).

Why not compressed chunks in HBM? The reference compresses to fit ~1M series in a
1GB JVM heap. A TPU chip has 16GB+ HBM: 1M series x 1k samples x (8B ts + 4B val)
fits raw, and raw arrays keep the query path a pure gather/reduce. Compression
(NibblePack & co) lives at the persistence/wire layer (core/store.py).
"""

from __future__ import annotations

import functools
import logging
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import diagnostics

log = logging.getLogger(__name__)

TS_PAD = np.int64(1) << np.int64(62)   # sentinel > any real timestamp
# the stamp of a cell BEFORE a row's birth cell (see the text on how the
# store keeps time): below any real stamp, so a row stays sorted and no
# window or lookback ever reaches it
TS_UNBORN = -(np.int64(1) << np.int64(62))


class DecodeRefused(RuntimeError):
    """A query asked for the whole f32 / s64 view of a store that is held
    narrow, and the device has no room for it
    (``SeriesStore.check_decode_budget``)."""

# How the store keeps time. While every series is scraped on one common grid
# (sample k at ``first + k * interval``, every first stamp on the grid, no
# scrape missed) the s64 block ``ts`` holds the stamps and the shard is in
# its GRID form. A scalar store that was not born narrow (``aligned``) keeps
# that form in TIME-ALIGNED CELLS: column c is cell c of the shard's grid
# for EVERY row, ``cell0 + c * interval``. A series that appears later (a
# redeployed pod, a new label set) is written from its BIRTH CELL on,
# ``born[row]`` (host mirror and device i32 beside ``n``); ``n[row]`` stays
# the cells the row USES, its birth cell's predecessors among them, as it
# counts holes in the line form. The cells before a birth hold
# ``TS_UNBORN`` and 0.0 and are read by nothing: the fused and grid kernels
# take ``born <= col < n`` as a row's samples, the general kernels find no
# window that reaches a stamp that low. So the start cohort of such a store
# is uniform by construction (``grid_cohorts``), one fused program answers
# rows born at any number of cells, compaction shifts every row by the
# same cells (``born`` with them) and a reused slot starts at its new
# owner's birth cell. The other grid forms (a layout store, a store born
# narrow) keep every row from column 0, where column k of a row is the
# row's k-th sample and a late row is one more start COHORT. The first stamp
# that is off that grid — a target with its own scrape phase, a scrape
# stamped late — or the first missed scrape turns the scalar store into its
# LINE form, once: column c is then CELL c of the row's line (``line0[row] +
# c * interval``, ``line0`` the row's first stamp) whether or not a sample
# sits in it, with a narrow signed residual per cell, ``res[S, C]`` on the
# device beside the values, and the s64 block is dropped (it is derivable:
# ``ts_block``, ``DeferredTs``). A sample's cell is ``round((ts - line0) /
# interval)``, so a late scrape never shares a cell with its successor. A
# cell WITHOUT a sample is a HOLE and is kept as one: its residual reads
# ``RES_HOLE`` (residuals lie in [-RES_MAX, RES_MAX], so the mark costs no
# byte). Holes come from Prometheus's staleness markers (a row whose value
# is ``STALE_NAN``: the scrape failed) and from cells a row skipped, runs of
# up to HOLE_RUN_MAX cells of the two together (``tail_holes[row]`` is the
# run a row ends in, so the bound holds across batches); ``n[row]`` counts
# the cells a row USES, holes among them, and ``holes_host[row]`` the
# holes. A hole's VALUE cell, which no function reads as a value, says
# which of the two it is: a marker's holds its own stamp less the cell's
# line stamp (what the residual would have held), a skipped cell's
# ``HOLE_SKIPPED``. No function counts, sums or extrapolates to a hole. An
# instant selector whose newest row at or before a step is a MARKER (by
# the marker's own stamp) returns nothing, Prometheus's rule; a skipped
# cell is nothing at all, and the sample before it is served for as long
# as the lookback says. A row whose sample
# does not fit its line — a residual beyond RES_MAX, a run of holes past
# the bound, a changed interval — is DEMOTED as a row: its exact stamps
# move to a host pool (a hole there is ``TS_PAD`` + its stamp), it joins the
# minority set (``line_info().minority``) and is answered by the general
# kernels over gathered rows, as a churned row is. The shard stays on the
# fused path (ref: upstream's delta-delta timestamp vectors, a line plus
# narrow residuals — doc/compression.md; Prometheus scrape/scrape.go for
# the markers). A layout (histogram, multi-column) store has no line form:
# a marker there is a NaN sample and a skipped cell clears ``grid_ok``.
RES_DTYPE = np.int8
RES_MAX = 127
RES_HOLE = -128         # the residual of a cell that holds no sample
HOLE_RUN_MAX = 3        # holes a row may hold in a run and stay on its line
HOLE_SKIPPED = 256.0    # the value cell of a hole that no marker came for
# Prometheus's value.StaleNaN: this bit pattern and no other NaN
STALE_NAN_BITS = np.uint64(0x7FF0000000000002)
STALE_NAN = np.array([STALE_NAN_BITS]).view(np.float64)[0]
DEMOTE_REASONS = ("residual", "gap", "interval")


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _scatter_append(ts, val, n, rows, cols, new_ts, new_val, counts_add):
    ts = ts.at[rows, cols].set(new_ts, mode="drop")
    val = val.at[rows, cols].set(new_val, mode="drop")
    n = n + counts_add
    return ts, val, n


# A scatter into a multi-GB block does not run in place on the TPU: XLA
# flattens the [S, C] operand to one dimension (a relayout copy in and out,
# temp = the block), and every program that takes the s64 timestamp block
# splits it into two u32 planes first (temp = the block again). Compiled for
# a v5e at 2^20 x 768 the one-program flush above asks for 9 GB of temp
# beside its 9 GB of donated arguments — "Ran out of memory in memory space
# hbm. Used 18.03G of 15.75G". So from DENSE_APPEND_BYTES up, when each row
# gains at most DENSE_APPEND_MAX_K samples (a scrape), the flush is a
# per-row select instead: the new sample's column per row, -1 for none —
# elementwise and donated, so the f32 block updates in place with no temp,
# and the two blocks go through separate programs, so the s64 split (6 GB at
# this shape) is the only temp alive at any moment. A layout store takes
# the same route, a select a block: compiled for a v5e at 2^15 x 768 x 64
# (prom-histogram, 6.4 GB of buckets) _scatter_append_multi asks for 6.0 GB
# of temp beside its 6.4 GB of donated arguments, and _dense_set on the
# [S, C, B] block for none.
DENSE_APPEND_BYTES = 2 << 30
DENSE_APPEND_MAX_K = 4


@functools.partial(jax.jit, donate_argnums=(0,))
def _dense_set(block, col, new):
    """``block[s, col[s]] = new[s]`` for every row with ``col[s] >= 0``."""
    hit = jax.lax.broadcasted_iota(jnp.int32, block.shape[:2], 1) \
        == col[:, None]
    new = new[:, None]
    if block.ndim == 3:         # histogram block [S, C, B], new [S, B]
        hit = hit[:, :, None]
    return jnp.where(hit, new.astype(block.dtype), block)


@functools.partial(jax.jit, donate_argnums=(0,))
def _add_counts(n, counts_add):
    return n + counts_add


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _scatter_append_multi(ts, val, extra, n, rows, cols, new_ts, new_val,
                          new_extra, counts_add):
    """Multi-value-column append: the default column plus named scalar
    columns scatter in ONE dispatch (extra/new_extra are dicts — pytree
    donation covers every leaf)."""
    ts = ts.at[rows, cols].set(new_ts, mode="drop")
    val = val.at[rows, cols].set(new_val, mode="drop")
    extra = {k: v.at[rows, cols].set(new_extra[k], mode="drop")
             for k, v in extra.items()}
    n = n + counts_add
    return ts, val, extra, n


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _compact(ts, val, n, cutoff):
    """Drop samples with ts < cutoff by shifting each series row left (one gather)."""
    S, C = ts.shape
    k = jax.vmap(lambda row: jnp.searchsorted(row, cutoff, side="left"))(ts)  # [S]
    idx = jnp.arange(C)[None, :] + k[:, None]                                 # [S, C]
    valid = idx < C
    idx = jnp.where(valid, idx, C - 1)
    new_ts = jnp.where(valid, jnp.take_along_axis(ts, idx, axis=1), TS_PAD)
    if val.ndim == 3:   # histogram store [S, C, B]
        new_val = jnp.where(valid[:, :, None],
                            jnp.take_along_axis(val, idx[:, :, None], axis=1), 0)
    else:
        new_val = jnp.where(valid, jnp.take_along_axis(val, idx, axis=1), 0)
    new_n = jnp.maximum(n - k.astype(n.dtype), 0)
    # re-pad anything beyond the new count (handles rows where k > old n)
    pos = jnp.arange(C)[None, :]
    new_ts = jnp.where(pos < new_n[:, None], new_ts, TS_PAD)
    return new_ts, new_val, new_n


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _compact_multi(ts, val, extra, n, cutoff):
    """Multi-column twin of ``_compact``: one gather per column, shared
    shift indices."""
    S, C = ts.shape
    k = jax.vmap(lambda row: jnp.searchsorted(row, cutoff, side="left"))(ts)
    idx = jnp.arange(C)[None, :] + k[:, None]
    valid = idx < C
    idx = jnp.where(valid, idx, C - 1)
    new_ts = jnp.where(valid, jnp.take_along_axis(ts, idx, axis=1), TS_PAD)
    if val.ndim == 3:
        new_val = jnp.where(valid[:, :, None],
                            jnp.take_along_axis(val, idx[:, :, None], axis=1), 0)
    else:
        new_val = jnp.where(valid, jnp.take_along_axis(val, idx, axis=1), 0)
    new_extra = {kk: jnp.where(valid, jnp.take_along_axis(vv, idx, axis=1), 0)
                 for kk, vv in extra.items()}
    new_n = jnp.maximum(n - k.astype(n.dtype), 0)
    pos = jnp.arange(C)[None, :]
    new_ts = jnp.where(pos < new_n[:, None], new_ts, TS_PAD)
    return new_ts, new_val, new_extra, new_n


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _free_rows(ts, n, pids):
    ts = ts.at[pids, :].set(TS_PAD, mode="drop")
    n = n.at[pids].set(0, mode="drop")
    return ts, n


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
def _mark_unborn(block, born_new, fill, carry: bool):
    """The cells before a birth: every row with ``born_new[row] > 0`` (0: a
    row this call leaves alone) is given ``fill`` in its columns below
    that cell. ``carry``: the row's one sample sits in column 0 (it came
    before the shard's interval was known) and moves to its birth cell.
    Elementwise and donated: in place on a block of any size."""
    col = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    b = born_new[:, None]
    out = jnp.where(col < b, jnp.asarray(fill, block.dtype), block)
    if carry:
        out = jnp.where((col == b) & (b > 0), block[:, :1], out)
    return out


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _compact_aligned(ts, val, n, born, k):
    """``_compact`` of a store in time-aligned cells: every row moves left
    by the SAME ``k`` cells (one roll, no gather), ``born`` with it; a row
    whose last cell is dropped is empty."""
    new_n = jnp.maximum(n - k, 0)
    new_born = jnp.maximum(born - k, 0)
    gone = new_n <= new_born
    new_n = jnp.where(gone, 0, new_n)
    new_born = jnp.where(gone, 0, new_born)
    col = jax.lax.broadcasted_iota(jnp.int32, ts.shape, 1)
    kept = col < new_n[:, None]
    ts = jnp.where(kept, jnp.roll(ts, -k, axis=1), TS_PAD)
    ts = jnp.where(col < new_born[:, None], TS_UNBORN, ts)
    val = jnp.where(kept & (col >= new_born[:, None]),
                    jnp.roll(val, -k, axis=1), 0)
    return ts, val, new_n, new_born


@functools.partial(jax.jit, donate_argnums=(0, 1), static_argnums=(4,))
def _unalign_block(val, n, born, r0, rows):
    """Rows ``r0 .. r0 + rows`` of a value block in time-aligned cells
    moved left to their own column 0 (the line form's layout: column k is
    the row's k-th cell), their counts less their birth cells."""
    C = val.shape[1]
    zero = jnp.zeros((), r0.dtype)
    blk = jax.lax.dynamic_slice(val, (r0, zero), (rows, C))
    kb = jax.lax.dynamic_slice(born, (r0,), (rows,))
    nb = jax.lax.dynamic_slice(n, (r0,), (rows,))
    return (jax.lax.dynamic_update_slice(val, _shift_left(blk, kb, nb),
                                         (r0, zero)),
            jax.lax.dynamic_update_slice(n, nb - kb, (r0,)))


@jax.jit
def _close_births(ts, val, n, born):
    """(ts, val, n) with every row's samples a sorted prefix: a store in
    time-aligned cells moved left by its birth cells (``closed_arrays``)."""
    keep = n - born
    col = jax.lax.broadcasted_iota(jnp.int32, ts.shape, 1)
    return (jnp.where(col < keep[:, None], _shift_left(ts, born, n), TS_PAD),
            _shift_left(val, born, n), keep)


def _pow2_rows(m: int) -> int:
    """The next power of two at or over ``m``: the shapes of the pool's
    few-row programs."""
    return 1 << max(int(m) - 1, 0).bit_length()


def _pad_size(m: int) -> int:
    """Bucket flush sizes to powers of two to bound jit recompilations."""
    size = 1024
    while size < m:
        size *= 2
    return size


@jax.jit
def _decode_narrow(q, vmin, scale, pool, pool_rows):
    """Reconstruct the f32 value block from the quant16 narrow-resident
    state: quantized rows decode as vmin + (q + 32768) * scale (bit-exact
    for rows the encoder marked ok — ops/narrow.py contract); raw-pool rows
    overlay their exact f32 values (pool pad rows carry row index S ->
    dropped)."""
    v = vmin[:, None] + (q.astype(jnp.float32) + 32768.0) * scale[:, None]
    return v.at[pool_rows].set(pool, mode="drop")


@jax.jit
def _decode_delta(dv, anchor, pool, pool_rows):
    """Reconstruct the f32 value block from the delta16/delta8 scalar state
    (ops/narrow.py build_narrow_delta): v = anchor + cumsum(dv), bit-exact
    for ok rows (integer deltas, |prefix| <= 2^23); raw-pool rows overlay
    their exact f32 values."""
    v = anchor[:, None] + jnp.cumsum(dv.astype(jnp.float32), axis=1)
    return v.at[pool_rows].set(pool, mode="drop")


def _derive_ts_impl(first, n, interval, C):
    """Reconstruct the i64 timestamp block of a grid-contiguous store from
    per-row first timestamps: ts[r, k] = first[r] + k * interval for k < n[r]
    (TS_PAD beyond, and everywhere for empty rows)."""
    col = jax.lax.broadcasted_iota(jnp.int64, (first.shape[0], C), 1)
    live = (col < n[:, None]) & (first[:, None] >= 0)
    return jnp.where(live, first[:, None] + col * interval, TS_PAD)


_derive_ts = jax.jit(_derive_ts_impl, static_argnums=(3,))


@functools.partial(jax.jit, static_argnums=(4,))
def _derive_line_ts(line0, n, interval, res, C):
    """The i64 stamps of a line-form store (or of gathered rows of one):
    ``line0[r] + k * interval + res[r, k]`` for k < n[r], TS_PAD beyond. A
    hole stays in its cell, told from a sample by being past TS_PAD: TS_PAD
    + the stamp its line gives the cell (a marker's, to the residual)."""
    ts = _derive_ts_impl(line0, n, interval, C)
    hole = res == RES_HOLE
    return jnp.where(ts == TS_PAD, TS_PAD,
                     ts + jnp.where(hole, TS_PAD, res.astype(jnp.int64)))


@jax.jit
def close_holes(ts, val, n):
    """Rows whose used cells hold holes (stamps past TS_PAD, see
    ``_derive_line_ts``) -> ``(ts, val, n)`` with every row's samples a
    sorted prefix, as the general kernels (ops/windows.py) assume: a stable
    partition along the row, ``n`` the samples left."""
    col = jax.lax.broadcasted_iota(jnp.int32, ts.shape, 1)
    real = (ts < TS_PAD) & (col < n[:, None])
    order = jnp.argsort(~real, axis=1, stable=True)
    m = real.sum(axis=1).astype(n.dtype)
    ts = jnp.where(col < m[:, None], jnp.take_along_axis(ts, order, axis=1),
                   TS_PAD)
    return ts, jnp.take_along_axis(val, order, axis=1), m


@functools.partial(jax.jit, donate_argnums=(1, 2))
def _compact_line(ts, val, n, cutoff):
    """``_compact`` over a line store's derived stamps: a row's cells shift
    left by the cells before its first SAMPLE at or after ``cutoff``, holes
    moving with their cells (a row never starts in a hole)."""
    S, C = ts.shape
    keep = (ts < TS_PAD) & (ts >= cutoff)
    k = jnp.where(keep.any(axis=1), jnp.argmax(keep, axis=1), n)
    idx = jnp.arange(C)[None, :] + k[:, None]
    new_n = jnp.maximum(n - k.astype(n.dtype), 0)
    valid = idx < n[:, None]
    idx = jnp.where(idx < C, idx, C - 1)
    new_ts = jnp.where(valid, jnp.take_along_axis(ts, idx, axis=1), TS_PAD)
    new_val = jnp.where(valid, jnp.take_along_axis(val, idx, axis=1), 0)
    return new_ts, new_val, new_n




@functools.partial(jax.jit, donate_argnums=(0,))
def _zero_counts(n, pids):
    return n.at[pids].set(0, mode="drop")


# -- the delta form mutated IN PLACE ------------------------------------------
#
# A scalar gauge store on a grid whose values are a delta block (``dv`` int8
# or int16 ``[S, C]``, ``anchor`` f32 ``[S]``: v[c] = anchor + sum(dv[:c + 1]),
# dv[:, 0] = 0) and whose stamps are elided is appended to, aged out and
# freed AS IT IS: the f32 ``[S, C]`` block and the s64 one are never built
# (at 2^20 x 4,608 they are 19 and 39 GB). The host keeps what an append has
# to know of a row — its last value and the value every sample of it lies
# within 2^23 of (``last_val``, ``ref_val``) — so a delta is one subtraction
# on the host and the device writes a byte a sample. A sample that does not
# fit (a delta that is no integer or past the width, a value 2^23 from the
# row's reference) moves ITS ROW to the raw pool with the row's decoded
# history, one row's decode; the row is exact there ever after.
DELTA_LIMIT = {"delta8": 127, "delta16": 32767}
PREFIX_MAX = float(1 << 23)
# rows a blocked pass of the in-place form handles at a time: its
# temporaries are a block's, at most 2^15 x C x (1 B shifted + 4 B index)
BLOCK_ROWS = 1 << 15
REHYDRATE_CAUSES = ("append", "compact", "free", "off_grid", "cohort_gate",
                    "births")


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _scatter_append_delta(dv, n, rows, cols, d, counts_add):
    return dv.at[rows, cols].set(d, mode="drop"), n + counts_add


def _delta_rows(dv, anchor, rid):
    """f32 ``[P, C]`` of the rows ``rid`` of a delta block, no pool laid
    over them."""
    dvg = jnp.take(dv, rid, axis=0).astype(jnp.float32)
    return jnp.take(anchor, rid)[:, None] + jnp.cumsum(dvg, axis=1)


@functools.partial(jax.jit, donate_argnums=(2, 3), static_argnums=(5,))
def _pool_admit(dv, anchor, pool, slot, picks, Rp):
    """Rows ``picks[0]`` (pads: S) leave the delta form for the pool slots
    ``picks[1]`` (pads: ``Rp``) with their decoded history; the pool grows
    to ``Rp`` rows where it has fewer."""
    rid = picks[0]
    hist = _delta_rows(dv, anchor, jnp.minimum(rid, dv.shape[0] - 1))
    if Rp > pool.shape[0]:
        pool = jnp.concatenate(
            [pool, jnp.zeros((Rp - pool.shape[0], pool.shape[1]),
                             pool.dtype)])
    return (pool.at[picks[1]].set(hist, mode="drop"),
            slot.at[rid].set(picks[1], mode="drop"))


@functools.partial(jax.jit, donate_argnums=(0,))
def _pool_write(pool, slots, cols, v):
    return pool.at[slots, cols].set(v, mode="drop")


@functools.partial(jax.jit, donate_argnums=(0,))
def _clear_slots(slot, pids):
    return slot.at[pids].set(-1, mode="drop")


def _shift_left(block, k, n):
    """Each row of ``block`` moved left by ``k[row]`` cells, zeros past the
    ``n[row] - k[row]`` it keeps."""
    C = block.shape[1]
    col = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    idx = col + k[:, None]
    kept = jnp.take_along_axis(block, jnp.minimum(idx, C - 1), axis=1)
    return jnp.where(idx < n[:, None], kept, 0)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2), static_argnums=(5,))
def _compact_delta_block(dv, anchor, n, k, r0, rows):
    """Rows ``r0 .. r0 + rows`` of a delta block aged out by ``k`` cells a
    row: the anchor moves on to the first sample kept, the deltas after it
    shift left (the one AT it becomes the row's zero)."""
    C = dv.shape[1]
    blk = jax.lax.dynamic_slice(dv, (r0, jnp.zeros((), r0.dtype)), (rows, C))
    kb = jax.lax.dynamic_slice(k, (r0,), (rows,))
    nb = jax.lax.dynamic_slice(n, (r0,), (rows,))
    ab = jax.lax.dynamic_slice(anchor, (r0,), (rows,))
    col = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    head = jnp.sum(jnp.where(col <= kb[:, None], blk, 0), axis=1,
                   dtype=jnp.int32)
    shifted = jnp.where(col > 0, _shift_left(blk, kb, nb), 0)
    nb = nb - kb
    ab = jnp.where(nb > 0, ab + head.astype(jnp.float32), 0.0)
    return (jax.lax.dynamic_update_slice(dv, shifted,
                                         (r0, jnp.zeros((), r0.dtype))),
            jax.lax.dynamic_update_slice(anchor, ab, (r0,)),
            jax.lax.dynamic_update_slice(n, nb, (r0,)))


@functools.partial(jax.jit, donate_argnums=(0,))
def _compact_pool(pool, k, n):
    return _shift_left(pool, k, n)


@jax.jit
def _row_last(dv, anchor):
    """Each row's last value: a delta block holds zeros past a row's
    count."""
    return anchor + jnp.sum(dv, axis=1, dtype=jnp.int32).astype(jnp.float32)


def _verify_ts_block_impl(ts, first, n, interval, C):
    """Fused derive-and-compare reduction over a ROW BLOCK — a whole-store
    comparison at 1M x 768 materializes multi-GB i64 hi/lo split temps and
    dies exactly when HBM is tight (the situation compression exists for)."""
    return jnp.all(ts == _derive_ts_impl(first, n, interval, C))


_verify_ts_block = jax.jit(_verify_ts_block_impl, static_argnums=(4,))

_VERIFY_BLOCK_ROWS = 1 << 16


def _verify_ts(ts, first, n, interval, C) -> bool:
    S = ts.shape[0]
    B = _VERIFY_BLOCK_ROWS
    if S <= B:
        return bool(_verify_ts_block(ts, first, n, interval, C))
    for i in range(0, S, B):
        j = min(i + B, S)
        if not bool(_verify_ts_block(ts[i:j], first[i:j], n[i:j],
                                     interval, C)):
            return False
    return True


@jax.jit
def _decode_hist(dd, first_d, pool, pool_rows):
    """Reconstruct the f32 [S, C, B] bucket block from the hist-resident
    state: v = cumsum_b(first_d + cumsum_c dd) (bit-exact for rows the
    encoder marked ok — ops/narrow.py build_narrow_hist contract); pool rows
    overlay their exact f32 blocks. Cells beyond a row's valid count extend
    the last frame constantly (the raw store holds zeros there) — every
    consumer masks by ``n``, same as the scalar decode's out-of-range cells."""
    d = first_d[:, None, :] + jnp.cumsum(dd.astype(jnp.float32), axis=1)
    v = jnp.cumsum(d, axis=2)
    return v.at[pool_rows].set(pool, mode="drop")


@jax.jit
def _decode_hist_rows(dd, first_d, pool, pool_slot, rid):
    """Decode ONLY the given store rows ([P] ids) of a hist-resident block —
    minority/pool fixes must not materialize the full [S, C, B] f32 block."""
    ddg = jnp.take(dd, rid, axis=0).astype(jnp.float32)
    d = jnp.take(first_d, rid, axis=0)[:, None, :] + jnp.cumsum(ddg, axis=1)
    v = jnp.cumsum(d, axis=2)
    slot = jnp.take(pool_slot, rid, mode="clip")
    pv = jnp.take(pool, jnp.maximum(slot, 0), axis=0, mode="clip")
    return jnp.where((slot >= 0)[:, None, None], pv, v)


@jax.jit
def _decode_narrow_rows(q, vmin, scale, pool, pool_slot, rid):
    """Decode ONLY the given store rows ([P] ids): quantized reconstruction
    with pool-value overlay — minority-cohort fixes must not materialize the
    full [S, C] block (several GB at 1M x 768) for a handful of rows."""
    qg = jnp.take(q, rid, axis=0)
    v = (jnp.take(vmin, rid)[:, None]
         + (qg.astype(jnp.float32) + 32768.0)
         * jnp.take(scale, rid)[:, None])
    slot = jnp.take(pool_slot, rid, mode="clip")
    pv = jnp.take(pool, jnp.maximum(slot, 0), axis=0, mode="clip")
    return jnp.where((slot >= 0)[:, None], pv, v)


def _decode_delta_rows_impl(dv, anchor, pool, pool_slot, rid):
    v = _delta_rows(dv, anchor, rid)
    slot = jnp.take(pool_slot, rid, mode="clip")
    pv = jnp.take(pool, jnp.maximum(slot, 0), axis=0, mode="clip")
    return jnp.where((slot >= 0)[:, None], pv, v)


# row-wise delta16/delta8 decode with pool-value overlay — the delta twin of
# _decode_narrow_rows
_decode_delta_rows = jax.jit(_decode_delta_rows_impl)


# row-wise derivation is the same rule applied to a gathered first/n pair
_derive_ts_rows = _derive_ts


@functools.partial(jax.jit, static_argnums=(3,))
def _gather_grid(val, n, picked, C):
    """(ts, val, n) of the rows ``picked[0]`` of a grid-form store, their
    stamps derived (``SeriesStore.grid_row_gather``): ``picked`` is int64
    ``[3, P]`` — store rows, each row's first stamp (-1: a pad row, or one
    without a sample), the interval — one upload for all a gather needs
    from the host. ``[4, P]`` where the store holds a row born late
    (``SeriesStore.born_late``): ``(ts, val, n, born)``."""
    rid = picked[0].astype(jnp.int32)
    first = picked[1]
    n_g = jnp.where(first >= 0, jnp.take(n, rid), 0).astype(jnp.int32)
    if picked.shape[0] == 3:
        return (_derive_ts_impl(first, n_g, picked[2, 0], C),
                jnp.take(val, rid, axis=0), n_g)
    # time-aligned cells with late births: a fourth row, the birth cells.
    # The rows stay in their cells (the grid kernel reads ``born <= col <
    # n``, and ``born`` comes back beside ``n``); the stamps start at the
    # birth cell, TS_UNBORN before it
    born = jnp.where(first >= 0, picked[3], 0).astype(jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int64, (first.shape[0], C), 1)
    ts = _derive_ts_impl(first - born * picked[2, 0], n_g, picked[2, 0], C)
    return (jnp.where(col < born[:, None], TS_UNBORN, ts),
            jnp.take(val, rid, axis=0), n_g, born)


@functools.partial(jax.jit, static_argnums=(6,))
def _gather_grid_delta(dv, anchor, pool, slot, n, picked, C):
    """``_gather_grid`` over a delta block: the picked rows decoded (the
    bodies of ``_decode_delta_rows``, the pool laid over them) and their
    stamps derived, one program."""
    rid = picked[0].astype(jnp.int32)
    first = picked[1]
    n_g = jnp.where(first >= 0, jnp.take(n, rid), 0).astype(jnp.int32)
    return (_derive_ts_impl(first, n_g, picked[2, 0], C),
            _decode_delta_rows_impl(dv, anchor, pool, slot, rid), n_g)


class _Deferred:
    """Base for lazy views of elided store blocks: shape metadata for
    planning; ``materialize()`` reconstructs. General query paths funnel
    through query/exec._dval; the fused/grid paths never materialize."""

    __slots__ = ("_store", "_arr")
    ndim = 2

    def __init__(self, store: "SeriesStore"):
        self._store = store
        self._arr = None

    @property
    def shape(self):
        return (self._store.S, self._store.C)

    def materialize(self):
        if self._arr is None:
            self._store.check_decode_budget(
                int(np.prod(self.shape)) * self.dtype.itemsize)
            self._arr = self._build()
        return self._arr

    def __getitem__(self, idx):
        return self.materialize()[idx]


class DeferredDecode(_Deferred):
    """Lazy f32 view of a narrow-resident store's value block."""

    dtype = np.dtype(np.float32)

    def _build(self):
        return self._store.value_block()

    def gather_rows(self, rid):
        """[P, C] f32 of the given rows only (row-wise decode; falls back to
        the materialized block if one already exists or the store changed
        residency since this view was handed out)."""
        st = self._store
        if self._arr is None and st._narrow is not None:
            kind, ops, pool, _pp, slot, _ok = st._narrow
            if kind == "quant16":
                return _decode_narrow_rows(*ops, pool, slot, rid)
            return _decode_delta_rows(*ops, pool, slot, rid)
        return jnp.take(self.materialize(), rid, axis=0)


class DeferredDecodeHist(_Deferred):
    """Lazy f32 view of a hist-resident store's [S, C, B] bucket block."""

    dtype = np.dtype(np.float32)
    ndim = 3

    @property
    def shape(self):
        return (self._store.S, self._store.C, self._store.nbuckets)

    def _build(self):
        return self._store.value_block()

    def gather_rows(self, rid):
        """[P, C, B] f32 of the given rows only (row-wise decode + pool
        overlay; falls back to a materialized block if one exists or the
        store changed residency since this view was handed out)."""
        st = self._store
        if self._arr is None and st._nhist is not None:
            dd, first_d, pool, _pp, slot, _ok = st._nhist
            return _decode_hist_rows(dd, first_d, pool, slot, rid)
        return jnp.take(self.materialize(), rid, axis=0)


class DeferredTs(_Deferred):
    """Lazy i64 view of a timestamp block the store does not hold: elided
    on a grid (compressed residency) or kept as line + residual."""

    dtype = np.dtype(np.int64)

    def _build(self):
        return self._store.ts_block()

    def gather_rows(self, rid):
        """[P, C] i64 of the given rows only (row-wise derivation)."""
        st = self._store
        if self._arr is None and st.res is not None:
            return st._line_ts(rid)
        if self._arr is None and st._ts_elided:
            first_g = jnp.take(jnp.asarray(st.first_ts), rid)
            n_g = jnp.take(st.n, rid)
            return _derive_ts_rows(first_g, n_g, jnp.int64(st._interval()),
                                   st.C)
        return jnp.take(self.materialize(), rid, axis=0)


@dataclass(frozen=True)
class LineInfo:
    """What the fused tier reads of a line-form store (``line_info``)."""
    base_ts: int            # the majority rows' lines start within
    interval_ms: int        # [base_ts, base_ts + interval_ms + RES_MAX]
    start: object           # device i32 [S]: line0 - base_ts (clipped)
    res: object             # device int8 [S, C]: stamp - line
    off_mask: np.ndarray    # bool [S]: live rows the line kernel must skip
    minority: np.ndarray    # int32: their row ids, ascending
    holes: bool = False     # some used cell of the store holds no sample


@dataclass
class SeriesStoreStats:
    samples_appended: int = 0
    out_of_order_dropped: int = 0
    capacity_dropped: int = 0
    compactions: int = 0
    frees: int = 0
    stale_markers: int = 0      # rows that carried STALE_NAN (counted in
                                # samples_appended too: they were ingested)


class SeriesStore:
    """One shard's device store for a non-histogram schema value column."""

    def __init__(self, max_series: int, capacity: int, dtype=jnp.float32,
                 device=None, nbuckets: int = 0, layout=None,
                 default_col: str | None = None, born_narrow: bool = False):
        """``born_narrow`` (compressed residency): a scalar f32 store starts
        in its delta8 form with stamps elided and no raw block is ever
        allocated (see the text at DELTA_LIMIT); any other shape of store
        is born raw, as without it.

        ``layout`` (from Schema.col_layout) declares multi-value-column
        storage: the schema's DEFAULT column lives in ``self.val`` and every
        other data column gets its own named [S, C] array in ``self.extra``
        — one ts/n pair serves all columns (ref: multi-column datasets,
        Schemas.scala / filodb-defaults.conf:17-106; a column is selected at
        query time via __col__)."""
        # round the row dimension up to a fused-kernel-friendly shape (mult
        # of 8 up to 512, mult of 512 beyond): wide selections then always
        # qualify for the single-pass Pallas path, at a cost of <= 511 empty
        # rows; the logical slot budget stays config.max_series_per_shard
        m = 8 if max_series <= 512 else 512
        self.S = (max_series + m - 1) // m * m
        self.C = capacity
        self.dtype = dtype
        self.nbuckets = nbuckets   # 0 = scalar values; >0 = histogram [S, C, B]
        self.layout = layout       # [(name, offset, width, is_hist)] or None
        self.default_col = None
        # local_devices, not devices: under multi-host jax.distributed the
        # global list leads with rank 0's (non-addressable) device
        dev = device or jax.local_devices()[0]
        S = self.S
        vshape = (S, capacity) if not nbuckets else (S, capacity, nbuckets)
        born_narrow = (born_narrow and not nbuckets and layout is None
                       and dtype == jnp.float32)
        self.ts = self.val = None
        if not born_narrow:
            self.ts = jax.device_put(
                jnp.full((S, capacity), TS_PAD, jnp.int64), dev)
            self.val = jax.device_put(jnp.zeros(vshape, dtype), dev)
        self.extra: dict[str, jax.Array] = {}
        if layout is not None:
            # default col = the schema's value_column (else the histogram
            # col / last col); every other column is a named scalar array
            hist = [nm for nm, _o, _w, ih in layout if ih]
            names = [nm for nm, _o, _w, _ih in layout]
            self.default_col = (default_col if default_col in names
                                else hist[0] if hist else layout[-1][0])
            for nm, _off, _w, is_h in layout:
                if nm != self.default_col:
                    assert not is_h, "only one histogram column per schema"
                    self.extra[nm] = jax.device_put(
                        jnp.zeros((S, capacity), dtype), dev)
        self.n = jax.device_put(jnp.zeros(S, jnp.int32), dev)
        # time-aligned cells (see the text on how the store keeps time):
        # the form of a scalar store that was not born narrow, for as long
        # as it is on the grid. ``born`` is each row's birth cell (host;
        # ``born_dev`` its device twin, uploaded when it changed),
        # ``born_late`` the rows that hold one past 0 — the STATE that
        # picks the kernels' births mode, as ``hole_cells`` picks the hole
        # mode — ``_cell0_off`` the cells compaction has dropped (column
        # 0's stamp is ``grid_base + _cell0_off * interval``) and
        # ``births`` what /metrics counts: rows given a birth cell past 0,
        # and late rows that a form without birth cells took as a minority
        self.aligned = not nbuckets and layout is None and not born_narrow
        self.born = np.zeros(S, np.int32)
        self._born_dev = self._late_mask = None
        self.born_late = 0
        self._cell0_off = 0
        self.births = {"aligned": 0, "minority": 0}
        # host mirrors: ingest-path bookkeeping without device->host syncs
        self.n_host = np.zeros(S, np.int32)
        self.last_ts = np.full(S, -(1 << 62), np.int64)
        self.first_ts = np.full(S, -1, np.int64)
        # scrape-grid tracking: when every series stays aligned to a common
        # (base, interval) grid with contiguous samples, queries take the MXU
        # band-matmul fast path (ops/gridfns.py) instead of per-row searches
        self.grid_base: int | None = None
        self.grid_interval: int | None = None
        self.grid_ok = True
        # the LINE form (see the text at RES_DTYPE): the residual block, the
        # stamp each row's line gives column 0, the rows demoted from their
        # line with their exact stamps in a host pool, and how many rows
        # each reason demoted. A layout (histogram, multi-column) store has
        # no line form: its first off-grid stamp clears grid_ok for the
        # shard, as before
        self.res = None
        self.line0 = np.full(S, -1, np.int64)
        self.off_line = np.zeros(S, bool)
        self._pool_ts = np.zeros((0, capacity), np.int64)
        self._pool_slot = np.full(S, -1, np.int32)
        self._pool_next = 0     # pool slots in use lie below this ...
        self._pool_free: list[int] = []    # ... but for those frees let go
        # what queries off the fused path read of a line store, kept until
        # the next mutation instead of built by each of them: the pool on
        # the device, and the s64 block derived from line + residual
        self._pool_dev = None
        self._line_block = None
        self.demoted = dict.fromkeys(DEMOTE_REASONS, 0)
        self.demoted_last_append = 0
        # holes a row holds among its n_host cells, and their sum (kept as a
        # running one: every query reads it under the shard lock)
        self.holes_host = np.zeros(S, np.int32)
        self.hole_cells = 0
        self.holes_last_append = 0
        self._closed = None     # closed_arrays(), until the next mutation
        self._phases_due = False
        self.tail_holes = np.zeros(S, np.int32)     # the run a row ends in
        # start-cohort summary cache: recomputing per-row offsets per QUERY is
        # an O(S) host pass; starts only change on new series/compact/free
        self._cohorts = None
        # concurrency diagnostics: the shard attaches its lock so donating
        # mutations can assert the locking discipline; the detective records
        # donation provenance for use-after-donation reports
        self.owner_lock = None
        self.detective = diagnostics.DonationDetective()
        self.stats = SeriesStoreStats()
        # backpressure: device mutations are dispatched asynchronously; an
        # unthrottled ingest loop would queue scatters faster than the device
        # retires them, building an unbounded backlog
        # that every query fetch then waits behind — and eventually blocking
        # the dispatcher itself INSIDE the shard lock. Callers drain via
        # throttle() after releasing the lock.
        self._appends_since_sync = 0
        self.max_inflight = 8
        # narrow-RESIDENT state (StoreConfig.narrow_resident /
        # compressed_residency): (kind, ops, pool, pp, slot, ok_host) where
        # kind names the decode variant (ops/decodereg.py: "quant16" |
        # "delta16" | "delta8") and ops its device operands ((q, vmin,
        # scale) or (dv, anchor)). When set, the narrow form IS the only
        # resident value copy — self.val is None and f32 views decode on
        # demand (see compress_resident)
        self._narrow = None
        # ok-contract fallback bookkeeping: when a flush WANTED compression
        # but every encoding failed the contract/cohort gate, the reason
        # ("resets" | "non-integer" | "range") lands here for the flush
        # path's filodb_store_residency_fallback counter — "compressed" and
        # "tried and fell back" must be distinguishable signals
        self.residency_decline: str | None = None
        # cohort-pool gate (StoreConfig.narrow_cohort_gate): the fraction of
        # live rows allowed to fail the ok-contract before raw f32 is the
        # cheaper residency
        self.cohort_gate = 0.25
        # histogram twin: (dd i8/i16 [S,C,B], first_d f32 [S,B], pool, pp,
        # slot, ok_host) — the 2D-delta form of the cumulative bucket block
        # (compressed_residency="all")
        self._nhist = None
        # grid-derived timestamp elision: ts[S, C] freed, derived from
        # (first_ts, n, grid_interval) on demand — the 8B/sample column is
        # redundant on a grid-contiguous store (compress_resident)
        self._ts_elided = False
        # the delta form mutated in place (see the text at DELTA_LIMIT):
        # what the host knows of each row, the pool's rows by slot, and
        # the counts the flush span and /metrics read
        self.last_val = self.ref_val = self.anchor_host = None
        self._slot_host = np.full(S, -1, np.int32)
        self._vpool_rows = np.full(1, S, np.int32)
        self._vpool_next = 0
        self._vpool_free: list[int] = []
        self.pooled_last_append = 0
        self.rehydrated = dict.fromkeys(REHYDRATE_CAUSES, 0)
        self.decode_budget = None   # bytes; None: what the device has free
        if born_narrow:
            put = functools.partial(jax.device_put, device=dev)
            self._narrow = (
                "delta8", (put(jnp.zeros((S, capacity), jnp.int8)),
                           put(jnp.zeros(S, jnp.float32))),
                put(jnp.zeros((1, capacity), jnp.float32)),
                put(jnp.asarray(self._vpool_rows)),
                put(jnp.asarray(self._slot_host)), np.ones(S, bool))
            self._ts_elided = True
            self.last_val = np.zeros(S, np.float32)
            self.ref_val = np.zeros(S, np.float32)
            self.anchor_host = np.zeros(S, np.float32)

    def _pre_donate(self, what: str) -> None:
        """Every buffer-donating mutation funnels through here: assert the
        locking discipline (diagnostics mode) and record provenance."""
        if self.owner_lock is not None:
            diagnostics.assert_owned(self.owner_lock, what)
        self.detective.record(what)
        self._pool_dev = self._line_block = self._closed = None

    # -- narrow-resident lifecycle ------------------------------------------
    #
    # Reference role: the read hot path of the reference keeps values ONLY in
    # compressed form (NibblePack/delta chunks) and decompresses on access
    # (memory/.../format/vectors/DoubleVector.scala:1-60, doc/compression.md)
    # — bytes-per-sample is the capacity lever. TPU analog: after a flush the
    # value column compresses to the narrowest decode variant that carries
    # it bit-exactly (ops/decodereg.py: delta8 anchor+i8 deltas, quant16
    # (q, vmin, scale), delta16) and the f32 array is FREED; rows that don't
    # round-trip bit-exactly keep their raw f32 in a small cohort pool.
    # Appends rehydrate (write buffers stay raw in the reference too); the
    # next flush re-compresses. Queries stream the narrow state in the fused
    # kernel, or decode a transient f32 for general paths.

    def mutation_epoch(self) -> tuple:
        """Changes whenever a donating mutation ran (append/compact/free) —
        the two-phase compression's staleness check."""
        s = self.stats
        return (s.samples_appended, s.compactions, s.frees)

    def _cohort_pool(self, bad: np.ndarray):
        """(pool, pp, slot) for the rows that don't round-trip bit-exactly:
        their raw f32 rows, the padded row-id vector (pads scatter-drop on
        decode), and the per-row pool slot (-1 = quantized) so row-wise
        decodes overlay pool values without touching the full block."""
        Rp = 1
        while Rp < len(bad):
            Rp *= 2
        pp = np.full(Rp, self.S, np.int32)
        pp[:len(bad)] = bad
        pool = jnp.take(self.val, jnp.asarray(np.minimum(pp, self.S - 1)),
                        axis=0)
        slot = np.full(self.S, -1, np.int32)
        slot[bad] = np.arange(len(bad), dtype=np.int32)
        return pool, jnp.asarray(pp), jnp.asarray(slot)

    def _bad_rows(self, ok_host: np.ndarray):
        """Live rows failing the bit-exactness contract, or None when they
        exceed the cohort gate (StoreConfig.narrow_cohort_gate, default 25%
        of live rows — raw f32 is then the cheaper residency)."""
        live = self.n_host > 0
        bad = np.nonzero(live & ~ok_host)[0].astype(np.int32)
        if len(bad) > self.cohort_gate * max(int(live.sum()), 1):
            return None
        return bad

    @staticmethod
    def _majority_reason(live_bad: np.ndarray,
                         reasons: list[tuple[str, np.ndarray]]) -> str:
        """Classify a residency decline: the first reason (in precedence
        order) that explains at least as many failing rows as any later
        one. ``reasons`` maps tag -> per-row failure mask."""
        counts = [(tag, int((live_bad & mask).sum())) for tag, mask in reasons]
        best = max(counts, key=lambda kv: kv[1])
        return best[0] if best[1] else counts[-1][0]

    def _prepare_scalar(self):
        """Narrow scalar residency, narrowest-first: delta8 (1B/sample),
        then quant16 (2B but keeps active-column slicing — see
        ops/decodereg.py full_columns), then delta16 (2B, full columns).
        Counter-shaped rows (large anchor, small integer increments) fail
        the quantized contract but carry exactly in the delta form."""
        from ..ops.narrow import (build_narrow, build_narrow_delta,
                                  cast_narrow_delta_i8)
        dv16, anchor, okd16, okd8, integral = build_narrow_delta(
            self.val, self.n)
        okd8_host = np.asarray(okd8)
        bad = self._bad_rows(okd8_host)
        if bad is not None:
            pool, pp, slot = self._cohort_pool(bad)
            dv8 = cast_narrow_delta_i8(dv16)   # donates/frees the i16 block
            return ("n", ("delta8", (dv8, anchor), pool, pp, slot, okd8_host))
        q, vmin, scale, okq = build_narrow(self.val, self.n)
        okq_host = np.asarray(okq)
        bad = self._bad_rows(okq_host)
        if bad is not None:
            pool, pp, slot = self._cohort_pool(bad)
            return ("n", ("quant16", (q, vmin, scale), pool, pp, slot,
                          okq_host))
        okd16_host = np.asarray(okd16)
        bad = self._bad_rows(okd16_host)
        if bad is not None:
            pool, pp, slot = self._cohort_pool(bad)
            return ("n", ("delta16", (dv16, anchor), pool, pp, slot,
                          okd16_host))
        # every encoding breached the cohort gate: classify for the flush
        # path's fallback counter (non-integer deltas vs integral-but-
        # out-of-range) — mostly continuous floats keep raw f32
        live_bad = (self.n_host > 0) & ~okq_host & ~okd16_host
        integral_host = np.asarray(integral)
        self.residency_decline = self._majority_reason(
            live_bad, [("non-integer", ~integral_host),
                       ("range", integral_host)])
        return None

    def _prepare_hist(self):
        """2D-delta residency for the [S, C, B] bucket block: the narrowest
        signed dtype (i8, then i16) whose bit-exact rows keep the cohort pool
        under the gate wins — quiet histograms' delta-of-deltas are near zero,
        so i8 usually carries them at a quarter of the raw f32 bytes."""
        from ..ops.narrow import build_narrow_hist, cast_narrow_hist_i8
        dd16, first_d, ok16, ok8, mono, exact = build_narrow_hist(
            self.val, self.n)
        ok8_host, ok16_host = np.asarray(ok8), np.asarray(ok16)
        bad8 = self._bad_rows(ok8_host)
        if bad8 is not None:
            dd, bad, ok_host = cast_narrow_hist_i8(dd16), bad8, ok8_host
        else:
            bad16 = self._bad_rows(ok16_host)
            if bad16 is None:
                # mostly inexact/bursty rows: keep raw f32, but say why —
                # counter resets (mono fail) vs non-integer round-trips vs
                # integral-but-out-of-range deltas
                mono_host, exact_host = np.asarray(mono), np.asarray(exact)
                live_bad = (self.n_host > 0) & ~ok16_host
                self.residency_decline = self._majority_reason(
                    live_bad, [("resets", ~mono_host),
                               ("non-integer", mono_host & ~exact_host),
                               ("range", mono_host & exact_host)])
                return None
            dd, bad, ok_host = dd16, bad16, ok16_host
        pool, pp, slot = self._cohort_pool(bad)
        return ("h", (dd, first_d, pool, pp, slot, ok_host))

    def compress_prepare(self, hist: bool = True):
        """Phase 1 (NO lock needed): stream the store into the compressed
        form — quantized scalar values / 2D-delta bucket blocks + cohort
        pool, and the ts-derivability verdict. Pure reads + host fetches; a
        concurrent donating mutation surfaces as RuntimeError (caller retries
        next flush). Returns None when the store/data doesn't qualify
        (multi-column, f64, mostly non-quantizable rows, or a histogram
        store with ``hist=False`` — the shard's residency-mode gate).
        ``residency_decline`` carries the ok-contract failure reason when
        the data itself (not eligibility) caused the None."""
        prep_val = None
        self.residency_decline = None
        if self.born_late:
            # the narrow forms and the derived stamps read every row from
            # column 0: a store that holds a row born late stays raw
            self.residency_decline = "births"
            return None
        if self._narrow is None and self._nhist is None:
            if self.dtype != jnp.float32 or self.val is None:
                return None
            if self.nbuckets:
                # histogram stores compress their DEFAULT [S, C, B] bucket
                # block — the dominant bytes; a multi-column store's named
                # scalar columns (prom-histogram's sum/count) stay raw
                if not hist:
                    return None
                prep_val = self._prepare_hist()
            elif self.layout is None:
                prep_val = self._prepare_scalar()
            else:
                return None   # multi-column scalar stores stay raw
            if prep_val is None:
                return None
        ts_ok = False
        if not self._ts_elided and self.ts is not None \
                and self.grid_info() is not None:
            # the grid invariant guarantees derivability; verify anyway —
            # a silently wrong timestamp block must be impossible
            ts_ok = bool(_verify_ts(self.ts, jnp.asarray(self.first_ts),
                                    self.n, jnp.int64(self.grid_interval),
                                    self.C))
        return (prep_val, ts_ok)

    def compress_commit(self, prep) -> None:
        """Phase 2 (under the shard lock): swap the compressed state in and
        free the raw blocks. Caller verified mutation_epoch() is unchanged."""
        prep_val, ts_ok = prep
        self._pre_donate("SeriesStore.compress_resident")
        if prep_val is not None:
            kind, data = prep_val
            if kind == "h":
                self._nhist = data
            else:
                self._narrow = data
            self.val = None    # the f32 block's HBM is released here
        if ts_ok and not self._ts_elided:
            self.ts = None     # the 8B/sample block's HBM released here
            self._ts_elided = True
        if self._narrow is not None and self._narrow[0] in DELTA_LIMIT \
                and self._ts_elided and self.res is None:
            self._adopt_mirrors()

    def _adopt_mirrors(self) -> None:
        """A store that a rebuild turned into the delta form with elided
        stamps is mutated in place from here on: what the host has to know
        of each row, read off the new state once (two ``[S]`` fetches and
        the pool)."""
        kind, (dv, anchor), pool, pp, slot, ok = self._narrow
        self._narrow = (kind, (dv, anchor), pool, pp, slot, np.array(ok))
        self.anchor_host = np.array(anchor)
        self.ref_val = self.anchor_host.copy()
        last = np.array(_row_last(dv, anchor))
        self._slot_host = np.array(slot)
        self._vpool_rows = np.array(pp)
        held = np.flatnonzero(self._vpool_rows < self.S)
        self._vpool_next, self._vpool_free = len(self._vpool_rows), []
        if len(held) != len(self._vpool_rows):
            self._vpool_next = int(held.max(initial=-1)) + 1
            self._vpool_free = [i for i in range(self._vpool_next)
                                if self._vpool_rows[i] >= self.S]
        if len(held):
            rows = self._vpool_rows[held]
            last[rows] = np.asarray(pool)[
                held, np.maximum(self.n_host[rows] - 1, 0)]
        self.last_val = last

    @property
    def _inplace(self) -> bool:
        """Is this the delta form that appends, ages out and frees as it
        is (see the text at DELTA_LIMIT)?"""
        return (self._narrow is not None and self._narrow[0] in DELTA_LIMIT
                and self._ts_elided and self.res is None
                and self.last_val is not None)

    def _interval(self) -> int:
        """The grid's interval; 1 before a second sample of any row has
        shown it (every row then derives its one stamp alike)."""
        return int(self.grid_interval or 1)

    def check_decode_budget(self, nbytes: int) -> None:
        """Raise ``DecodeRefused`` where a transient of ``nbytes`` (the
        f32 or s64 ``[S, C]`` view of a block this store holds narrow or
        not at all) is more than the device has free — with its working
        copy beside it, half of what is free. A query that asks for it
        fails; the node does not."""
        if not self.is_narrow_resident:
            return
        budget = self.decode_budget
        if budget is None:
            stats = next(iter(self.n.devices())).memory_stats() or {}
            if "bytes_limit" not in stats:
                return              # a backend that does not say (the CPU)
            budget = (stats["bytes_limit"] - stats.get("bytes_in_use", 0)) // 2
        if nbytes > budget:
            raise DecodeRefused(
                f"decoding {nbytes} bytes of a compressed-resident store "
                f"({self.S} x {self.C}) exceeds the device's budget of "
                f"{budget}: narrow the selection")

    @property
    def _val_compressed(self) -> bool:
        return self._narrow is not None or self._nhist is not None

    def compress_resident(self, hist: bool = True) -> bool:
        """One-call form (caller holds the shard lock): adopt the
        compressed-resident state — i16 quantized rows (or i8/i16 2D-delta
        bucket blocks) + raw-f32 cohort pool as the only value copy,
        timestamps elided on grid-contiguous stores. Returns True when
        resident-narrow (already or newly)."""
        if self._val_compressed and (self._ts_elided
                                     or self.grid_info() is None):
            return True
        prep = self.compress_prepare(hist=hist)
        if prep is None:
            return self._val_compressed
        self.compress_commit(prep)
        return self._val_compressed or self._ts_elided

    def _rehydrate(self, cause: str = "append") -> None:
        """Restore the resident f32/i64 blocks (mutations write raw); the
        next compress_resident() re-adopts the compressed state. Counted
        by ``cause`` (REHYDRATE_CAUSES): the delta form on a grid never
        comes here to be appended to, aged out or freed."""
        if not self._val_compressed and not self._ts_elided:
            return
        self.rehydrated[cause] += 1
        self.last_val = None        # the in-place form's mirrors go with it
        self._pre_donate("SeriesStore.rehydrate")
        if self._narrow is not None:
            kind, ops, pool, pp, _slot, _ok = self._narrow
            dec = _decode_narrow if kind == "quant16" else _decode_delta
            self.val = dec(*ops, pool, pp)
            self._narrow = None
        elif self._nhist is not None:
            dd, first_d, pool, pp, _slot, _ok = self._nhist
            self.val = _decode_hist(dd, first_d, pool, pp)
            self._nhist = None
        if self._ts_elided:
            self.ts = _derive_ts(jnp.asarray(self.first_ts), self.n,
                                 jnp.int64(self._interval()), self.C)
            self._ts_elided = False

    def value_block(self):
        """f32 value block: the resident array, or a TRANSIENT decode of the
        narrow state (not retained — capacity stays at the compressed form +
        pool)."""
        if self._narrow is not None:
            kind, ops, pool, pp, _slot, _ok = self._narrow
            dec = _decode_narrow if kind == "quant16" else _decode_delta
            return dec(*ops, pool, pp)
        if self._nhist is not None:
            dd, first_d, pool, pp, _slot, _ok = self._nhist
            return _decode_hist(dd, first_d, pool, pp)
        return self.val

    def ts_block(self):
        """i64 timestamp block: resident, or a TRANSIENT derivation (from
        the grid, or from line + residual with the demoted rows' exact
        stamps laid over it)."""
        if self.res is not None:
            # one derivation per state of the store, not one per query: the
            # next mutation lets it go (``_pre_donate``)
            kept = self._line_block
            if kept is None or kept[0] is not self.res or kept[1] is not self.n:
                kept = self._line_block = (self.res, self.n, self._line_ts())
            return kept[2]
        if not self._ts_elided:
            return self.ts
        return _derive_ts(jnp.asarray(self.first_ts), self.n,
                          jnp.int64(self._interval()), self.C)

    def narrow_operands(self):
        """(kind, operands, ok_host) when narrow-resident, else None — the
        fused kernel's direct-stream form: ``kind`` names the decode variant
        (ops/decodereg.py) and ``operands = (block, *row_operands)`` its
        device arrays ((q, vmin, scale) or (dv, anchor))."""
        if self._narrow is None:
            return None
        kind, ops, _pool, _pp, _slot, ok = self._narrow
        return kind, ops, ok

    def hist_operands(self):
        """(dd, first_d, ok_host) when hist-resident, else None — the narrow
        hist grid kernels' direct-stream operands (ops/gridfns.py *_narrow)."""
        if self._nhist is None:
            return None
        dd, first_d, _pool, _pp, _slot, ok = self._nhist
        return dd, first_d, ok

    @property
    def is_narrow_resident(self) -> bool:
        return self._val_compressed or self._ts_elided

    def resident_value_bytes(self) -> int:
        """Resident HBM bytes of the value state (capacity accounting)."""
        if self._narrow is not None:
            _kind, ops, pool, _pp, _slot, _ok = self._narrow
            return (sum(o.size * o.dtype.itemsize for o in ops)
                    + pool.size * 4)
        if self._nhist is not None:
            dd, first_d, pool, _pp, _slot, _ok = self._nhist
            return (dd.size * dd.dtype.itemsize + first_d.size * 4
                    + pool.size * 4)
        v = self.val
        return 0 if v is None else v.size * v.dtype.itemsize

    def resident_sample_bytes(self) -> int:
        """Total resident HBM of the (ts + value) sample state — the
        retention-per-HBM-byte accounting: ts elision + narrow values take a
        12B/sample f32 store to ~1-2B/sample (delta8 / quant16)."""
        t = 0 if self._ts_elided or self.ts is None \
            else self.ts.size * self.ts.dtype.itemsize
        if self.res is not None:
            t = self.res.size * self.res.dtype.itemsize
        return t + self.resident_value_bytes()

    def resident_bytes_per_sample(self) -> float:
        """``resident_sample_bytes`` over the cells the store holds
        (``S x C``, times the buckets of a histogram): 12 raw, ~1 in the
        delta8 form with elided stamps."""
        return self.resident_sample_bytes() / (
            self.S * self.C * max(self.nbuckets, 1))

    @property
    def rehydrates(self) -> int:
        return sum(self.rehydrated.values())

    # -- ingest -------------------------------------------------------------

    def append(self, part_ids: np.ndarray, ts: np.ndarray, values: np.ndarray) -> int:
        """Batched append of samples (one flush group). Samples must be presented
        in ingest order; per-series out-of-order or over-capacity samples drop
        (reference behavior: TimeSeriesPartition drops out-of-order rows).
        Returns the number of samples actually written."""
        if len(part_ids) == 0:
            return 0
        part_ids = np.asarray(part_ids, np.int32)
        ts = np.asarray(ts, np.int64)
        # stable sort by series, then position within batch = running offset
        order = np.argsort(part_ids, kind="stable")
        r = part_ids[order]
        t = ts[order]
        v = np.asarray(values)[order]
        # out-of-order detection: a sample must exceed both the stored last_ts and
        # the running max of earlier in-batch samples of its series (fast path when
        # nothing violates — the common time-ordered-stream case)
        prev_t = np.concatenate([[0], t[:-1]])
        same_series = np.concatenate([[False], np.diff(r) == 0])
        viol = (t <= self.last_ts[r]) | (same_series & (t <= prev_t))
        keep = ~viol
        if viol.any():
            # slow path: exact per-series running-max filter, only for violators
            for s in np.unique(r[viol]):
                mask = r == s
                tt = t[mask]
                run = self.last_ts[s]
                kk = np.empty(len(tt), bool)
                for i, x in enumerate(tt):
                    kk[i] = x > run
                    if kk[i]:
                        run = x
                keep[mask] = kk
            self.stats.out_of_order_dropped += int((~keep).sum())
            r, t, v = r[keep], t[keep], v[keep]
        # a staleness marker (the scrape failed) is a row, not a sample: it
        # is counted as ingested and leaves a hole in its cell. One that
        # comes before its series' first sample has no line to sit on
        stale = self._markers(v)
        if stale is not None:
            real = np.cumsum(~stale) - ~stale
            first = np.concatenate([[0], np.nonzero(np.diff(r))[0] + 1])
            before = real - np.repeat(
                real[first], np.diff(np.concatenate([first, [len(r)]])))
            lead = stale & (self.n_host[r] == 0) & (before == 0)
            self.stats.stale_markers += int(stale.sum())
            if lead.any():
                r, t, v, stale = r[~lead], t[~lead], v[~lead], stale[~lead]
            if not len(r):
                return 0
        # running occurrence index within the (filtered) sorted batch -> the
        # rows' next columns
        boundaries = np.concatenate([[0], np.nonzero(np.diff(r))[0] + 1])
        occ = np.arange(len(r)) - np.repeat(
            boundaries, np.diff(np.concatenate([boundaries, [len(r)]])))
        cols = self.n_host[r] + occ
        late = None
        if len(r) and self.aligned and self.res is None and self.grid_ok:
            # time-aligned cells: a row this batch starts is written from
            # its birth cell on
            cols, late = self._place_births(r, t, occ, cols, boundaries)
        over = cols >= self.C
        if over.any():
            self.stats.capacity_dropped += int(over.sum())
            r, t, v, cols = r[~over], t[~over], v[~over], cols[~over]
            occ = occ[~over]
            stale = None if stale is None else stale[~over]
        m = len(r)
        if m == 0 and late is None:
            return 0
        inplace = self._inplace
        if inplace and late is not None:
            # the delta form in place reads every row from column 0
            self._rehydrate("births")
            inplace = False
        if not inplace:
            self._rehydrate("append")   # mutations write the raw f32 block
        self._pre_donate("SeriesStore.append")
        if late is not None:
            self._settle_births(*late)
        if m == 0:
            return 0
        # host bookkeeping
        # (the batch is sorted by row: its rows are its runs)
        first_pos = np.flatnonzero(np.concatenate([[True], r[1:] != r[:-1]]))
        uniq = r[first_pos]
        # a row's column is its next one or, on its line, its CELL (see the
        # text at RES_DTYPE): a skipped cell stays behind as a hole
        cols, skipped, gap = self._cells(r, t, cols, uniq, first_pos, stale)
        newly = uniq[self.n_host[uniq] == 0]
        self.first_ts[newly] = t[first_pos[self.n_host[uniq] == 0]]
        self.line0[newly] = self.first_ts[newly]
        if len(newly):
            self._cohorts = None   # new starts can change the cohort summary
            if self.grid_interval and not (self.aligned and self.res is None):
                # a form without birth cells: a row that starts a whole
                # interval or more after the oldest one is a minority
                live = self.n_host > 0
                if live.any():
                    self.births["minority"] += int(
                        (self.first_ts[newly] - self.first_ts[live].min()
                         >= self.grid_interval).sum())
        # line form: the stamp block written below is the residual block
        # (markers and runs past the bound are named only where there are
        # any: a batch without them is held as it ever was)
        marks = {k: a for k, a in (("stale", stale), ("gap", gap))
                 if a is not None}
        res = self._track_stamps(r, t, cols, uniq, first_pos, **marks)
        if inplace and not (self._inplace and self.grid_ok):
            # the batch left the grid (``_to_line``): the store is raw now
            self._rehydrate("off_grid")
            inplace = False
        stamps = t if res is None else res
        if res is not None:
            over = cols >= self.C       # a cell past the row's capacity
            if over.any():
                self.stats.capacity_dropped += int(over.sum())
                r, t, v, cols = r[~over], t[~over], v[~over], cols[~over]
                occ, stamps = occ[~over], stamps[~over]
                stale = None if stale is None else stale[~over]
                m = len(r)
                if m == 0:
                    return 0
            if stale is not None:
                # a marker's value cell: its stamp less the cell's line
                # stamp (a demoted row's pool holds the stamp itself)
                v = np.where(stale, np.where(
                    self.off_line[r], 0,
                    t - (self.line0[r] + cols.astype(np.int64)
                         * self.grid_interval)), v)
            if skipped:
                # the cells a row skipped are written as holes: entries of
                # their own, after the batch's (``occ`` tells a row's apart)
                r, t, v, cols, occ, stamps, stale = self._with_skipped(
                    r, t, v, cols, occ, stamps, stale)
        ingested, m = m, len(r)
        np.maximum.at(self.last_ts, r, t)
        # a row's columns rise along the sorted batch: its last one counts
        if m != ingested:       # entries for skipped cells joined the runs
            first_pos = np.flatnonzero(
                np.concatenate([[True], r[1:] != r[:-1]]))
        last = np.concatenate([first_pos[1:], [m]]) - 1
        moved = (cols[last] + 1 - self.n_host[uniq]).astype(np.int32)
        counts = np.zeros(self.S, np.int32)
        counts[uniq] = moved
        self.holes_last_append = 0
        if res is not None:
            # the cells each row moved on by, less the samples it was given
            given = np.diff(np.concatenate([first_pos, [m]])) \
                if stale is None else np.add.reduceat(~stale, first_pos)
            holes = moved - given.astype(np.int32)
            self.holes_host[uniq] += holes
            self.holes_last_append = int(holes.sum())
            self.hole_cells += self.holes_last_append
            # the run of holes each row ends in now: the entries after its
            # last sample of the batch, or all of them on top of the old run
            if stale is None:
                self.tail_holes[uniq] = 0
            else:
                at = np.maximum.reduceat(
                    np.where(stale, -1, np.arange(m)), first_pos)
                self.tail_holes[uniq] = np.where(
                    at >= 0, last - at,
                    self.tail_holes[uniq] + last - first_pos + 1)
        self.n_host[uniq] += moved
        self.pooled_last_append = 0
        if inplace:
            self._append_delta(r, cols, v, occ, uniq, first_pos, last, counts)
            self.stats.samples_appended += ingested
            self._appends_since_sync += 1
            return ingested
        # pad to bucketed size; padded rows use row index S => dropped by scatter
        P = _pad_size(m)
        v = np.asarray(v)
        rp = np.full(P, self.S, np.int32); rp[:m] = r
        cp = np.zeros(P, np.int32); cp[:m] = cols
        tp = np.zeros(P, stamps.dtype); tp[:m] = stamps
        # split the flat [m, W] ingest row by the schema layout: default
        # column (scalar or histogram span) + named scalar columns
        dv, ev = v, {}
        for nm, off, w, _is_h in self.layout or ():
            colv = v[:, off] if w == 1 else v[:, off:off + w]
            if nm == self.default_col:
                dv = colv
            else:
                ev[nm] = colv
        block = self._stamp_block
        if (int(occ.max()) < DENSE_APPEND_MAX_K
                and block.nbytes + self.val.nbytes >= DENSE_APPEND_BYTES):
            self._append_dense(r, cols, stamps, dv, ev, occ, counts)
        elif self.layout is None:
            vp = np.zeros((P,) + v.shape[1:], v.dtype); vp[:m] = v
            self._stamp_block, self.val, self.n = _scatter_append(
                block, self.val, self.n,
                jnp.asarray(rp), jnp.asarray(cp), jnp.asarray(tp),
                jnp.asarray(vp).astype(self.dtype), jnp.asarray(counts))
        else:
            vp = np.zeros((P,) + dv.shape[1:], dv.dtype); vp[:m] = dv
            evp = {}
            for k, a in ev.items():
                ap = np.zeros(P, a.dtype); ap[:m] = a
                evp[k] = jnp.asarray(ap).astype(self.dtype)
            self.ts, self.val, self.extra, self.n = _scatter_append_multi(
                self.ts, self.val, self.extra, self.n,
                jnp.asarray(rp), jnp.asarray(cp), jnp.asarray(tp),
                jnp.asarray(vp).astype(self.dtype), evp, jnp.asarray(counts))
        self.stats.samples_appended += ingested
        self._appends_since_sync += 1
        return ingested

    def _cell0(self) -> int:
        """The stamp of column 0 of a store in time-aligned cells."""
        return int(self.grid_base) + self._cell0_off * int(self.grid_interval)

    @property
    def born_dev(self):
        """``born`` on the device, i32 [S]: uploaded once a change."""
        if self._born_dev is None:
            self._born_dev = jax.device_put(self.born.copy(),
                                            next(iter(self.n.devices())))
        return self._born_dev

    def late_mask(self) -> np.ndarray:
        """bool [S]: the rows born past the grid's first cell, one pass per
        change of ``born`` (kept beside the device copy)."""
        if self._late_mask is None:
            self._late_mask = self.born > 0
        return self._late_mask

    def _place_births(self, r, t, occ, cols, first_pos):
        """Time-aligned cells: the rows a sorted batch STARTS are written
        from their birth cell — the cell of their first stamp on the
        shard's grid — so from here on they count as rows that have used
        the cells before it (``n_host = born``: every later rule of the
        append, the next column, the line, the phase, holds for them as
        for a row that was always there). Host bookkeeping only; returns
        the batch's columns and, where a row was placed past cell 0, what
        ``_settle_births`` does on the device. The shard's rule for its
        interval is the store's own (``_interval_of``: the first batch that
        shows a second sample of any series): a row that came BEFORE it was
        known sits in column 0 with its one sample and is moved to its cell
        now (``early``). A first stamp that is on no cell of the grid,
        before cell 0 or past the last column (the capacity of such a store
        is a span of TIME) is left as it came: ``_track_stamps`` then turns
        the store to its line form, where a row starts where it starts."""
        if self.grid_base is None:
            self.grid_base = int(t.min())
        uniq = r[first_pos]
        iv = self.grid_interval
        if iv is None:
            iv = self._interval_of(r, t, uniq, first_pos)
            if iv is None or iv <= 0:
                return cols, None
            self.grid_interval = iv
            self._phases_due = True
        cell0 = self._cell0()

        def placed(rows, first):
            """(rows, cells) of those whose first stamp has a cell past 0."""
            d = first - cell0
            b = d // iv
            keep = (d % iv == 0) & (b > 0) & (b < self.C)
            return rows[keep], b[keep].astype(np.int32), keep

        early = early_b = None
        if self._phases_due:
            rows = np.flatnonzero((self.n_host == 1) & (self.line0 != cell0))
            rows, b, _keep = placed(rows, self.line0[rows])
            if len(rows):
                early, early_b = rows, b
                self.born[early] = early_b
                self.n_host[early] = early_b + 1
                self.line0[early] = cell0
        fresh = np.flatnonzero(self.n_host[uniq] == 0)
        rows, b, keep = placed(uniq[fresh], t[first_pos[fresh]])
        if len(rows):
            self.born[rows] = b
            self.n_host[rows] = b
            self.line0[rows] = cell0
            self.first_ts[rows] = t[first_pos[fresh]][keep]
        if early is None and not len(rows):
            return cols, None
        placed = len(rows) + (0 if early is None else len(early))
        self.born_late += placed
        self.births["aligned"] += placed
        self._born_dev = self._late_mask = self._cohorts = None
        return self.n_host[r] + occ, (rows, b, early, early_b)

    def _settle_births(self, rows, b, early, early_b) -> None:
        """The device's part of ``_place_births``, before the batch is
        written: the cells before each birth marked (TS_UNBORN, 0.0 — what
        a reused slot's old owner left there goes), a row that came early
        moved to its cell, and the counts raised by the cells skipped."""
        add = np.zeros(self.S, np.int32)
        for who, cell, carry in ((rows, b, False), (early, early_b, True)):
            if who is None or not len(who):
                continue
            vec = np.zeros(self.S, np.int32)
            vec[who] = cell
            add[who] = cell
            vec = jnp.asarray(vec)
            self.ts = _mark_unborn(self.ts, vec, TS_UNBORN, carry)
            self.val = _mark_unborn(self.val, vec, 0, carry)
        self.n = _add_counts(self.n, jnp.asarray(add))

    def _append_delta(self, r, cols, v, occ, uniq, first_pos, last,
                      counts) -> None:
        """The device's part of an append to the delta form in place: each
        entry's delta against the sample before it — the batch's, or the
        row's last — taken on the host and written as it is, one narrow
        cell a sample (the dense / scatter split is the raw store's, by
        the block's bytes). A row with an entry that does not fit moves
        to the pool first (``_pool_rows``); a pooled row's entries are
        written there, raw."""
        kind, (dv, anchor), pool, pp, slot, ok = self._narrow
        vf = np.asarray(v, np.float32)      # what a raw store would hold
        prev = np.empty_like(vf)
        prev[1:] = vf[:-1]
        prev[first_pos] = self.last_val[uniq]
        start = cols == 0
        if start.any():
            rows0 = r[start]
            self.ref_val[rows0] = self.anchor_host[rows0] = prev[start] = \
                vf[start]
            anchor = jax.device_put(self.anchor_host,
                                    next(iter(dv.devices())))
        v64 = vf.astype(np.float64)     # differences of f32s, exactly
        d = v64 - prev
        with np.errstate(invalid="ignore"):
            fits = ((d == np.rint(d)) & (np.abs(d) <= DELTA_LIMIT[kind])
                    & (np.abs(v64 - self.ref_val[r]) <= PREFIX_MAX))
        self._narrow = (kind, (dv, anchor), pool, pp, slot, ok)
        bad = ~fits & (self._slot_host[r] < 0)
        if bad.any():
            self._pool_rows(np.unique(r[bad]))
            kind, (dv, anchor), pool, pp, slot, ok = self._narrow
        out = self._slot_host[r] >= 0
        if out.any():
            z = int(out.sum())
            P = _pow2_rows(z)
            picks = np.zeros((2, P), np.int32)
            picks[0] = pool.shape[0]        # pads: past the pool, dropped
            picks[0, :z], picks[1, :z] = self._slot_host[r[out]], cols[out]
            vp = np.zeros(P, np.float32)
            vp[:z] = vf[out]
            pool = _pool_write(pool, jnp.asarray(picks[0]),
                               jnp.asarray(picks[1]), jnp.asarray(vp))
            d = np.where(out, 0.0, d)
        dq = d.astype(dv.dtype)
        if (int(occ.max()) < DENSE_APPEND_MAX_K
                and dv.nbytes >= DENSE_APPEND_BYTES):
            for k in range(int(occ.max()) + 1):
                sel = occ == k
                rk = r[sel]
                col = np.full(self.S, -1, np.int32)
                col[rk] = cols[sel]
                new = np.zeros(self.S, dq.dtype)
                new[rk] = dq[sel]
                dv = _dense_set(dv, jnp.asarray(col), jnp.asarray(new))
            n = _add_counts(self.n, jnp.asarray(counts))
        else:
            m = len(r)
            P = _pad_size(m)
            rp = np.full(P, self.S, np.int32); rp[:m] = r
            cp = np.zeros(P, np.int32); cp[:m] = cols
            dp = np.zeros(P, dq.dtype); dp[:m] = dq
            dv, n = _scatter_append_delta(
                dv, self.n, jnp.asarray(rp), jnp.asarray(cp),
                jnp.asarray(dp), jnp.asarray(counts))
        self.n = n
        self._narrow = (kind, (dv, anchor), pool, pp, slot, ok)
        self.last_val[uniq] = vf[last]
        if self.pooled_last_append:
            self._hold_gate()

    def _pool_rows(self, rows: np.ndarray) -> None:
        """Take ``rows`` out of the delta form: their history so far,
        decoded a row at a time (``_pool_admit``: ``len(rows) x C`` f32,
        never the block), goes to pool slots, where their samples are
        written raw from now on."""
        kind, (dv, anchor), pool, pp, slot, ok = self._narrow
        again = [self._vpool_free.pop()
                 for _ in range(min(len(rows), len(self._vpool_free)))]
        fresh = len(rows) - len(again)
        slots = np.asarray(again + list(range(
            self._vpool_next, self._vpool_next + fresh)), np.int32)
        self._vpool_next += fresh
        Rp = pool.shape[0]
        while Rp < self._vpool_next:
            Rp *= 2
        B = _pow2_rows(len(rows))
        picks = np.empty((2, B), np.int32)
        picks[0], picks[1] = self.S, Rp
        picks[0, :len(rows)], picks[1, :len(rows)] = rows, slots
        pool, slot = _pool_admit(dv, anchor, pool, slot, jnp.asarray(picks),
                                 Rp)
        held = np.full(Rp, self.S, np.int32)
        held[:len(self._vpool_rows)] = self._vpool_rows
        held[slots] = rows
        self._vpool_rows = held
        self._slot_host[rows] = slots
        ok[rows] = False
        self._narrow = (kind, (dv, anchor), pool,
                        jax.device_put(held, next(iter(dv.devices()))),
                        slot, ok)
        self.pooled_last_append += len(rows)
        log.info("%d row(s) left the %s form for the raw pool (%d there)",
                 len(rows), kind, int((self._slot_host >= 0).sum()))

    def _hold_gate(self) -> None:
        """More rows in the pool than the cohort gate allows: raw f32 is
        the cheaper residency, as a rebuild would have found (``_bad_rows``)
        — the store declines and the next flush tries the other forms. A
        store too deep to be held raw at all keeps its form and its pool,
        and says so."""
        live = self.n_host > 0
        pooled = int((live & (self._slot_host >= 0)).sum())
        if pooled <= self.cohort_gate * max(int(live.sum()), 1):
            return
        try:
            self.check_decode_budget(self.S * self.C * 12)
        except DecodeRefused:
            log.warning("%d of %d live rows are in the raw pool, past the "
                        "cohort gate, and the store is too deep to be held "
                        "raw: it stays narrow", pooled, int(live.sum()))
            return
        self._rehydrate("cohort_gate")

    def _next_cols(self, r, cols):
        """The column each entry of a sorted batch takes if its row skips
        nothing: one past its row's entry before it, or the row's next."""
        nxt = self.n_host[r].astype(cols.dtype)
        again = np.flatnonzero(r[1:] == r[:-1]) + 1     # few, or none
        nxt[again] = cols[again - 1] + 1
        return nxt

    def _with_skipped(self, r, t, v, cols, occ, res, stale):
        """The batch with one more entry for every cell a row skipped on
        its line (sorted by row still): residual RES_HOLE, value
        HOLE_SKIPPED, and no sample (``stale`` true)."""
        nxt = self._next_cols(r, cols)
        skip = np.where(self.off_line[r], 0, cols - nxt)
        at = np.flatnonzero(skip)
        rows = np.repeat(r[at], skip[at])
        cells = np.concatenate([np.arange(nxt[i], cols[i]) for i in at])
        top = int(occ.max()) + 1
        k = np.concatenate([np.arange(skip[i]) for i in at]) + top
        stale = np.zeros(len(r), bool) if stale is None else stale
        order = np.lexsort((np.concatenate([cols, cells]),
                            np.concatenate([r, rows])))

        def both(a, b):
            return np.concatenate([a, b])[order]
        z = len(rows)
        return (both(r, rows), both(t, np.repeat(t[at], skip[at])),
                both(v, np.full((z,) + v.shape[1:], HOLE_SKIPPED, v.dtype)),
                both(cols, cells), both(occ, k),
                both(res, np.full(z, RES_HOLE, res.dtype)),
                both(stale, np.ones(z, bool)))

    def _append_dense(self, r, cols, t, v, extra, occ, counts) -> None:
        """The flush of a large store (see DENSE_APPEND_BYTES): one donated
        per-row select per block and per in-batch occurrence, instead of
        one scatter over all blocks. ``t`` is what the stamp block takes
        (s64 stamps, or the line form's residuals, written with the values
        by the same select). ``v`` is the default column's values
        ([m] or, for a histogram column, [m, B]); ``extra`` the named
        scalar columns' of a layout store, each block through the same
        select."""
        def rows_of(a, sel, rk):
            out = np.zeros((self.S,) + a.shape[1:], a.dtype)
            out[rk] = a[sel]
            return jnp.asarray(out)

        for k in range(int(occ.max()) + 1):
            sel = occ == k
            rk = r[sel]
            col = np.full(self.S, -1, np.int32); col[rk] = cols[sel]
            col_d = jnp.asarray(col)
            self._stamp_block = _dense_set(self._stamp_block, col_d,
                                           rows_of(t, sel, rk))
            self.val = _dense_set(self.val, col_d, rows_of(v, sel, rk))
            for nm, a in extra.items():
                self.extra[nm] = _dense_set(self.extra[nm], col_d,
                                            rows_of(a, sel, rk))
        self.n = _add_counts(self.n, jnp.asarray(counts))

    def throttle(self) -> None:
        """Bound the in-flight device mutations (call OUTSIDE the shard
        lock): after ``max_inflight`` un-synced appends, block until the
        LATEST scatter retires, so a hot ingest loop runs at the device's
        retirement rate instead of growing a backlog that starves concurrent
        query fetches. Blocks on the current ``n`` output (a queued older
        handle would already be donated/deleted by a newer append); if a
        concurrent append donates it mid-wait, retry on the replacement."""
        if self._appends_since_sync <= self.max_inflight:
            return
        for _ in range(4):
            arr = self.n
            try:
                arr.block_until_ready()
                break
            except Exception:
                if arr is self.n:
                    raise   # a REAL device failure, not a racing donation
                continue    # donated by a racing append: retry on the new n
        self._appends_since_sync = 0

    # -- how the store keeps time (see the text at RES_DTYPE) ----------------

    @property
    def _stamp_block(self):
        """The device block a flush writes stamps into: the s64 block, or
        the line form's residuals."""
        return self.ts if self.res is None else self.res

    @_stamp_block.setter
    def _stamp_block(self, block) -> None:
        if self.res is None:
            self.ts = block
        else:
            self.res = block

    @property
    def stamp_form(self) -> str:
        return "grid" if self.res is None else "line"

    def rows_off_line(self) -> int:
        """Live rows the line kernel skips now (0 in the grid form): what
        the last ``line_info`` found, or, before any query has asked, the
        demoted rows alone. Reads host state only: the metrics page asks
        without the shard lock."""
        summary = self._cohorts
        if summary is not None and summary[0] == "line":
            return len(summary[1][-1])
        return int(self.off_line.sum())

    def _interval_of(self, r, t, uniq, first_pos):
        """The shard's scrape interval, from the first batch that holds a
        second sample of any series: the median step (a late scrape among
        them must not set it)."""
        same = np.concatenate([[False], np.diff(r) == 0])
        existing = self.n_host[uniq] > 0
        d = np.concatenate([np.diff(t)[same[1:]],
                            t[first_pos[existing]]
                            - self.last_ts[uniq[existing]]])
        if not len(d):
            return None
        return int(np.partition(d, len(d) // 2)[len(d) // 2])

    def _markers(self, v):
        """bool [m]: the batch's staleness markers, None where it has none
        (or the store is none that keeps them as holes)."""
        if self.nbuckets or self.layout is not None or v.ndim != 1 \
                or v.dtype != np.float64:
            return None
        stale = v.view(np.uint64) == STALE_NAN_BITS
        return stale if stale.any() else None

    def _cells(self, r, t, dense, uniq, first_pos, stale):
        """(columns, whether a row skips a cell, the entries of rows whose
        holes run past the bound or None) of a sorted batch: a scalar
        store holds a row that is on its line by CELLS, so a stamp that
        lies some cells past the row's next one goes to its own cell and
        leaves holes behind, as long as the RUN of holes it closes — the
        cells it skipped, the markers (``stale``) before it in the batch,
        the run the row ended in — stays within HOLE_RUN_MAX; a marker
        that would lengthen a run past it is held to the same. Every other
        sample — a layout store's, a demoted row's, one that fits no cell
        of its row or closes too long a run (``_track_stamps`` then
        demotes the row), any before the interval is known — goes to its
        row's next column, ``dense``. Sets the interval when this batch is
        the first to show it."""
        if self.nbuckets or self.layout is not None or (
                self.res is None and not self.grid_ok):
            return dense, False, None
        iv = self.grid_interval
        if iv is None:
            iv = self._interval_of(r, t, uniq, first_pos)
            if iv is None or iv <= 0:       # _track_stamps reads the same,
                return dense, False, None   # and says
            self.grid_interval = iv
            self._phases_due = True     # starts recorded before it was known
        l0 = self.line0[r]
        # the scrape every row was waiting for, each in its next cell:
        # nothing to place (the stream's steady state, one multiply a row)
        if len(uniq) == len(r) and dense.all() and (
                np.abs(t - (l0 + dense * iv)) <= RES_MAX).all() and (
                stale is None
                or (self.tail_holes[r[stale]] < HOLE_RUN_MAX).all()):
            return dense, False, None
        fresh = np.flatnonzero(self.n_host[uniq] == 0)
        if len(fresh):              # rows this batch starts: its first stamp
            runs = np.diff(np.concatenate([first_pos, [len(r)]]))
            began = np.zeros(len(uniq), bool)
            began[fresh] = True
            l0 = np.where(np.repeat(began, runs),
                          np.repeat(t[first_pos], runs), l0)
        cell = (t - l0 + iv // 2) // iv
        shift = cell - self._next_cols(r, cell)
        long = self._hole_runs(r, shift, stale, first_pos) > HOLE_RUN_MAX
        misfit = ((shift < 0) | long
                  | (np.abs(t - (l0 + cell * iv)) > RES_MAX))
        off = self.off_line[r]
        gap = None
        if misfit.any():            # one misfit: the whole row's batch
            rows = np.zeros(self.S, bool)
            rows[r[misfit]] = True
            off = off | rows[r]
            if long.any():
                rows[:] = False
                rows[r[long]] = True
                gap = rows[r]
        cols = np.where(off, dense, cell) if off.any() else cell
        return cols, bool(((shift > 0) & ~off).any()), gap

    def _hole_runs(self, r, shift, stale, first_pos):
        """int [m]: the run of holes each entry of a sorted batch closes
        (a sample: the cells it skipped and the holes before them) or
        lengthens (a marker: itself too), counted back to its row's last
        sample — in the batch, or before it (``tail_holes``)."""
        tail = self.tail_holes[r]
        if stale is None and len(first_pos) == len(r):
            return tail + shift             # one sample a row
        m = len(r)
        stale = np.zeros(m, bool) if stale is None else stale
        h = shift + stale                   # holes an entry adds
        upto = np.cumsum(h)
        at = np.arange(m)
        # the row's last sample before the entry, where the batch has one
        prev = np.maximum.accumulate(np.where(stale, -1, at))
        prev = np.concatenate([[-1], prev[:-1]])
        first = np.repeat(first_pos,
                          np.diff(np.concatenate([first_pos, [m]])))
        since = np.where(prev >= first, upto[prev],
                         (upto - h)[first] - tail)
        return upto - since

    def _track_stamps(self, r, t, cols, uniq, first_pos, stale=None,
                      gap=None):
        """Hold each append batch, laid into the columns ``cols``, against
        the rows' lines. In the grid form (every stamp ON its line, every
        line's phase the shard's, no cell skipped, no marker) nothing
        changes hands and None is returned: the s64 block takes the stamps.
        The first batch off that turns a scalar store to its line form,
        once (a layout store: ``grid_ok`` off for the shard, as ever); from
        then on the batch's residuals (int8 [m], ``RES_HOLE`` for a
        staleness marker, ``stale``) are returned for the residual block,
        and a sample that does not fit its column demotes its row, as does
        a run of holes past the bound (``gap``, from ``_cells``)."""
        self.demoted_last_append = 0
        if self.res is None and not self.grid_ok:
            return None
        if self.grid_base is None:
            self.grid_base = int(t.min())
        iv = self.grid_interval
        phases = uniq
        if iv is None:
            iv = self._interval_of(r, t, uniq, first_pos)
            if iv is None:
                return None
            if iv <= 0:
                self.grid_ok = False
                return None
            self.grid_interval = iv
            self._phases_due = True
        if self._phases_due:            # starts recorded before it was known
            phases = np.union1d(uniq, np.flatnonzero(self.n_host > 0))
            self._phases_due = False
        off = t - (self.line0[r] + cols.astype(np.int64) * iv)
        if self.res is None:
            # every line on the shard's phase; in time-aligned cells every
            # line IS the grid's (a row that starts before cell 0 has none)
            stray = (self.line0[phases] != self._cell0() if self.aligned
                     else (self.line0[phases] - self.grid_base) % iv)
            if (not off.any() and stale is None
                    and (cols == self._next_cols(r, cols)).all()
                    and not stray.any()):
                return None
            if self.nbuckets or self.layout is not None:
                self.grid_ok = False
                return None
            moved = self._to_line()
            if moved is not None:       # the rows left their aligned cells
                cols -= moved[r].astype(cols.dtype)
        shift = (off + iv // 2) // iv          # cells off the given column
        fits = (shift == 0) & (np.abs(off) <= RES_MAX)
        if gap is not None:
            fits &= ~gap
        bad = ~fits & ~self.off_line[r]
        if bad.any():
            rows, first = np.unique(r[bad], return_index=True)
            sh = shift[bad][first]
            near = np.abs((off - shift * iv)[bad][first]) <= RES_MAX
            # on the line, in another cell or in its own: a gap (a run of
            # holes past what a line keeps); in its own cell and too far
            # from the line for the width: the residual; neither: the
            # row's interval is not the shard's any more
            reason = np.where(near, "gap", np.where(sh == 0, "residual",
                                                    "interval"))
            self._demote(rows, reason)
        out = self.off_line[r]
        hole = np.zeros(len(r), bool) if stale is None else stale
        if out.any():
            # a marker keeps its stamp in the pool, past TS_PAD
            self._pool_ts[self._pool_slot[r[out]], cols[out]] = \
                t[out] + np.where(hole[out], TS_PAD, 0)
            self._pool_dev = None
        return np.where(hole, RES_HOLE, np.where(out, 0, off)).astype(
            RES_DTYPE)

    def _to_line(self):
        """Grid form -> line form, once: every stamp so far is ON its row's
        line (the grid invariant), so the residual block starts as zeros
        and the s64 block is dropped. Returns the cells each row moved left
        by where the store held rows born late (None: none did)."""
        dev = next(iter(self.n.devices()))
        moved = None
        if self.born_late:
            # out of the time-aligned cells: the line form holds every row
            # from its own column 0, its line starting at its first stamp
            moved = self.born
            S = self.S
            rows = S if S <= BLOCK_ROWS else min(BLOCK_ROWS, S & -S)
            for r0 in range(0, S, rows):
                self.val, self.n = _unalign_block(
                    self.val, self.n, self.born_dev,
                    jax.device_put(np.int32(r0), dev), rows)
            self.n_host = self.n_host - moved
            self.line0 = self.first_ts.copy()
            self.births["minority"] += self.born_late
            self.born = np.zeros(S, np.int32)
            self.born_late, self._born_dev, self._late_mask = 0, None, None
        self.res = jax.device_put(jnp.zeros((self.S, self.C), RES_DTYPE), dev)
        self.ts = None
        self.grid_ok = False
        self._cohorts = None
        log.info("store keeps stamps as line + residual from here on "
                 "(%d rows live, interval %d ms, residual %s)",
                 int((self.n_host > 0).sum()), self.grid_interval,
                 np.dtype(RES_DTYPE).name)
        return moved

    def _demote(self, rows: np.ndarray, reasons: np.ndarray) -> None:
        """Take ``rows`` off their lines: their exact stamps so far (line +
        residual) go to the host pool, where every later stamp of theirs
        is written too."""
        # slots that freed rows let go first, new ones past them
        again = [self._pool_free.pop()
                 for _ in range(min(len(rows), len(self._pool_free)))]
        fresh = len(rows) - len(again)
        have, used = len(self._pool_ts), self._pool_next
        self._pool_next += fresh
        if used + fresh > have:
            grown = np.full((max(2 * have, used + fresh, 8), self.C),
                            TS_PAD, np.int64)
            grown[:have] = self._pool_ts
            self._pool_ts = grown
        self._pool_slot[rows] = again + list(range(used, used + fresh))
        n = self.n_host[rows]
        past = np.asarray(jnp.take(self.res, jnp.asarray(rows), axis=0),
                          np.int64)
        k = np.arange(self.C, dtype=np.int64)[None, :]
        stamps = (self.line0[rows, None] + k * self.grid_interval
                  + np.where(past == RES_HOLE, TS_PAD, past))
        self._pool_ts[self._pool_slot[rows]] = np.where(k < n[:, None],
                                                        stamps, TS_PAD)
        self.off_line[rows] = True
        self._cohorts = self._pool_dev = None
        for why in DEMOTE_REASONS:
            self.demoted[why] += int((reasons == why).sum())
        self.demoted_last_append += len(rows)
        log.info("%d row(s) demoted from their line (%s)", len(rows),
                 ", ".join(sorted(set(reasons.tolist()))))

    def _line_ts(self, rid=None):
        """i64 stamps of a line-form store from line + residual, the
        demoted rows' exact stamps laid over them: the whole block [S, C],
        or the rows ``rid`` (a device id vector) [P, C]."""
        if rid is None:
            line0, slot, n, res = self.line0, self._pool_slot, self.n, self.res
        else:
            rows = np.asarray(rid)
            line0, slot = self.line0[rows], self._pool_slot[rows]
            n, res = jnp.take(self.n, rid), jnp.take(self.res, rid, axis=0)
        ts = _derive_line_ts(jnp.asarray(line0), n,
                             jnp.int64(self.grid_interval), res, self.C)
        out = np.flatnonzero(slot >= 0)
        if len(out):
            if self._pool_dev is None:      # one upload per state
                self._pool_dev = jax.device_put(
                    self._pool_ts[:self._pool_next],
                    next(iter(self.res.devices())))
            ts = ts.at[jnp.asarray(out)].set(
                jnp.take(self._pool_dev, jnp.asarray(slot[out]), axis=0))
        return ts

    def grid_info(self):
        """(base_ts, interval_ms) when the shard stays on a common scrape grid
        (common interval, on-grid timestamps, per-series contiguity), else None.

        Series may START at different grid cells — churn (a new pod appearing
        mid-stream) does not take the shard off the grid. A store in
        time-aligned cells (``aligned``: see the text on how the store keeps
        time) writes such a row from its birth cell, ``born[row]``, and its
        start cohort stays uniform: the query layer runs ONE band program in
        its births mode (``born_late``) and corrects nothing. The other grid
        forms (a layout store, a store born narrow) hold every row from
        column 0: per-series start cells come from :meth:`grid_offsets`, and
        the query layer runs the band-matmul path on the majority start
        cohort, correcting minority rows via the general kernels. Compaction
        shifts every row's offset uniformly, so either rule survives it."""
        if not self.grid_ok or not self.grid_interval:
            return None
        if not (self.n_host > 0).any():
            return None
        return int(self.grid_base), int(self.grid_interval)

    def grid_row_gather(self):
        """``(rows, live, val, n) -> (ts, val, n)`` of a few gathered rows of
        a store in its GRID form (the caller has seen ``grid_info()``), None
        where the stamps are not a resident s64 block. There a row's stamps
        are ``first_ts[row] + k * interval`` for k < n[row] — the grid
        invariant, what ``compress_prepare`` verifies before it elides the
        block — so the gather derives them from the host's ``first_ts`` and
        the block is no operand of its program: on the TPU a program that
        takes the s64 ``[S, C]`` block splits ALL of it into two u32 planes
        (2^20 x 768: 6.4 GB read and 3 GB of temporaries) to hand back
        eight rows. ONE program and one upload a gather (``_gather_grid``):
        a leaf holds the shard lock through every dispatch it makes.
        ``rows``: the pow2-padded row ids on the host, the first ``live``
        real; the pad rows come back with n = 0 and TS_PAD all along."""
        if self.grid_gather_operands(self.column_array()) is None:
            return None

        def gather(rows: np.ndarray, live: int, val, n):
            store_ops, body, _decode = self.grid_gather_operands(val)
            return body(*store_ops[:-1], n,
                        jnp.asarray(self.grid_row_picks(rows, live)))

        return gather

    def grid_gather_operands(self, val):
        """``(store_ops, body, decode)`` of a gather of a few rows of the
        value block ``val`` (``column_array``'s) on the grid, None where
        this store has none (a line store; a quant16 block; stamps neither
        resident nor elided): ``body(*store_ops, picked) -> (ts, val, n)``,
        traceable, ``store_ops`` the device blocks it reads, ``n`` last,
        and ``decode`` what it does to the values on the way — ``raw``, or
        ``delta8`` / ``delta16`` where the block is held in that form and
        the picked rows are decoded inside the program
        (``_gather_grid_delta``). The stamps are derived from the host's
        ``first_ts`` either way."""
        if self.res is not None or (self.ts is None and not self._ts_elided):
            return None
        C = self.C
        if not isinstance(val, _Deferred):
            return ((val, self.n),
                    lambda val, n, picked: _gather_grid(val, n, picked, C),
                    "raw")
        if not isinstance(val, DeferredDecode) or val._arr is not None \
                or self._narrow is None or self._narrow[0] not in DELTA_LIMIT:
            return None
        kind, ops, pool, _pp, slot, _ok = self._narrow
        return ((*ops, pool, slot, self.n),
                lambda dv, anchor, pool, slot, n, picked: _gather_grid_delta(
                    dv, anchor, pool, slot, n, picked, C),
                kind)

    def grid_row_picks(self, rows: np.ndarray, live: int) -> np.ndarray:
        """All that ``_gather_grid`` needs from the host, int64 ``[3, P]``:
        the pow2-padded row ids ``rows``, each row's first stamp (-1 past
        the first ``live``: a pad row) and the interval — and, where the
        store holds a row born late, each row's birth cell. A host array: a
        leaf that composes the gather into its one program
        (query/exec.py ``GatheredRows``) hands it over as that program's
        argument."""
        picked = np.full((4 if self.born_late else 3, len(rows)), -1,
                         np.int64)
        picked[0] = rows
        picked[1, :live] = self.first_ts[rows[:live]]
        picked[2] = self._interval()
        if self.born_late:      # a fourth row: the birth cells
            picked[3] = self.born[rows]
        return picked

    def grid_offsets(self, rows: np.ndarray) -> np.ndarray:
        """Start cell of each given row (the grid cell of its column 0
        relative to ``grid_base``: its first sample's, or, in time-aligned
        cells, the shard's own for every row); 0 for empty rows."""
        first = self.first_ts[rows]
        if self.aligned:        # every row's cells start at the grid's
            return np.where(first >= 0, self._cell0_off, 0).astype(np.int64)
        return np.where(first >= 0,
                        (first - self.grid_base) // self.grid_interval,
                        0).astype(np.int64)

    def grid_cohorts(self):
        """Cached start-cohort summary over live rows: ``("uniform", off)``
        when every live series starts at the same grid cell (the overwhelmingly
        common shape — one scrape cohort), else ``("mixed", offsets[S])``.
        Invalidated whenever starts can move (new series, compaction, frees)."""
        if self._cohorts is None:
            live = self.n_host > 0
            if not live.any():
                self._cohorts = ("uniform", 0)
            elif self.aligned and self.grid_interval:
                # time-aligned cells: uniform by construction
                self._cohorts = ("uniform", self._cell0_off)
            else:
                offs = self.grid_offsets(np.arange(self.S))
                lv = offs[live]
                if (lv == lv[0]).all():
                    self._cohorts = ("uniform", int(lv[0]))
                else:
                    self._cohorts = ("mixed", offs)
        return self._cohorts

    def line_info(self) -> LineInfo | None:
        """The line form's operands for the fused tier, None in the grid
        form. Cached like ``grid_cohorts`` (new series, compaction, frees
        and demotions invalidate it; an append that demotes no row does
        not). The majority are the live rows on their line whose line
        starts within one interval (+ RES_MAX) of ``base_ts``, so that a
        window's first and last cell differ between any two of them by at
        most one; demoted rows and rows that start elsewhere (churn) are
        the minority, answered by the general kernels."""
        if self.res is None or not self.grid_interval:
            return None
        if self._cohorts is None or self._cohorts[0] != "line":
            iv = int(self.grid_interval)
            live = self.n_host > 0
            on = live & ~self.off_line
            a = self.line0 - int(self.grid_base)
            a_q = 0
            if on.any():
                rel = a - a[on].min()
                cells, cnts = np.unique(rel[on] // iv, return_counts=True)
                a_q = int(a[on].min()) + int(cells[np.argmax(cnts)]) * iv
            rel = a - a_q
            major = on & (rel >= 0) & (rel <= iv + RES_MAX)
            off = live & ~major
            start = jax.device_put(
                jnp.asarray(np.clip(rel, -1, iv + RES_MAX + 1), jnp.int32),
                next(iter(self.res.devices())))
            self._cohorts = ("line", (int(self.grid_base) + a_q, iv, start,
                                      off,
                                      np.flatnonzero(off).astype(np.int32)))
        base, iv, start, off, minority = self._cohorts[1]
        # the block as it is NOW: every flush donates and replaces it
        return LineInfo(base, iv, start, self.res, off, minority,
                        self.hole_cells > 0)

    def compact(self, cutoff_ts: int) -> None:
        """Evict samples older than ``cutoff_ts`` (amortized; ref: block reclaim
        by time bucket, BlockManager.scala markBucketedBlocksReclaimable)."""
        if self._inplace:
            return self._compact_delta(int(cutoff_ts))
        self._rehydrate("compact")     # the shift gathers the raw f32 block
        self._pre_donate("SeriesStore.compact")
        if self.aligned and self.res is None and self.grid_ok \
                and self.grid_interval:
            return self._compact_cells(int(cutoff_ts))
        line = self.res is not None
        old_n = self.n_host
        if self.extra:
            self.ts, self.val, self.extra, self.n = _compact_multi(
                self.ts, self.val, self.extra, self.n, jnp.int64(cutoff_ts))
        else:
            # line form: the shift runs over a transient derivation
            new_ts, self.val, self.n = (_compact_line if line else _compact)(
                self._line_ts() if line else self.ts_block(), self.val, self.n,
                jnp.int64(cutoff_ts))
            if not line:
                self.ts = new_ts
        self.n_host = np.array(self.n)  # fresh writable host copy
        if line:
            self._recompact_line(new_ts, old_n - self.n_host)
        else:
            new_first = np.array(self.ts[:, 0])
            self.first_ts = np.where(self.n_host > 0, new_first, -1)
            self.line0 = self.first_ts.copy()
        self._cohorts = None
        self.stats.compactions += 1

    def _compact_cells(self, cutoff_ts: int) -> None:
        """``compact`` in time-aligned cells: the cells before the first
        one at or after ``cutoff_ts`` go, the same number for every row
        (``_compact_aligned``), and ``born`` moves with them."""
        iv = int(self.grid_interval)
        k = int(np.clip(-(-(cutoff_ts - self._cell0()) // iv), 0, self.C))
        if k:
            self.ts, self.val, self.n, born = _compact_aligned(
                self.ts, self.val, self.n, self.born_dev, jnp.int32(k))
            self._cell0_off += k
            n, b = np.maximum(self.n_host - k, 0), np.maximum(self.born - k, 0)
            kept = n > b
            self.n_host = np.where(kept, n, 0).astype(np.int32)
            self.born = np.where(kept, b, 0).astype(np.int32)
            self._born_dev, self._late_mask = born, None
            self.born_late = int((self.born > 0).sum())
            self.first_ts = np.where(
                kept, self._cell0() + self.born.astype(np.int64) * iv, -1)
            self.line0 = np.where(kept, self._cell0(), -1)
        self._cohorts = None
        self.stats.compactions += 1

    def _compact_delta(self, cutoff_ts: int) -> None:
        """``compact`` of the delta form in place: how many cells a row
        drops is the host's to say (its stamps are ``first_ts + k x
        interval``), each row's anchor moves on to the first sample it
        keeps and its deltas shift left — in blocks of ``BLOCK_ROWS``
        rows, donated, so that the temporaries are a block's."""
        self._pre_donate("SeriesStore.compact")
        kind, (dv, anchor), pool, pp, slot, ok = self._narrow
        iv = self._interval()
        live = self.n_host > 0
        k = np.where(live, np.clip(-(-(cutoff_ts - self.first_ts) // iv), 0,
                                   self.n_host), 0).astype(np.int32)
        dev = next(iter(dv.devices()))
        k_d = jax.device_put(k, dev)
        held = self._vpool_rows < self.S
        if held.any():
            rows = np.minimum(self._vpool_rows, self.S - 1)
            pool = _compact_pool(
                pool, jnp.asarray(np.where(held, k[rows], 0)),
                jnp.asarray(np.where(held, self.n_host[rows], 0)))
        S = self.S
        rows = S if S <= BLOCK_ROWS else min(BLOCK_ROWS, S & -S)
        n = self.n
        for r0 in range(0, S, rows):
            dv, anchor, n = _compact_delta_block(
                dv, anchor, n, k_d, jax.device_put(np.int32(r0), dev), rows)
        self.n = n
        self._narrow = (kind, (dv, anchor), pool, pp, slot, ok)
        self.n_host = self.n_host - k
        kept = self.n_host > 0
        self.first_ts = np.where(kept, self.first_ts + k.astype(np.int64) * iv,
                                 -1)
        self.line0 = self.first_ts.copy()
        self.anchor_host = np.array(anchor)
        self.last_val = np.where(kept, self.last_val, 0).astype(np.float32)
        self._cohorts = None
        self.stats.compactions += 1

    def _recompact_line(self, new_ts, dropped: np.ndarray) -> None:
        """After a compaction of a line-form store: every row's line moves
        on by the cells it dropped, its residuals shift with the samples,
        and a demoted row's pool row is read anew."""
        live = self.n_host > 0
        self.line0 = np.where(
            live, self.line0 + dropped.astype(np.int64) * self.grid_interval,
            -1)
        self.first_ts = np.where(live, np.array(new_ts[:, 0]), -1)
        line = _derive_ts(jnp.asarray(self.line0), self.n,
                          jnp.int64(self.grid_interval), self.C)
        fit = jnp.asarray(live & ~self.off_line)[:, None] & (new_ts < TS_PAD)
        self.res = jnp.where(fit, new_ts - line, jnp.where(
            new_ts > TS_PAD, RES_HOLE, 0)).astype(RES_DTYPE)
        real = new_ts < TS_PAD
        self.holes_host = self.n_host - np.asarray(real.sum(axis=1), np.int32)
        self.hole_cells = int(self.holes_host.sum())
        k = jnp.arange(1, self.C + 1, dtype=jnp.int32)[None, :]
        self.tail_holes = self.n_host - np.asarray(
            jnp.where(real, k, 0).max(axis=1), np.int32)
        out = np.flatnonzero(self._pool_slot >= 0)
        if len(out):
            self._pool_ts[self._pool_slot[out]] = np.asarray(
                jnp.take(new_ts, jnp.asarray(out), axis=0))
            self._pool_dev = None

    def free_rows(self, part_ids: np.ndarray) -> None:
        """Release the rows of purged partitions so their slots can be reused
        (ref: TimeSeriesShard partition purge frees the partition's memory).
        Stale val cells stay in HBM but are masked by n=0; the ts rows are
        reset to padding so grid/first-ts scans never see them. Buffers are
        donated in-place — no transient second copy of the [S, C] arrays."""
        if len(part_ids) == 0:
            return
        inplace = self._inplace
        if not inplace:
            self._rehydrate("free")    # the scatter resets the raw ts block
        self.stats.frees += 1
        self._pre_donate("SeriesStore.free_rows")
        m = len(part_ids)
        P = _pad_size(m)
        # padded entries use row S -> dropped by the out-of-bounds scatter mode
        pp = np.full(P, self.S, np.int32)
        pp[:m] = np.asarray(part_ids, np.int32)
        if inplace:
            # n = 0 masks the row's deltas (its next sample starts it anew,
            # at column 0); its pool slot is let go
            kind, ops, pool, vp, slot, ok = self._narrow
            self.n = _zero_counts(self.n, jnp.asarray(pp))
            held = self._slot_host[part_ids]
            held = held[held >= 0]
            if len(held):
                self._vpool_free.extend(held.tolist())
                self._vpool_rows[held] = self.S
                vp = jax.device_put(self._vpool_rows,
                                    next(iter(self.n.devices())))
                slot = _clear_slots(slot, jnp.asarray(pp))
            self._slot_host[part_ids] = -1
            ok[part_ids] = True
            self._narrow = (kind, ops, pool, vp, slot, ok)
            self.last_val[part_ids] = self.ref_val[part_ids] = \
                self.anchor_host[part_ids] = 0
        elif self.res is None:
            self.ts, self.n = _free_rows(self.ts, self.n, jnp.asarray(pp))
        else:       # n = 0 masks the row's residuals; its pool row is let go
            self.n = _zero_counts(self.n, jnp.asarray(pp))
            self.off_line[part_ids] = False
            held = self._pool_slot[part_ids]
            self._pool_free.extend(held[held >= 0].tolist())
            self._pool_slot[part_ids] = -1
        self.hole_cells -= int(self.holes_host[part_ids].sum())
        self.n_host[part_ids] = 0
        self.holes_host[part_ids] = 0
        self.tail_holes[part_ids] = 0
        self.first_ts[part_ids] = -1
        self.line0[part_ids] = -1
        self.last_ts[part_ids] = -(1 << 62)
        late = int((self.born[part_ids] > 0).sum())
        if late:        # a reused slot starts at its next owner's birth cell
            self.born[part_ids] = 0
            self.born_late -= late
            self._born_dev = self._late_mask = None
        self._cohorts = None

    # -- query access -------------------------------------------------------

    def arrays(self, column: str | None = None):
        """(ts[S,C], val, n[S]) device arrays for query kernels; ``column``
        selects a named value column of a multi-column store (None = the
        schema's default column). Compressed-resident stores return deferred
        views (the grid/fused paths plan from shape metadata and never
        materialize; general paths decode transients at exec._dval)."""
        ts = DeferredTs(self) if self.ts is None else self.ts
        return ts, self.column_array(column), self.n

    def column_array(self, column: str | None = None):
        if column is None or column == self.default_col:
            if self._narrow is not None:
                # deferred view: the fused path streams the i16 state and
                # never decodes; general paths materialize a transient f32
                # at their single choke points (query/exec.py _dval)
                return DeferredDecode(self)
            if self._nhist is not None:
                return DeferredDecodeHist(self)
            return self.val
        if column in self.extra:
            return self.extra[column]
        raise KeyError(f"unknown value column {column!r}")

    @property
    def samples_host(self) -> np.ndarray:
        """int32 [S]: the samples each row holds — its used cells less its
        holes; what ``closed_arrays`` / ``snapshot_arrays`` rows are cut
        to."""
        if self.born_late:
            return self.n_host - self.born
        return self.n_host - self.holes_host if self.hole_cells \
            else self.n_host

    def closed_arrays(self, column: str | None = None):
        """(ts, val, n) blocks with every row's samples a sorted prefix,
        for readers that know no holes (the mesh's general programs, the
        per-series loops): the store's own blocks while no cell is a hole,
        else one partition per state of the store (``close_holes``)."""
        v = self.column_array(column)
        if isinstance(v, _Deferred):
            v = v.materialize()
        if self.born_late:
            # time-aligned cells: one shift per state of the store
            kept = self._closed
            if kept is None or kept[0] != column or kept[1] is not self.n:
                kept = self._closed = (column, self.n, _close_births(
                    self.ts_block(), v, self.n, self.born_dev))
            return kept[2]
        if not self.hole_cells:
            return self.ts_block(), v, self.n
        kept = self._closed
        if kept is None or kept[0] != column or kept[1] is not self.res:
            kept = self._closed = (column, self.res,
                                   close_holes(self.ts_block(), v, self.n))
        return kept[2]

    def snapshot_arrays(self, column: str | None = None):
        """(ts, val) blocks materialized ONCE for per-series slicing loops —
        callers iterating many pids must use this instead of per-pid
        series_snapshot (which would re-decode a compressed-resident store's
        full block per series). Row ``p`` holds ``samples_host[p]``
        samples."""
        return self.closed_arrays(column)[:2]

    def series_snapshot(self, part_id: int, column: str | None = None):
        """Host copy of one series (tests/debug; loops use snapshot_arrays)."""
        cnt = int(self.samples_host[part_id])
        t, v = self.snapshot_arrays(column)
        return (np.asarray(t[part_id, :cnt]), np.asarray(v[part_id, :cnt]))
