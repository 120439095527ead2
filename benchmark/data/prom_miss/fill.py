"""Registration through the write path, history WITH ITS HOLES on the device
from the seed.

As ``prom``'s fill: scrape 0 goes the real way and sets each row's line;
columns 1..fill-1 are then written by one donated elementwise program a
block, in the store's own form for a missed scrape: the cell's residual is
the store's hole mark and its value cell holds the marker's stamp less the
cell's line stamp (the marker comes on schedule, the line started with
scrape 0's lateness: ``-late(s, 0)``), exactly what the write path leaves
for a staleness marker. The host mirrors (cells used, holes a row and the
run a row ends in, the store's hole count, the newest stamp) are set to
what the write path would have left. The store is asked for its hole form FIRST: a program that has
none stops here.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.data.counter.fill import _programs as _counter_programs

from . import datagen


@functools.lru_cache(maxsize=None)
def _programs():
    import jax
    import jax.numpy as jnp
    _fill_val, _fill_ts, fill_n = _counter_programs()

    def cells(shape, sid, word, c_lo, c_hi):
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        hit = (sid >= 0)[:, None] & (col >= c_lo) & (col < c_hi)
        s, k = sid[:, None].astype(jnp.uint32), col[:1].astype(jnp.uint32)
        return hit, s, k, datagen.miss(jnp, word, s, k)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fill_val(block, sid, word, c_lo, c_hi):
        hit, s, k, missed = cells(block.shape, sid, word, c_lo, c_hi)
        v = datagen.counter(jnp, word, s, k).astype(block.dtype)
        mark = -datagen.late(jnp, word, s, jnp.zeros_like(k)).astype(
            jnp.int32)
        return jnp.where(hit, jnp.where(missed, mark.astype(block.dtype), v),
                         block)

    @functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(5,))
    def fill_res(block, sid, word, c_lo, c_hi, hole):
        """stamp - line (``prom``'s residual), the hole mark where the
        scrape failed."""
        hit, s, k, missed = cells(block.shape, sid, word, c_lo, c_hi)
        r = (datagen.late(jnp, word, s, k).astype(jnp.int32)
             - datagen.late(jnp, word, s, jnp.zeros_like(k)).astype(jnp.int32))
        return jnp.where(hit, jnp.where(missed, hole, r).astype(block.dtype),
                         block)

    @functools.partial(jax.jit, static_argnums=(1,))
    def holes_a_row(sid, C, word, c_lo, c_hi):
        hit, _s, _k, missed = cells((sid.shape[0], C), sid, word, c_lo, c_hi)
        return (hit & missed).sum(axis=1).astype(jnp.int32)

    @jax.jit
    def tail_a_row(sid, word, c_hi):
        """The run of missed scrapes each row's filled cells end in."""
        s = sid[:, None].astype(jnp.uint32)
        run = out = (sid >= 0)[:, None]
        for j in range(1, datagen.RUN_MAX + 1):
            k = (c_hi - j).astype(jnp.uint32)[None, None]
            run = run & datagen.miss(jnp, word, s, k) & (c_hi - j >= 1)
            out = run if j == 1 else out + run
        return out[:, 0].astype(jnp.int32)

    @functools.partial(jax.jit, static_argnums=(2,))
    def marked(res, n, hole):
        col = jax.lax.broadcasted_iota(jnp.int32, res.shape, 1)
        return ((res == hole) & (col < n[:, None])).sum(axis=1).astype(
            jnp.int32)

    return fill_val, fill_res, fill_n, holes_a_row, marked, tail_a_row


def hole_form(st):
    """The store's mark for a cell without a sample; raises where the
    program keeps no holes (a commit before it did)."""
    from filodb_tpu.core import chunkstore
    mark = getattr(chunkstore, "RES_HOLE", None)
    if mark is None or not hasattr(st, "holes_host"):
        raise RuntimeError("this store keeps no holes: a missed scrape "
                           "would take its row off the line")
    return int(mark)


def fill_history(shard, sid: np.ndarray, seed: int, fill_cols: int,
                 iv: int) -> None:
    """Columns 1..fill_cols-1 of every registered row, on the device."""
    import jax
    import jax.numpy as jnp
    from filodb_tpu.core.memstore import EPOCH_AFFECTS_ALL
    st = shard.store
    hole = hole_form(st)
    fill_val, fill_res, fill_n, holes_a_row, _marked, tail_a_row = _programs()
    if fill_cols > st.C:
        raise RuntimeError(f"fill of {fill_cols} columns into capacity {st.C}")
    live = sid >= 0
    if not (st.n_host[live] == 1).all() or st.n_host[~live].any():
        raise RuntimeError("fill expects exactly scrape 0 in every "
                           "registered row and nothing elsewhere")
    first = datagen.stamps_np(seed, sid[live], [0], iv)[:, 0]
    if not (st.line0[live] == first).all():
        raise RuntimeError("scrape 0 did not set the rows' lines")
    (dev,) = st.val.devices()
    put = functools.partial(jax.device_put, device=dev)
    sid_d = put(jnp.asarray(sid, jnp.int32))
    word = put(jnp.uint32(datagen.fold_seed(seed)))
    lo, hi = put(jnp.int32(1)), put(jnp.int32(fill_cols))
    with shard.lock:
        st._pre_donate("benchmark.fill")
        # what scrape 1 through the write path would have done first
        st.grid_interval = iv
        if st.res is None:
            st._to_line()
        st.val = fill_val(st.val, sid_d, word, lo, hi)
        st.res = fill_res(st.res, sid_d, word, lo, hi, hole)
        st.n = fill_n(st.n, sid_d, hi)
        holes = np.asarray(holes_a_row(sid_d, st.C, word, lo, hi))
        jax.block_until_ready((st.val, st.res, st.n))
        st.n_host[live] = fill_cols
        st.holes_host[:] = holes
        st.tail_holes[:] = np.asarray(tail_a_row(sid_d, word, hi))
        st.hole_cells = int(holes.sum())
        st.last_ts[live] = datagen.stamps_np(seed, sid[live], [fill_cols - 1],
                                             iv)[:, 0]
        last = int(st.last_ts[live].max())
        st._cohorts = None
        st.stats.samples_appended += int(live.sum()) * (fill_cols - 1)
        st.stats.stale_markers += st.hole_cells
        shard.lead_ms = max(shard.lead_ms, last)
        shard.visible_lead_ms = max(shard.visible_lead_ms, last)
        shard._bump_epoch_locked(EPOCH_AFFECTS_ALL)


def check_filled(shard, sid: np.ndarray, fill_cols: int, iv: int) -> None:
    """Raises unless the store is in its line form WITH holes, no row is
    demoted or off its line, every row uses ``fill_cols`` cells, and the
    cells marked as holes on the device are as many as the host says."""
    st = shard.store
    hole = hole_form(st)
    live = sid >= 0
    form = getattr(st, "stamp_form", "none")
    marked = (int(np.asarray(_programs()[4](st.res, st.n, hole)).sum())
              if form == "line" else -1)
    ok = (form == "line" and st.grid_interval == iv
          and st.hole_cells > 0
          and not any(st.demoted.values()) and st.rows_off_line() == 0
          and (st.n_host[live] == fill_cols).all()
          and not st.n_host[~live].any()
          and int(np.asarray(st.n).sum()) == int(live.sum()) * fill_cols
          and marked == st.hole_cells == int(st.holes_host.sum()) > 0)
    if not ok:
        raise RuntimeError(
            f"shard {shard.shard_num}: store not as the write path would "
            f"have left it: stamps kept as {form}, interval "
            f"{st.grid_interval}, demoted {getattr(st, 'demoted', None)}, "
            f"n_host={np.unique(st.n_host[live])}, holes marked {marked}, "
            f"counted {st.hole_cells}")
