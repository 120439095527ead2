"""Registration through the write path, history on the device from the seed.

As ``counter``'s fill: only scrape 0 goes through the served write path —
it registers every series the real way, creates the store with the
container's ``bucket_les`` and leaves column 0 as the write path writes it —
and scrapes 1..fill-1 are written on the device from ``datagen.columns``.
The bucket block is ``[S, C, B]`` (6.4 GB at 2^15 x 768 x 64) and its
generator cumulates over ``B``, so one program over the whole block would
hold the block twice: the history goes in ROW BLOCKS, each a donated
read-modify-write of its rows in the bucket block and in the ``sum`` and
``count`` columns' blocks (in place; the temporaries are a block's, 0.2 GB
at 1,024 rows). The host mirrors are then set to what the write path would
have left.

This reaches into ``SeriesStore`` fields; PERF.md lists "a public bulk-load
entry on SeriesStore" under Open questions.
"""

from __future__ import annotations

import functools

import numpy as np

from . import datagen

ROWS = 1024         # store rows a program


@functools.lru_cache(maxsize=None)
def _programs():
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2),
                       static_argnames=("rows",))
    def fill_rows(val, sums, counts, sid, row0, word, c_lo, c_hi, *, rows):
        C, nb = val.shape[1], val.shape[2]
        col = jnp.arange(C, dtype=jnp.int32)
        su, cn, h = datagen.columns(jnp, word, sid[:, None], col[None, :], nb)
        hit = (sid >= 0)[:, None] & (col >= c_lo)[None, :] \
            & (col < c_hi)[None, :]

        def put(block, new, hit):
            at = (row0,) + (jnp.int32(0),) * (block.ndim - 1)
            cur = jax.lax.dynamic_slice(block, at, (rows,) + block.shape[1:])
            return jax.lax.dynamic_update_slice(
                block, jnp.where(hit, new.astype(block.dtype), cur), at)

        return (put(val, h, hit[:, :, None]), put(sums, su, hit),
                put(counts, cn, hit))

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fill_ts(block, sid, iv, c_lo, c_hi):
        col = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
        hit = (sid >= 0)[:, None] & (col >= c_lo) & (col < c_hi)
        stamp = jnp.int64(datagen.BASE_TS) + col.astype(jnp.int64) * iv
        return jnp.where(hit, stamp, block)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fill_n(n, sid, c_hi):
        return jnp.where(sid >= 0, c_hi, n).astype(n.dtype)

    return fill_rows, fill_ts, fill_n


def fill_history(shard, sid: np.ndarray, seed: int, fill_cols: int,
                 iv: int) -> None:
    """Scrapes 1..fill_cols-1 of every registered row, on the device."""
    import jax
    import jax.numpy as jnp
    from filodb_tpu.core.memstore import EPOCH_AFFECTS_ALL
    fill_rows, fill_ts, fill_n = _programs()
    st = shard.store
    if fill_cols > st.C:
        raise RuntimeError(f"fill of {fill_cols} columns into capacity {st.C}")
    if st.val.ndim != 3 or sorted(st.extra) != ["count", "sum"]:
        raise RuntimeError(
            f"fill expects a prom-histogram store: a bucket block and the "
            f"columns sum, count; got val{tuple(st.val.shape)} and "
            f"{sorted(st.extra)}")
    live = sid >= 0
    if not (st.n_host[live] == 1).all() or st.n_host[~live].any():
        raise RuntimeError("fill expects exactly scrape 0 in every "
                           "registered row and nothing elsewhere")
    (dev,) = st.val.devices()
    put = functools.partial(jax.device_put, device=dev)
    rows = min(ROWS, st.S)
    if st.S % rows:
        raise RuntimeError(f"{st.S} store rows are no whole blocks of {rows}")
    word = put(jnp.uint32(datagen.fold_seed(seed)))
    c_lo, c_hi = put(jnp.int32(1)), put(jnp.int32(fill_cols))
    with shard.lock:
        st._pre_donate("benchmark.fill")
        for row0 in range(0, st.S, rows):
            if not live[row0:row0 + rows].any():
                continue
            st.val, st.extra["sum"], st.extra["count"] = fill_rows(
                st.val, st.extra["sum"], st.extra["count"],
                put(jnp.asarray(sid[row0:row0 + rows], jnp.int32)),
                put(jnp.int32(row0)), word, c_lo, c_hi, rows=rows)
        sid_d = put(jnp.asarray(sid, jnp.int32))
        st.ts = fill_ts(st.ts, sid_d, put(jnp.int64(iv)), c_lo, c_hi)
        st.n = fill_n(st.n, sid_d, c_hi)
        jax.block_until_ready((st.val, st.extra, st.ts, st.n))
        last = datagen.BASE_TS + (fill_cols - 1) * iv
        st.n_host[live] = fill_cols
        st.last_ts[live] = last
        st.grid_interval = iv
        st._cohorts = None
        st.stats.samples_appended += int(live.sum()) * (fill_cols - 1)
        shard.lead_ms = max(shard.lead_ms, last)
        shard.visible_lead_ms = max(shard.visible_lead_ms, last)
        shard._bump_epoch_locked(EPOCH_AFFECTS_ALL)


def check_filled(shard, sid: np.ndarray, fill_cols: int, iv: int,
                 nb: int) -> None:
    st = shard.store
    live = sid >= 0
    les = getattr(shard, "bucket_les", None)
    ok = (st.grid_ok and st.grid_info() == (datagen.BASE_TS, iv)
          and (st.n_host[live] == fill_cols).all()
          and not st.n_host[~live].any()
          and int(np.asarray(st.n).sum()) == int(live.sum()) * fill_cols
          and les is not None
          and np.array_equal(np.asarray(les), datagen.bucket_les(nb)))
    if not ok:
        raise RuntimeError(
            f"shard {shard.shard_num}: store not as the write path would "
            f"have left it: grid_ok={st.grid_ok} grid_info={st.grid_info()} "
            f"n_host={np.unique(st.n_host[live])} bucket_les={les}")
