"""Registration through the write path, history on the device from the seed.

A store that holds what a deployment holds (720 of 768 columns at 2^20
series) would take half an hour to write through the served write path
(~0.4M rows/s, PERF.md PR 22). So only scrape 0 goes that way — it
registers every series the real way: bus -> consumer -> index -> staging ->
flush — and columns 1..fill-1 are written by one donated elementwise
program per block, the shape of ``chunkstore._dense_set`` (which is known
to run in place at this size), from ``datagen.counter``. The host mirrors
are then set to what the write path would have left.

This reaches into ``SeriesStore`` fields; PERF.md lists "a public bulk-load
entry on SeriesStore" under Open questions.
"""

from __future__ import annotations

import functools

import numpy as np

from . import datagen


@functools.lru_cache(maxsize=None)
def _programs():
    import jax
    import jax.numpy as jnp

    def hit_mask(shape, sid, c_lo, c_hi):
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return col, (sid >= 0)[:, None] & (col >= c_lo) & (col < c_hi)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fill_val(block, sid, word, c_lo, c_hi):
        col, hit = hit_mask(block.shape, sid, c_lo, c_hi)
        v = datagen.counter(jnp, word, sid[:, None], col[:1])
        return jnp.where(hit, v.astype(block.dtype), block)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fill_ts(block, sid, iv, c_lo, c_hi):
        col, hit = hit_mask(block.shape, sid, c_lo, c_hi)
        stamp = jnp.int64(datagen.BASE_TS) + col.astype(jnp.int64) * iv
        return jnp.where(hit, stamp, block)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fill_n(n, sid, c_hi):
        return jnp.where(sid >= 0, c_hi, n).astype(n.dtype)

    return fill_val, fill_ts, fill_n


def fill_history(shard, sid: np.ndarray, seed: int, fill_cols: int,
                 iv: int) -> None:
    """Columns 1..fill_cols-1 of every registered row, on the device."""
    import jax
    import jax.numpy as jnp
    from filodb_tpu.core.memstore import EPOCH_AFFECTS_ALL
    fill_val, fill_ts, fill_n = _programs()
    st = shard.store
    if fill_cols > st.C:
        raise RuntimeError(f"fill of {fill_cols} columns into capacity {st.C}")
    live = sid >= 0
    if not (st.n_host[live] == 1).all() or st.n_host[~live].any():
        raise RuntimeError("fill expects exactly scrape 0 in every "
                           "registered row and nothing elsewhere")
    (dev,) = st.val.devices()
    put = functools.partial(jax.device_put, device=dev)
    sid_d = put(jnp.asarray(sid, jnp.int32))
    with shard.lock:
        st._pre_donate("benchmark.fill")
        st.val = fill_val(st.val, sid_d, put(jnp.uint32(datagen.fold_seed(seed))),
                          put(jnp.int32(1)), put(jnp.int32(fill_cols)))
        st.ts = fill_ts(st.ts, sid_d, put(jnp.int64(iv)),
                        put(jnp.int32(1)), put(jnp.int32(fill_cols)))
        st.n = fill_n(st.n, sid_d, put(jnp.int32(fill_cols)))
        jax.block_until_ready((st.val, st.ts, st.n))
        last = datagen.BASE_TS + (fill_cols - 1) * iv
        st.n_host[live] = fill_cols
        st.last_ts[live] = last
        st.grid_interval = iv
        st._cohorts = None
        st.stats.samples_appended += int(live.sum()) * (fill_cols - 1)
        shard.lead_ms = max(shard.lead_ms, last)
        shard.visible_lead_ms = max(shard.visible_lead_ms, last)
        shard._bump_epoch_locked(EPOCH_AFFECTS_ALL)


def check_filled(shard, sid: np.ndarray, fill_cols: int, iv: int) -> None:
    st = shard.store
    live = sid >= 0
    ok = (st.grid_ok and st.grid_info() == (datagen.BASE_TS, iv)
          and (st.n_host[live] == fill_cols).all()
          and not st.n_host[~live].any()
          and int(np.asarray(st.n).sum()) == int(live.sum()) * fill_cols)
    if not ok:
        raise RuntimeError(
            f"shard {shard.shard_num}: store not as the write path would "
            f"have left it: grid_ok={st.grid_ok} grid_info={st.grid_info()} "
            f"n_host={np.unique(st.n_host[live])}")
