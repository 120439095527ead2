"""Registration through the write path, history on the device from the seed.

As ``counter``'s fill (a store that holds 720 of 768 columns at 2^20 series
would take half an hour through the served write path): scrape 0 goes the
real way — bus, consumer, index, staging, flush — and so sets each row's
line (its first stamp: the target's phase, and that scrape's lateness).
Columns 1..fill-1 are then written by one donated elementwise program a
block: the values, and the residuals ``stamp - (line0 + c * interval)`` into
the store's int8 block, which the second scrape of a real stream would have
turned on (``SeriesStore._to_line``: the stamps are off any common grid from
the first one on). The host mirrors are set to what the write path would
have left.

This reaches into ``SeriesStore`` fields; PERF.md lists "a public bulk-load
entry on SeriesStore" under Open questions.
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

    # the values and the counts are counter's programs; the stamps are
    # this module's: residuals into the int8 block, not stamps into s64
    fill_val, _fill_ts, fill_n = _counter_programs()

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fill_res(block, sid, word, c_lo, c_hi):
        """stamp - line: both hold the row's phase, so what is left is this
        scrape's lateness less scrape 0's (the line starts at ITS stamp)."""
        col = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
        hit = (sid >= 0)[:, None] & (col >= c_lo) & (col < c_hi)
        s = sid[:, None]
        r = (datagen.late(jnp, word, s, col[:1]).astype(jnp.int32)
             - datagen.late(jnp, word, s, jnp.zeros_like(col[:1]))
             .astype(jnp.int32))
        return jnp.where(hit, r.astype(block.dtype), block)

    return fill_val, fill_res, fill_n


def fill_history(shard, sid: np.ndarray, seed: int, fill_cols: int,
                 iv: int) -> None:
    """Columns 1..fill_cols-1 of every registered row, on the device."""
    import jax
    import jax.numpy as jnp
    from filodb_tpu.core.memstore import EPOCH_AFFECTS_ALL
    fill_val, fill_res, fill_n = _programs()
    st = shard.store
    if not hasattr(st, "line0"):
        raise RuntimeError("this store has no line form: it cannot hold "
                           "stamps that are on no common grid")
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
    with shard.lock:
        st._pre_donate("benchmark.fill")
        # what scrape 1 through the write path would have done first
        st.grid_interval = iv
        if st.res is None:
            st._to_line()
        st.val = fill_val(st.val, sid_d, word, put(jnp.int32(1)),
                          put(jnp.int32(fill_cols)))
        st.res = fill_res(st.res, sid_d, word, put(jnp.int32(1)),
                          put(jnp.int32(fill_cols)))
        st.n = fill_n(st.n, sid_d, put(jnp.int32(fill_cols)))
        jax.block_until_ready((st.val, st.res, st.n))
        st.n_host[live] = fill_cols
        st.last_ts[live] = datagen.stamps_np(seed, sid[live], [fill_cols - 1],
                                             iv)[:, 0]
        last = int(st.last_ts[live].max())
        st._cohorts = None
        st.stats.samples_appended += int(live.sum()) * (fill_cols - 1)
        shard.lead_ms = max(shard.lead_ms, last)
        shard.visible_lead_ms = max(shard.visible_lead_ms, last)
        shard._bump_epoch_locked(EPOCH_AFFECTS_ALL)


def check_filled(shard, sid: np.ndarray, fill_cols: int, iv: int) -> None:
    st = shard.store
    live = sid >= 0
    form = getattr(st, "stamp_form", "none")
    ok = (form == "line" and st.grid_interval == iv
          and not any(st.demoted.values()) and st.rows_off_line() == 0
          and (st.n_host[live] == fill_cols).all()
          and not st.n_host[~live].any()
          and int(np.asarray(st.n).sum()) == int(live.sum()) * fill_cols)
    if not ok:
        raise RuntimeError(
            f"shard {shard.shard_num}: store not as the write path would "
            f"have left it: stamps kept as {form}, interval "
            f"{st.grid_interval}, demoted {getattr(st, 'demoted', None)}, "
            f"n_host={np.unique(st.n_host[live])}")
