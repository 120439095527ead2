"""Registration through the write path, history on the device from the seed.

Scrape 0 of every series goes through the served write path (it registers
10^6 part keys of eleven labels the real way); scrapes 1..fill-1 are written
on the device. The value law is a WALK, so a column is a function of the
column before it: the history is walked a block of ``ROWS`` rows at a time —
one ``lax.scan`` over the scrapes with the block's state as its carry, the
``[scrapes, ROWS]`` result turned and written into the donated value block
at the block's rows (0.19 GB of temporaries a block at 768 columns; the
whole store in one program would hold 6 GB beside 9.66 resident). Stamps
and counts are ``counter``'s elementwise programs (imported, not copied),
one each over the whole block, and so is the check. The host mirrors are then set to what the write path would have
left.

This reaches into ``SeriesStore`` fields, as ``counter``'s fill does.
"""

from __future__ import annotations

import functools

import numpy as np

from ..counter.fill import _programs as counter_programs
from ..counter.fill import check_filled  # noqa: F401 — the same layout, the same grid
from . import datagen

ROWS = 1 << 16        # rows walked by one program (fewer in a smaller store)


@functools.lru_cache(maxsize=None)
def _programs(fill_cols: int, rows: int):
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def walk_rows(block, sid, word, r0):
        """Columns 1..fill_cols-1 of rows r0..r0+rows-1 from the law; a row
        without a series (sid < 0) keeps what it holds."""
        s = jax.lax.dynamic_slice(sid, (r0,), (rows,))
        su = s.astype(jnp.uint32)
        x0 = datagen.start_of(jnp, word, su)

        def one(x, k):
            x = datagen.advance(jnp, x, datagen.step_of(jnp, word, su, k))
            return x, x

        _, xs = jax.lax.scan(one, x0,
                             jnp.arange(1, fill_cols, dtype=jnp.uint32))
        one_col = jnp.ones((), r0.dtype)
        old = jax.lax.dynamic_slice(block, (r0, one_col),
                                    (rows, fill_cols - 1))
        new = jnp.where((s >= 0)[:, None], xs.T.astype(block.dtype), old)
        return jax.lax.dynamic_update_slice(block, new, (r0, one_col))

    return walk_rows


def fill_history(shard, sid: np.ndarray, seed: int, fill_cols: int,
                 iv: int) -> None:
    """Scrapes 1..fill_cols-1 of every registered row, on the device."""
    import jax
    import jax.numpy as jnp
    from filodb_tpu.core.memstore import EPOCH_AFFECTS_ALL
    st = shard.store
    if not hasattr(st, "grid_row_gather"):
        # the parent commit: its narrow leaf takes the s64 [S, C] stamp block
        # as an operand of a gather of eight rows, and the TPU splits ALL of
        # it into two u32 planes a query (at 2^20 x 768: 6.4 GB read, 6.4
        # written; my chip run, PR 41: 9 queries/s, p50 727 ms)
        raise RuntimeError(
            "tsbs_cpu: this store cannot gather a few rows without taking "
            "its whole stamp block (no SeriesStore.grid_row_gather): the "
            "deployment's queries select 1 or 8 series of 10^6; refused "
            "before the fill")
    rows = min(ROWS, len(sid))      # a store smaller than a block: one block
    walk_rows = _programs(int(fill_cols), rows)
    _, fill_ts, fill_n = counter_programs()
    if fill_cols > st.C:
        raise RuntimeError(f"fill of {fill_cols} columns into capacity {st.C}")
    live = sid >= 0
    if not (st.n_host[live] == 1).all() or st.n_host[~live].any():
        raise RuntimeError("fill expects exactly scrape 0 in every "
                           "registered row and nothing elsewhere")
    (dev,) = st.val.devices()
    put = functools.partial(jax.device_put, device=dev)
    sid_d = put(jnp.asarray(sid, jnp.int32))
    word = put(jnp.uint32(datagen.fold_seed(seed)))
    with shard.lock:
        st._pre_donate("benchmark.fill")
        for r0 in range(0, len(sid), rows):
            r0 = min(r0, len(sid) - rows)       # the last block may overlap
            st.val = walk_rows(st.val, sid_d, word, put(jnp.int32(r0)))
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
