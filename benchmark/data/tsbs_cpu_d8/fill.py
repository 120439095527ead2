"""The history of ``tsbs_cpu_d8`` on the device: the walk's deltas, written
into the int8 block as they are.

Scrape 0 of every series goes through the served write path (it registers
10^6 part keys of eleven labels the real way, and the store's own append
sets each row's anchor to its first value). Scrapes 1..fill-1 are walked on
the device as ``tsbs_cpu`` walks them — one ``lax.scan`` over the scrapes a
block of ``ROWS`` rows, the block's state its carry — but what a step
leaves behind is its DELTA, an int8, and the ``[scrapes, ROWS]`` result is
turned and written into the donated ``dv`` block at the block's rows: 0.29
GB a copy of it at 4,415 scrapes, beside 4.83 GB resident (an f32 block of
the walk would be 19 GB). The walk's last state, int32 a row, comes back to
the host: it is the rows' last value (the append's mirror) and the
scraper's state (``__init__.fill``). The host mirrors are then set to what
the write path would have left.

This reaches into ``SeriesStore`` fields, as ``counter``'s fill does.
"""

from __future__ import annotations

import functools

import numpy as np

from ..tsbs_cpu import datagen

ROWS = 1 << 16        # rows walked by one program (fewer in a smaller store)
MAX_B_PER_SAMPLE = 1.05


@functools.lru_cache(maxsize=None)
def _programs(fill_cols: int, rows: int):
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def walk_rows(dv, sid, word, r0):
        """Deltas of columns 1..fill_cols-1 of rows r0..r0+rows-1 from the
        law, and the walk's last state; a row without a series (sid < 0)
        keeps what it holds."""
        s = jax.lax.dynamic_slice(sid, (r0,), (rows,))
        su = s.astype(jnp.uint32)
        x0 = datagen.start_of(jnp, word, su)

        def one(x, k):
            nxt = datagen.advance(jnp, x, datagen.step_of(jnp, word, su, k))
            return nxt, (nxt - x).astype(dv.dtype)

        last, ds = jax.lax.scan(one, x0,
                                jnp.arange(1, fill_cols, dtype=jnp.uint32))
        one_col = jnp.ones((), r0.dtype)
        old = jax.lax.dynamic_slice(dv, (r0, one_col), (rows, fill_cols - 1))
        new = jnp.where((s >= 0)[:, None], ds.T, old)
        return jax.lax.dynamic_update_slice(dv, new, (r0, one_col)), last

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fill_n(n, sid, c_hi):
        return jnp.where(sid >= 0, c_hi, n).astype(n.dtype)

    return walk_rows, fill_n


def fill_history(shard, sid: np.ndarray, seed: int, fill_cols: int,
                 iv: int) -> np.ndarray:
    """Scrapes 1..fill_cols-1 of every registered row, on the device, in
    the store's narrow form. Returns int32 [S]: each row's value at scrape
    ``fill_cols - 1`` (0 for a row without a series)."""
    import jax
    import jax.numpy as jnp
    from filodb_tpu.core.memstore import EPOCH_AFFECTS_ALL
    st = shard.store
    if not st._inplace or st._narrow[0] != "delta8":
        raise RuntimeError(
            "tsbs_cpu_d8: the store is not in its delta8 form with elided "
            "stamps after registration (store.compressed_residency is "
            f"{shard.config.residency_mode()!r}, rehydrated "
            f"{st.rehydrated}): refused before the fill")
    if fill_cols > st.C:
        raise RuntimeError(f"fill of {fill_cols} columns into capacity {st.C}")
    live = sid >= 0
    if not (st.n_host[live] == 1).all() or st.n_host[~live].any():
        raise RuntimeError("fill expects exactly scrape 0 in every "
                           "registered row and nothing elsewhere")
    rows = min(ROWS, len(sid))      # a store smaller than a block: one block
    walk_rows, fill_n = _programs(int(fill_cols), rows)
    kind, (dv, anchor), pool, pp, slot, ok = st._narrow
    (dev,) = dv.devices()
    put = functools.partial(jax.device_put, device=dev)
    sid_d = put(jnp.asarray(sid, jnp.int32))
    word = put(jnp.uint32(datagen.fold_seed(seed)))
    last = np.zeros(len(sid), np.int32)
    with shard.lock:
        st._pre_donate("benchmark.fill")
        for r0 in range(0, len(sid), rows):
            r0 = min(r0, len(sid) - rows)       # the last block may overlap
            dv, x = walk_rows(dv, sid_d, word, put(jnp.int32(r0)))
            last[r0:r0 + rows] = np.asarray(x)
        st._narrow = (kind, (dv, anchor), pool, pp, slot, ok)
        st.n = fill_n(st.n, sid_d, put(jnp.int32(fill_cols)))
        jax.block_until_ready((dv, st.n))
        last = np.where(live, last, 0).astype(np.int32)
        head = datagen.BASE_TS + (fill_cols - 1) * iv
        st.n_host[live] = fill_cols
        st.last_ts[live] = head
        st.last_val[live] = last[live]
        st.grid_interval = iv
        st._cohorts = None
        st.stats.samples_appended += int(live.sum()) * (fill_cols - 1)
        shard.lead_ms = max(shard.lead_ms, head)
        shard.visible_lead_ms = max(shard.visible_lead_ms, head)
        shard._bump_epoch_locked(EPOCH_AFFECTS_ALL)
    return last


def check_filled(shard, sid: np.ndarray, fill_cols: int, iv: int) -> set:
    """Raises unless the store is as the write path would have left it IN
    ITS NARROW FORM: delta8, stamps elided, no row in the raw pool, never
    rehydrated, ``fill_cols`` samples a row on the grid, under
    ``MAX_B_PER_SAMPLE`` bytes a cell (+ 8 B a row), and every row's deltas summing to
    the last value the append's mirror holds. Returns the block's devices."""
    from filodb_tpu.core import chunkstore
    st = shard.store
    live = sid >= 0
    facts = {
        "inplace": bool(st._inplace), "form": (st._narrow or ("raw",))[0],
        "stamps_elided": st.ts is None and st._ts_elided,
        "pooled": int((st._slot_host >= 0).sum()),
        "rehydrates": st.rehydrates, "grid_ok": st.grid_ok,
        "grid_info": st.grid_info(),
        "n_host": np.unique(st.n_host[live]).tolist(),
        "strays": int(st.n_host[~live].sum()),
        "bytes_per_sample": round(st.resident_bytes_per_sample(), 4)}
    want = dict(facts, inplace=True, form="delta8", stamps_elided=True,
                pooled=0, rehydrates=0, grid_ok=True,
                grid_info=(datagen.BASE_TS, iv), n_host=[fill_cols], strays=0)
    # a row's anchor and the empty pool's one row beside the block: 0.002 B
    # a cell at 4,608 columns
    ok = (facts == want
          and facts["bytes_per_sample"] < MAX_B_PER_SAMPLE + 8 / st.C
          and int(np.asarray(st.n).sum()) == int(live.sum()) * fill_cols)
    if ok:
        dv, anchor = st._narrow[1]
        summed = np.asarray(chunkstore._row_last(dv, anchor))
        ok = bool((summed[live] == st.last_val[live]).all())
        facts["sums"] = "anchor + sum(dv) == last value" if ok else \
            "anchor + sum(dv) != the host's last value in some row"
    if not ok:
        raise RuntimeError(
            f"shard {shard.shard_num}: store not as the write path would "
            f"have left it in its narrow form: {facts}")
    return set(st._narrow[1][0].devices())
