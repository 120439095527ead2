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

from . import datagen, served


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


def pid_series(shard, ids: np.ndarray, seed: int) -> np.ndarray:
    """[S] series id held by each store row, -1 for unused rows. Scrape 0
    registers a shard's series in the order published; a seeded sample of
    rows is checked against the index's own labels."""
    st = shard.store
    if shard.num_series != len(ids):
        raise RuntimeError(f"shard {shard.shard_num}: registered "
                           f"{shard.num_series} of {len(ids)} series")
    sid = np.full(st.S, -1, np.int64)
    sid[:len(ids)] = ids
    rng = np.random.default_rng(seed)
    rows = np.unique(np.concatenate(
        [[0, len(ids) - 1], rng.integers(0, len(ids), 254)]))
    for p in rows:
        host = shard.index.labels_of(int(p)).get("host")
        if host != f"h{sid[p]}":
            raise RuntimeError(f"shard {shard.shard_num}: row {p} holds "
                               f"{host}, expected h{sid[p]}")
    return sid


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


def build(srv, deploy: dict, seed: int) -> dict:
    """Register, fill and check every shard. Returns {"writers", "sids"
    (sorted, the series written), "sid_of" {shard: [S]}, "seconds" {...}}."""
    import time
    nsh = int(deploy["server"]["num_shards"])
    per = int(deploy["server"]["store"]["max_series_per_shard"])
    n_series = int(deploy["series"])
    iv = int(deploy["scrape_interval_ms"])
    fill_cols = int(deploy["fill_columns"])
    dataset = srv.config["dataset"]
    t0 = time.perf_counter()
    if nsh == 1:
        ids_of = [np.arange(n_series)]
    else:
        # hashing spreads the series a little unevenly; a shard holds
        # ``per`` at most, so the overflow of the fuller shards is not
        # written (nor counted in the reference)
        owner = served.owners(srv, n_series, deploy)
        ids_of = [np.flatnonzero(owner == sh)[:per] for sh in range(nsh)]
    writers = [served.Writer(srv, sh, ids_of[sh], deploy) for sh in range(nsh)]
    t1 = time.perf_counter()
    for w in writers:
        for j in range(len(w.templates)):
            w.publish(j, 0, seed)
    for w in writers:
        w.drain()
    t2 = time.perf_counter()
    sid_of = {}
    homes = set()
    for w in writers:
        sid = pid_series(w.shard, w.ids, seed)
        fill_history(w.shard, sid, seed, fill_cols, iv)
        check_filled(w.shard, sid, fill_cols, iv)
        sid_of[w.shard_num] = sid
        homes |= set(w.shard.store.val.devices())
        if w.shard.store.ts.devices() != w.shard.store.val.devices():
            raise RuntimeError(f"shard {w.shard_num}: ts/val on two devices")
    if len(homes) != nsh:
        raise RuntimeError(f"{nsh} shards sit on {len(homes)} device(s)")
    t3 = time.perf_counter()
    written = np.sort(np.concatenate([w.ids for w in writers]))
    served.log(f"fill: {len(written)} of {n_series} series over {nsh} "
               f"shard(s) {[len(w.ids) for w in writers]}; templates "
               f"{t1 - t0:.1f} s, registration (scrape 0 through the write "
               f"path) {t2 - t1:.1f} s, {fill_cols - 1} columns on the "
               f"device {t3 - t2:.1f} s; dataset {dataset}")
    return {"writers": writers, "sids": written, "sid_of": sid_of,
            "seconds": {"templates": t1 - t0, "registration": t2 - t1,
                        "device_fill": t3 - t2}}
