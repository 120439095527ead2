"""Registration of every series through the write path, at the stamp it is
born; the history on the device from the law.

Scrape 0 of every slot went through the served write path
(``served.build``). The store then has to know its interval before it can
give a late series its birth cell — its own rule is the first batch that
shows a second sample of any series (``SeriesStore._interval_of``) — so
scrape 1 of ONE target goes through the shard's ingest first. Then each
update event of the history, in order: the event's new label sets
(``revision`` + 1) with their first sample, at the event's stamp, THROUGH
THE SHARD'S OWN INGEST — slots, index entries, start times and birth cells
are the write path's. Only then the device: every row's cells from its
birth to its end, values and stamps, in donated elementwise programs over
the blocks (the shape of ``counter``'s fill, which runs in place at this
size), and the host's mirrors as the write path would have left them.
"""

from __future__ import annotations

import functools

import numpy as np

from ..counter import datagen
from . import law


@functools.lru_cache(maxsize=None)
def programs():
    import jax
    import jax.numpy as jnp

    def hit_mask(shape, born, end):
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return col, (col >= born[:, None]) & (col < end[:, None])

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fill_val(block, sid, born, end, word):
        col, hit = hit_mask(block.shape, born, end)
        age = jnp.maximum(col - born[:, None], 0)
        v = datagen.counter(jnp, word, sid[:, None], age)
        return jnp.where(hit, v.astype(block.dtype), block)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fill_ts(block, born, end, iv):
        col, hit = hit_mask(block.shape, born, end)
        stamp = jnp.int64(datagen.BASE_TS) + col.astype(jnp.int64) * iv
        return jnp.where(hit, stamp, block)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def fill_n(n, end):
        return jnp.where(end > 0, end, n).astype(n.dtype)

    return fill_val, fill_ts, fill_n


def container(labels: dict, ts_ms: int, values, schema):
    """One container of the label sets ``labels`` with one sample each."""
    import dataclasses
    from filodb_tpu.core.record import RecordBuilder
    b = RecordBuilder(schema)
    b.add_series_batch(labels, ts_ms, 0.0)
    rc = b.build()
    return dataclasses.replace(
        rc, values=np.ascontiguousarray(values, np.float64))


def register_events(shard, sched, seed: int, deploy: dict, labels_of,
                    scrape_ms, schema) -> None:
    """Scrape 1 of the first target, then each history event's births,
    through ``shard.ingest``; every batch flushed before the next."""
    p = sched.plan
    first = np.arange(p.per_target)
    shard.ingest(container(labels_of(first, np.zeros(p.per_target, np.int32),
                                     deploy), scrape_ms(1, deploy),
                           sched.values(seed, first, [1])[:, 0], schema))
    shard.flush()
    for e in range(1, p.events):        # the last event is the live scrape's
        k = e * p.every
        rows = np.flatnonzero(sched.born == k)
        if not len(rows):
            continue
        shard.ingest(container(labels_of(sched.slot[rows], sched.rev[rows],
                                         deploy), scrape_ms(k, deploy),
                               sched.values(seed, rows, [k])[:, 0], schema))
        shard.flush()


def fill_history(shard, sched, seed: int, iv: int) -> None:
    """Every registered row's cells from its birth to its end (the head of
    the history for a series alive there), on the device."""
    import jax
    import jax.numpy as jnp
    from filodb_tpu.core.memstore import EPOCH_AFFECTS_ALL
    fill_val, fill_ts, fill_n = programs()
    p = sched.plan
    st = shard.store
    if p.fill_cols > st.C:
        raise RuntimeError(f"fill of {p.fill_cols} columns into capacity "
                           f"{st.C}")
    reg = p.registered_by_fill
    if shard.num_series != reg:
        raise RuntimeError(f"shard {shard.shard_num}: {shard.num_series} "
                           f"series registered, the law gives {reg}")
    end = np.zeros(st.S, np.int32)
    end[:reg] = np.minimum(sched.end[:reg], p.fill_cols)
    born = np.zeros(st.S, np.int32)
    born[:reg] = sched.born[:reg]
    sid = np.zeros(st.S, np.uint32)
    sid[:reg] = sched.series_id[:reg]
    (dev,) = st.val.devices()
    put = functools.partial(jax.device_put, device=dev)
    born_d, end_d = put(jnp.asarray(born)), put(jnp.asarray(end))
    with shard.lock:
        st._pre_donate("benchmark.fill")
        st.val = fill_val(st.val, put(jnp.asarray(sid)), born_d, end_d,
                          put(jnp.uint32(datagen.fold_seed(seed))))
        st.ts = fill_ts(st.ts, born_d, end_d, put(jnp.int64(iv)))
        st.n = fill_n(st.n, end_d)
        jax.block_until_ready((st.val, st.ts, st.n))
        added = int((end[:reg] - born[:reg]).sum()) \
            - int((st.n_host[:reg] - st.born[:reg]).sum())
        st.n_host[:reg] = end[:reg]
        st.last_ts[:reg] = datagen.BASE_TS + (end[:reg].astype(np.int64)
                                              - 1) * iv
        st._cohorts = None
        st.stats.samples_appended += added
        last = datagen.BASE_TS + (p.fill_cols - 1) * iv
        shard.lead_ms = max(shard.lead_ms, last)
        shard.visible_lead_ms = max(shard.visible_lead_ms, last)
        shard._bump_epoch_locked(EPOCH_AFFECTS_ALL)


def check_filled(shard, sched, iv: int) -> None:
    """Raises unless the store is what the write path would have left."""
    st = shard.store
    p = sched.plan
    reg = p.registered_by_fill
    born = sched.born[:reg]
    end = np.minimum(sched.end[:reg], p.fill_cols)
    late = int((born > 0).sum())
    facts = {
        "grid form": st.res is None and st.grid_ok and st.aligned,
        "grid_info": st.grid_info() == (datagen.BASE_TS, iv),
        "one cohort": st.grid_cohorts() == ("uniform", 0),
        "registered": shard.num_series == reg,
        "born on the host": (st.born[:reg] == born).all()
        and not st.born[reg:].any(),
        "born on the device": (np.asarray(st.born_dev) == st.born).all(),
        "born_late": st.born_late == late,
        "n_host": (st.n_host[:reg] == end).all()
        and not st.n_host[reg:].any(),
        "n on the device": (np.asarray(st.n) == st.n_host).all(),
        "first_ts": (st.first_ts[:reg]
                     == datagen.BASE_TS + born.astype(np.int64) * iv).all(),
        "no row demoted": not st.off_line.any()
        and not sum(st.demoted.values()),
        "no row pooled": st.narrow_operands() is None,
    }
    bad = [k for k, ok in facts.items() if not ok]
    if bad:
        raise RuntimeError(
            f"shard {shard.shard_num}: store not as the write path would "
            f"have left it: {bad}; registered {shard.num_series} (law "
            f"{reg}), born_late {st.born_late} (law {late}), n_host "
            f"{np.unique(st.n_host[:reg])[:16]}")
