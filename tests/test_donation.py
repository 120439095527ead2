"""Donated scatter double-buffering (ISSUE 9).

The memstore flush path commits staged rows with ``donate_argnums`` scatter
jits (core/chunkstore.py): XLA aliases each donated input buffer into the
matching output, so a staged-row commit UPDATES the store arrays in place
instead of allocating a full [S, C] copy per flush — at any moment at most
two logical buffers exist (the live handle and the in-flight donated one),
never a third. These tests assert that through jax's own donation
machinery: donated handles are deleted, the compiled HLO carries the
input-output aliasing, and repeated commits do not accumulate store-sized
buffers. filolint's ``jit-donation-unused`` rule guards the static side
(every flush-path scatter must donate; no donation may go unused)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from filodb_tpu.core.chunkstore import (SeriesStore, _compact, _free_rows,
                                        _scatter_append)
from filodb_tpu.core.memstore import StoreConfig, TimeSeriesMemStore
from filodb_tpu.core.record import RecordBuilder
from filodb_tpu.core.schemas import GAUGE

BASE = 1_700_000_000_000
IV = 10_000


def _append(st: SeriesStore, t: int, rows=8) -> None:
    st.append(np.arange(rows, dtype=np.int32),
              np.full(rows, BASE + t * IV, np.int64),
              np.full(rows, float(t), np.float32))


def test_append_donates_all_store_buffers():
    st = SeriesStore(64, 32)
    old = {"ts": st.ts, "val": st.val, "n": st.n}
    _append(st, 0)
    for name, h in old.items():
        assert h.is_deleted(), f"{name} must be donated by the scatter"
    # the new handles are live and correct
    assert int(st.n_host[0]) == 1
    assert float(np.asarray(st.val)[0, 0]) == 0.0


def test_compact_and_free_rows_donate():
    st = SeriesStore(64, 32)
    for t in range(4):
        _append(st, t)
    jax.block_until_ready(st.n)
    old = (st.ts, st.val, st.n)
    st.compact(BASE + 2 * IV)
    assert all(h.is_deleted() for h in old)
    old = (st.ts, st.n)
    st.free_rows(np.array([1, 2], np.int32))
    assert all(h.is_deleted() for h in old)


def test_scatter_hlo_carries_input_output_alias():
    """The donation is visible in the compiled program itself: XLA's
    input_output_alias config maps each donated operand to its output —
    the machine-checkable form of "updates the store in place"."""
    S, C = 16, 8
    args = (jnp.full((S, C), 1 << 62, jnp.int64), jnp.zeros((S, C)),
            jnp.zeros(S, jnp.int32), jnp.zeros(4, jnp.int32),
            jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int64),
            jnp.zeros(4), jnp.zeros(S, jnp.int32))
    txt = _scatter_append.lower(*args).compile().as_text()
    assert "input_output_alias" in txt
    txt = _compact.lower(args[0], args[1], args[2],
                         jnp.int64(0)).compile().as_text()
    assert "input_output_alias" in txt
    txt = _free_rows.lower(args[0], args[2],
                           jnp.zeros(4, jnp.int32)).compile().as_text()
    assert "input_output_alias" in txt


def test_repeated_commits_keep_two_logical_buffers():
    """Double-buffering bound: across N flush commits the process never
    accumulates store-sized arrays — each donated scatter retires its
    input, so exactly ONE [S, C] ts and ONE [S, C] val handle stay live
    (the in-flight second copy exists only while a scatter is executing)."""
    shape = (96, 48)   # distinctive: nothing else in the process uses it
    st = SeriesStore(*shape)
    for t in range(10):
        _append(st, t)
    jax.block_until_ready(st.n)
    live = [a for a in jax.live_arrays() if a.shape == shape]
    assert len(live) == 2, (   # one i64 ts + one f32 val block
        f"expected exactly the live ts+val blocks, found {len(live)}")


def test_multi_column_append_donates_extras():
    layout = (("v", 0, 1, False), ("aux", 1, 1, False))
    st = SeriesStore(32, 16, layout=list(layout), default_col="v")
    old = {"ts": st.ts, "val": st.val, "n": st.n,
           "extra:aux": st.extra["aux"]}
    st.append(np.arange(4, dtype=np.int32), np.full(4, BASE, np.int64),
              np.tile(np.array([[1.0, 2.0]], np.float32), (4, 1)))
    for name, h in old.items():
        assert h.is_deleted(), f"{name} must be donated (pytree donation)"


def test_staged_row_commit_donates_through_the_shard():
    """End to end: TimeSeriesShard.flush's staged-row commit runs the
    donating scatter — the pre-flush store handles die with it."""
    ms = TimeSeriesMemStore()
    cfg = StoreConfig(max_series_per_shard=16, samples_per_series=32,
                      flush_batch_size=1 << 30)
    sh = ms.setup("donate", GAUGE, 0, cfg)
    b = RecordBuilder(GAUGE)
    for t in range(8):
        b.add({"_metric_": "m", "host": "h0"}, BASE + t * IV, float(t))
    ms.ingest("donate", 0, b.build())
    old = (sh.store.ts, sh.store.val, sh.store.n)
    sh.flush()
    assert all(h.is_deleted() for h in old)
    r = sh.store.series_snapshot(0)
    np.testing.assert_array_equal(r[1], np.arange(8, dtype=np.float32))


@pytest.mark.parametrize("nbuckets, layout", [(0, False), (4, False),
                                              (4, True)])
def test_dense_append_matches_the_scatter_and_donates(monkeypatch, nbuckets,
                                                      layout):
    """Large stores flush through per-row selects (chunkstore
    DENSE_APPEND_BYTES: the one-program scatter needs a store-sized temp on
    the TPU). Same batches through both paths — ragged starts, two samples
    per row in one batch, repeated (dropped) samples — must leave identical
    stores, and the dense programs must donate too. ``layout``: a
    prom-histogram store (``sum``, ``count`` beside the bucket block),
    whose flat rows are split a column before either route."""
    from filodb_tpu.core.schemas import PROM_HISTOGRAM
    lay = PROM_HISTOGRAM.col_layout(nbuckets) if layout else None
    width = nbuckets + 2 if layout else nbuckets
    from filodb_tpu.core import chunkstore
    rng = np.random.default_rng(3)
    batches = []
    for step in range(5):
        rows = np.sort(rng.choice(48, 30, replace=False)).astype(np.int32)
        pid = np.concatenate([rows, rows[:7]])            # K = 2 for seven rows
        ts = np.concatenate([np.full(30, BASE + 2 * step * IV),
                             np.full(7, BASE + (2 * step + 1) * IV)])
        if step:
            pid[0], ts[0] = batches[-1][0][0], batches[-1][1][0]   # a repeat
        shape = (len(pid), width) if width else (len(pid),)
        batches.append((pid, ts.astype(np.int64),
                        rng.integers(0, 1000, shape).astype(np.float32)))

    def run(dense: bool):
        monkeypatch.setattr(chunkstore, "DENSE_APPEND_BYTES",
                            0 if dense else 1 << 60)
        st = SeriesStore(64, 16, nbuckets=nbuckets, layout=lay,
                         default_col="h")
        for pid, ts, v in batches:
            old = (st.val, st.n, *st.extra.values())
            stamps = st._stamp_block
            st.append(pid, ts, v)
            assert all(h.is_deleted() for h in old)
            # rows that skip a cell turn the scalar store to its line form:
            # the s64 block is dropped whole there, not donated
            assert stamps.is_deleted() or (st.ts is None
                                           and stamps is not st.res)
        return st

    a, b = run(False), run(True)
    assert a.stamp_form == b.stamp_form == ("grid" if nbuckets else "line")
    np.testing.assert_array_equal(np.asarray(a.ts_block()),
                                  np.asarray(b.ts_block()))
    np.testing.assert_array_equal(np.asarray(a.val), np.asarray(b.val))
    assert sorted(a.extra) == sorted(b.extra) == (
        ["count", "sum"] if layout else [])
    for nm in a.extra:
        np.testing.assert_array_equal(np.asarray(a.extra[nm]),
                                      np.asarray(b.extra[nm]))
    np.testing.assert_array_equal(np.asarray(a.n), np.asarray(b.n))
    np.testing.assert_array_equal(a.n_host, b.n_host)
    assert a.stats.out_of_order_dropped == b.stats.out_of_order_dropped >= 4
