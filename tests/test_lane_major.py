"""The fused scalar program's per-row operands, lane-major.

``n`` (on a line store with the row's start packed above it), the group
ids and the narrow variants' f32 row operands reach the kernel as ``[S /
Sb, 1, Sb]`` — a reshape of ``[S]`` that moves no byte — one ``[1, Sb]``
block a tile; the count turns to a column inside the tile and the group
one-hot is built transposed (ops/fusedgrid.py ``lane_major``, ``_column``,
``group_fold``). Nothing about WHAT is computed changed with that, so:
the whole program on both backends to the bit, and both against the golden
model (``tests/prom_reference.py``), in its three modes (grid, line, hole)
at one short tile, one full tile and four tiles whose ``n``, ``start`` and
``gids`` DIFFER tile by tile (a tile that read its neighbour's row block
would miss the reference), with rows that end before, inside and after the
windows; and the plan keys, which are the ones these programs had.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from filodb_tpu.core.chunkstore import HOLE_RUN_MAX, STALE_NAN, SeriesStore
from filodb_tpu.ops import decodereg, fusedgrid
from filodb_tpu.query.plancache import plan_cache

from .prom_reference import eval_range_fn
from .test_line_stamps import (BACKENDS, BASE, IV, WINDOW, aggregate, err,
                               present, stream)

C, K, T, G = 128, 100, 61, 8
SIZES = (8, 512, 2048)          # one short tile, one full tile, four tiles
MODES = ("grid", "line", "hole")
FNS = ("rate", "avg_over_time", "sum_over_time", "count_over_time")
OUT_TS = BASE + 330_007 + 9_001 * np.arange(T)      # BASE + 330 s .. 870 s


def gids_of(rows):
    """Group ids that differ tile by tile AND along a tile: the same row
    of the next tile is in another group; group 7 holds no row."""
    r = np.arange(rows)
    return ((r + r // 512 * 3) % (G - 1)).astype(np.int32)


@functools.lru_cache(maxsize=None)
def the_stream(mode, rows):
    """(stamps, values, there [rows, K]): ``there`` says which scrapes
    came. Every row ends somewhere between scrape 25 and the stream's end:
    before a step's window, inside it, after it (``n`` differs row by
    row, so tile by tile). ``grid``: every stamp on the common
    grid; ``line``: test_line_stamps' phases and late scrapes; ``hole``:
    one scrape in sixteen missed besides, runs capped at the bound."""
    rng = np.random.default_rng(rows + len(mode))
    t, v = stream(seed=rows, rows=rows, scrapes=K,
                  reset_row=5 if rows > 5 else None)
    if mode == "grid":
        t = np.broadcast_to(BASE + np.arange(K) * IV, (rows, K)).copy()
    ends = rng.integers(25, K + 1, rows)
    ends[:3] = K, 25, 60                    # whole; early; in mid-stream
    there = np.arange(K)[None, :] < ends[:, None]
    if mode == "hole":
        raw = rng.random((rows, K)) < 1 / 16
        for k in range(K):
            if k >= HOLE_RUN_MAX:
                raw[:, k] &= ~raw[:, k - HOLE_RUN_MAX:k].all(axis=1)
        raw[:, 0] = False
        there &= ~raw
    return t, v, there


@functools.lru_cache(maxsize=None)
def the_store(mode, rows):
    t, v, there = the_stream(mode, rows)
    last = there.shape[1] - 1 - np.argmax(there[:, ::-1], axis=1)
    st = SeriesStore(rows, C)
    for k in range(K):
        live = k <= last                    # a missed scrape sends a marker
        st.append(np.arange(rows)[live], t[live, k],
                  np.where(there[live, k], v[live, k], STALE_NAN))
    assert st.stamp_form == ("grid" if mode == "grid" else "line")
    assert not any(st.demoted.values())
    return st


@functools.lru_cache(maxsize=None)
def parts(mode, rows, backend, fn, grouped):
    """One kernel run gives every aggregate's partial state."""
    st = the_store(mode, rows)
    gids = gids_of(rows) if grouped else np.zeros(rows, np.int32)
    if mode == "grid":
        base, line, holes = BASE, None, False
    else:
        info = st.line_info()
        assert info.holes == (mode == "hole") and len(info.minority) == 0
        base, line, holes = info.base_ts, (info.start, info.res), info.holes
    out = fusedgrid.fused_grid_aggregate(
        "stddev", fn, st.val, st.n, jnp.asarray(gids), G if grouped else 1,
        OUT_TS, WINDOW, base, IV, variant=backend, line=line, holes=holes)
    return {k: np.asarray(a) for k, a in out.items()}


@functools.lru_cache(maxsize=None)
def want_matrix(mode, rows, fn):
    t, v, there = the_stream(mode, rows)
    return np.array([eval_range_fn(fn, t[r][there[r]], v[r][there[r]],
                                   OUT_TS, WINDOW) for r in range(rows)])


@pytest.mark.parametrize("grouped", (False, True), ids=("global", "by"))
@pytest.mark.parametrize("fn", FNS)
@pytest.mark.parametrize("rows", SIZES)
@pytest.mark.parametrize("mode", MODES)
def test_both_backends_to_the_bit_and_both_against_the_reference(
        mode, rows, fn, grouped):
    a, b = (parts(mode, rows, backend, fn, grouped) for backend in BACKENDS)
    for k in ("sum", "count", "sumsq"):
        np.testing.assert_array_equal(a[k], b[k])
    want = want_matrix(mode, rows, fn)
    gids = gids_of(rows) if grouped else np.zeros(rows, np.int32)
    ng = G if grouped else 1
    aggs = ("sum", "avg", "count") + (
        ("stddev",) if fn == "sum_over_time" and not grouped else ())
    for agg in aggs:
        assert err(present(agg, a), aggregate(agg, want, gids, ng)) < 1.0, agg
    if grouped:
        assert not a["count"][G - 1].any()          # the group with no row
    # counts are exact, so a tile that read another tile's rows shows here
    np.testing.assert_array_equal(
        a["count"], np.nan_to_num(aggregate(
            "count", want, gids, ng)).astype(np.float32))


def test_the_streams_meet_the_cases_they_are_there_for():
    for mode in MODES:
        t, _v, there = the_stream(mode, 2048)
        n = there.shape[1] - np.argmax(there[:, ::-1], axis=1)
        # against the LAST step's window: ended before it, in it, after it
        opens, closes = OUT_TS[-1] - WINDOW, OUT_TS[-1]
        end_ts = t[np.arange(2048), n - 1]
        assert (end_ts < opens).any() and (end_ts > closes).any()
        assert ((end_ts > opens) & (end_ts < closes)).any()
        per_tile = n.reshape(4, 512)
        assert all((per_tile[0] != per_tile[i]).mean() > 0.9
                   for i in range(1, 4))
    g = gids_of(2048).reshape(4, 512)
    assert all((g[0] != g[i]).all() for i in range(1, 4))
    st = the_store("line", 2048)
    s4 = np.asarray(st.line_info().start).reshape(4, 512)
    assert all((s4[0] != s4[i]).mean() > 0.9 for i in range(1, 4))
    assert 0 < the_store("hole", 2048).hole_cells


# -- the operands' form --------------------------------------------------------

@pytest.mark.parametrize("dtype", (np.int32, np.float32))
@pytest.mark.parametrize("Sb", (8, 64, 512))
def test_a_row_block_turns_to_the_column_it_was(Sb, dtype):
    x = (np.arange(4 * Sb) * 7919 % 100_003 - 50_000).astype(dtype)
    blocks = fusedgrid.lane_major(jnp.asarray(x), Sb)
    assert blocks.shape == (4, 1, Sb) and blocks.dtype == dtype
    for i in range(4):
        col = fusedgrid._column(blocks[i])
        assert col.shape == (Sb, 1)
        np.testing.assert_array_equal(np.asarray(col)[:, 0],
                                      x[i * Sb:(i + 1) * Sb])


def test_a_packed_start_survives_the_turn():
    n = jnp.asarray([0, 1, 1024, 767], jnp.int32)
    start = jnp.asarray([-1, 0, (1 << 20) - 1, 9_999], jnp.int32)
    row = fusedgrid.lane_major(fusedgrid.pack_start(n, start), 4)[0]
    got_n, got_start = fusedgrid.unpack_start(fusedgrid._column(row))
    np.testing.assert_array_equal(np.asarray(got_n)[:, 0], np.asarray(n))
    np.testing.assert_array_equal(np.asarray(got_start)[:, 0],
                                  np.asarray(start))


@pytest.mark.parametrize("residency", ("quant16", "delta8", "delta16"))
@pytest.mark.parametrize("backend", BACKENDS)
def test_the_narrow_variants_row_operands_take_the_same_form(backend,
                                                             residency):
    """Four tiles whose anchors (``vmin`` / ``scale``) differ tile by
    tile: the decode reads its own tile's, on both backends."""
    rows = 2048
    var = decodereg.variant(residency)
    rng = np.random.default_rng(3)
    if residency == "quant16":
        blk = rng.integers(-32768, 32767, (rows, C)).astype(np.int16)
        blk.sort(axis=1)
        ops = (rng.integers(0, 1000, rows).astype(np.float32),
               np.exp2(rng.integers(-2, 3, rows)).astype(np.float32))
    else:
        hi = 100 if residency == "delta8" else 20_000
        blk = rng.integers(0, hi, (rows, C)).astype(var.block_dtype)
        ops = (rng.integers(0, 1000, rows).astype(np.float32),)
    val = np.asarray(var.xla(jnp.asarray(blk),
                             *(jnp.asarray(o)[:, None] for o in ops)))
    n = rng.integers(40, C + 1, rows).astype(np.int32)
    gids = gids_of(rows)
    out_ts = BASE + 330_000 + 10_000 * np.arange(T)
    got = fusedgrid.fused_grid_aggregate(
        "sum", "rate", None, jnp.asarray(n), jnp.asarray(gids), G, out_ts,
        WINDOW, BASE, IV, variant=backend,
        narrow=(residency, tuple(jnp.asarray(o) for o in (blk, *ops))))
    raw = fusedgrid.fused_grid_aggregate(
        "sum", "rate", jnp.asarray(val), jnp.asarray(n), jnp.asarray(gids),
        G, out_ts, WINDOW, BASE, IV, variant=backend)
    for k in ("sum", "count"):
        np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(raw[k]))
    assert np.asarray(raw["count"]).any()


# -- the programs are the ones that were ---------------------------------------

def test_the_plan_keys_are_what_they_were():
    """The operands' layout is no choice the program has: a grid, a line
    (packed and not) and a hole program key as they did before the
    operands were lane-major."""
    def keys_of(run):
        plan_cache.clear()
        run()
        return [k[1:] for k in plan_cache._entries if k[0] == "fused-grid"]

    tag = fusedgrid.kernel_tag("pallas")
    head = ("rate", True, WINDOW, IV, 512, 512, C, 128, 8, "raw", 0, C, tag)
    for mode, tail in (("grid", ()), ("line", ("line", 2)),
                       ("hole", ("line", 2, "holes"))):
        assert keys_of(lambda: parts.__wrapped__(
            mode, 512, "pallas", "rate", True)) == [head + tail]
    st = the_store("line", 512)
    info = st.line_info()
    long_ts = BASE + 330_007 + 5_001 * np.arange(100)       # one slot a block
    assert keys_of(lambda: fusedgrid.fused_grid_aggregate(
        "sum", "rate", st.val, st.n, fusedgrid.zero_gids(512), 1, long_ts,
        WINDOW, info.base_ts, IV, line=(info.start, info.res))) == [
            ("rate", False) + head[2:] + ("line",)]


def test_the_whole_program_is_one_jit_with_no_column():
    """From the store's ``[S]`` arrays to the partial state: the reshapes
    are in the program, and none of them makes an ``[S, 1]`` array."""
    prog = fusedgrid.fused_program("rate", False, WINDOW, IV, 2048, 512, C,
                                   128, 8, "raw", 0, 0, "xla", 2)
    sds = jax.ShapeDtypeStruct
    i32, bf16 = jnp.int32, jnp.bfloat16
    with jax.enable_x64(False):
        text = jax.jit(prog).lower(
            sds((2048, C), jnp.float32), sds((2048,), i32), sds((2048,), i32),
            sds((2048,), i32), sds((2048, C), jnp.int8),
            sds((C, 128), bf16), sds((C, 384), bf16), sds((1, 128), i32),
            sds((1, 128), i32), sds((1, 128), i32), sds((8, 128), i32)
        ).as_text()
    assert "2048x1x" not in text and "tensor<2048x1x" not in text
    assert "4x1x512xi32" in text


def test_the_driver_entry_point_runs_the_whole_program():
    """``__graft_entry__.entry()`` is a caller outside the package: its
    jitted ``sum(rate(m[5m]))`` step takes the store's ``[S]`` arrays as
    every other caller does, runs, and answers what the golden model does."""
    import __graft_entry__ as graft

    fn, args = graft.entry()
    val, n = (np.asarray(a) for a in args[:2])
    got = np.asarray(jax.jit(fn)(*args))
    base = 1_700_000_000_000
    out_ts = base + np.arange(400_000, 900_001, 30_000)
    ts = base + 10_000 * np.arange(val.shape[1])
    want = sum(eval_range_fn("rate", ts[:k], row[:k].astype(np.float64),
                             out_ts, 300_000) for row, k in zip(val, n))
    assert got.shape == (1, len(out_ts))
    np.testing.assert_allclose(got[0], want, rtol=1e-5)


def test_the_on_chip_witness_script_runs(capsys):
    """``scripts/fused_bits.py`` is what says, on the chip, that two trees
    answer to the bit: here that it runs (two tiles, interpret mode), prints
    a line a shape and one checksum, the same on a second run."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location("fused_bits", os.path.join(
        os.path.dirname(os.path.dirname(__file__)), "scripts",
        "fused_bits.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    last = []
    for _ in range(2):
        assert mod.main(["--rows", "1024", "--tag", "t"]) == 0
        lines = capsys.readouterr().out.strip().splitlines()
        assert len(lines) == 10 and " ALL " in lines[-1]
        last.append(lines[-1].split(" ALL ")[1].split()[0])
    assert last[0] == last[1] and len(last[0]) == 16
