"""The main path's kernels compiled for the chip, without the chip.

The TPU's compiler is installed in the sandbox and compiles for a chip that is
described, not attached (on-chip-measurement guide, section 2.3). Interpret
mode — what every other test runs — cannot show what Mosaic refuses: this file
found ``cumsum`` unimplemented in the Pallas TPU lowering (delta8/delta16
decode, the hist kernel), the hist kernel's [Sb, C, 64] tile transposing the
whole store into a 2x lane-padded copy per query, and a rank-1 SMEM block that
must match XLA's 1024-wide tiling; and it holds what a whole program may keep
beside its kernel (the scalar program turned two [S] operands to lane-padded
[S, 1] columns a query, 1 GB of temporaries, until its kernel took them
lane-major). Each case compiles one kernel or program at the shapes
``chip_smoke.py`` serves (2^20 series, capacity 768/1024, 8..64 groups) with
``interpret=False`` passed directly — code that asks ``jax.default_backend()``
sees the CPU here — under ``enable_x64(False)`` like the call sites.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold libtpu, every xdist worker imports every test file,
and all of these tests live in this one file so one worker gets them all.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from filodb_tpu.ops import decodereg, fusedgrid, fusedresident
from filodb_tpu.parallel import distributed

S, SB = 1 << 20, 512
ROWS = (S // SB, 1, SB)     # a per-row operand, lane-major (fusedgrid.lane_major)
WINDOW, IV = 300_000, 10_000
f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile can be written to the persistent cache but
    # never read back: keep the cache off around these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, args):
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _scalar_args(sh, C, Tp, residency):
    var = decodereg.variant(residency)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)  # noqa: E731
    return ([sds((S, C), var.block_dtype)]
            + [sds(ROWS, f32)] * var.row_operands
            + [sds(ROWS, i32), sds(ROWS, i32),
               sds((C, Tp), bf16), sds((C, Tp), bf16),   # band, ohlo: 0/1
               sds((1, Tp), i32), sds((1, Tp), i32), sds((1, Tp), i32)])


@pytest.mark.parametrize("fn,sumsq,C,Tp,G,residency", [
    ("rate", False, 768, 128, 8, "raw"),            # sum(rate), sum by (g)
    ("rate", False, 1024, 512, 64, "raw"),          # every cap at once
    ("avg_over_time", False, 768, 128, 8, "raw"),   # window_reduce
    ("sum_over_time", True, 768, 128, 8, "raw"),    # stddev: sumsq plane
    ("rate", False, 768, 128, 8, "delta8"),
    ("rate", False, 768, 128, 8, "quant16"),
    ("rate", False, 768, 128, 8, "delta16"),
])
def test_scalar_kernel_compiles_for_v5e(one_chip, fn, sumsq, C, Tp, G,
                                        residency):
    call = fusedgrid.build_pallas(fn, sumsq, WINDOW, IV, S, 512, C, Tp, G,
                                  False, residency, 0, 0)
    _compile(call, _scalar_args(one_chip, C, Tp, residency))


def _line_args(sh, C, Tp, per=1):
    """A line store's operands (fusedgrid._line_contrib): each row's start
    packed above its count, the int8 residual block beside the values,
    ``ohe`` (int8: picks only) for ``ohlo`` with ``per`` edge slots a
    block, the edge bounds last."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)  # noqa: E731
    return [sds((S, C), f32), sds(ROWS, i32), sds(ROWS, i32),
            sds((S, C), jnp.int8),
            sds((C, Tp), bf16),
            sds((C, fusedgrid.EDGE_SLOTS // per * Tp), jnp.int8),
            sds((1, Tp), i32), sds((1, Tp), i32), sds((1, Tp), i32),
            sds((8, Tp), i32)]


@pytest.mark.parametrize("fn,sumsq,Tp,G,per", [
    ("rate", False, 128, 8, 1),          # sum(rate), sum by (g)
    ("avg_over_time", False, 128, 8, 1),
    ("sum_over_time", True, 128, 8, 1),  # stddev: sumsq plane
    ("count_over_time", False, 128, 8, 1),
    ("delta", True, 512, 64, 1),         # every cap at once
    # up to 64 steps two edge slots share a block: the 64-lane roll that
    # brings a half down; the window fns' band is the ``band`` operand
    ("rate", False, 128, 8, 2),
    ("rate", False, 128, 64, 2),
    ("sum_over_time", True, 128, 8, 2),
    ("sum_over_time", True, 128, 64, 2),
    ("count_over_time", False, 128, 8, 2),
])
def test_line_kernel_compiles_for_v5e_with_no_block_sized_temp(one_chip, fn,
                                                               sumsq, Tp, G,
                                                               per):
    """promdev_prom_1m's kernel at 2^20 x 768: values and int8 residuals
    stream in row tiles straight from their blocks, no operand is s64, and
    there is no temporary to speak of: the per-row operands (a row's start
    packed above its count, the group ids) arrive lane-major, a [1, Sb]
    block a tile, and turn to a column inside the tile (PERF.md §5) —
    nothing that grows with the rows or the columns."""
    C = 768
    call = fusedgrid.build_pallas(fn, sumsq, WINDOW, IV, S, 512, C, Tp, G,
                                  False, "raw", 0, 0, per)
    args = _line_args(one_chip, C, Tp, per)
    assert not any(a.dtype == jnp.int64 for a in args)
    compiled = _compile(call, args)
    assert "s64[" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (64 << 20), mem
    grid = _compile(fusedgrid.build_pallas(fn, sumsq, WINDOW, IV, S, 512, C,
                                           Tp, G, False, "raw", 0, 0),
                    _scalar_args(one_chip, C, Tp, "raw"))
    assert (mem.temp_size_in_bytes
            <= grid.memory_analysis().temp_size_in_bytes + (8 << 20)), mem


@pytest.mark.parametrize("fn,sumsq,Tp,G,per", [
    ("rate", False, 128, 8, 2),          # packed: adhoc_prom_miss's cards
    ("rate", False, 128, 64, 2),
    ("sum_over_time", True, 128, 8, 2),
    ("avg_over_time", False, 128, 8, 2),
    ("count_over_time", False, 128, 8, 2),
    ("rate", False, 128, 8, 1),          # one slot a block
    ("sum_over_time", True, 128, 8, 1),
    ("delta", True, 512, 64, 1),         # every cap at once
])
def test_hole_mode_of_the_line_kernel_compiles_for_v5e(one_chip, fn, sumsq,
                                                       Tp, G, per):
    """promdev_prom_miss_1m's kernel at 2^20 x 768 (fusedgrid._hole_contrib):
    the same operands as the line kernel's, the filled planes live in VMEM
    a tile at a time — no s64, and no temporary the line kernel has not."""
    C = 768
    call = fusedgrid.build_pallas(fn, sumsq, WINDOW, IV, S, 512, C, Tp, G,
                                  False, "raw", 0, 0, per, True)
    compiled = _compile(call, _line_args(one_chip, C, Tp, per))
    assert "s64[" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (64 << 20), mem


def test_hole_modes_xla_twin_compiles_for_v5e(one_chip):
    C, Tp, G = 768, 128, 8
    call = fusedgrid.build_xla_tiles("rate", False, WINDOW, IV, S, 512, C, Tp,
                                     G, "raw", 0, 0, 2, True)
    with jax.enable_x64(False):
        compiled = jax.jit(call).lower(
            *_line_args(one_chip, C, Tp, 2)).compile()
    assert "s64[" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < S * C * 5


@pytest.mark.parametrize("per", [1, 2])
def test_line_kernels_xla_twin_compiles_for_v5e(one_chip, per):
    """The twin scans the same tiles through the same tile math; its temp
    is the tiles' relayout at most, never an s64 plane."""
    C, Tp, G = 768, 128, 8
    call = fusedgrid.build_xla_tiles("rate", False, WINDOW, IV, S, 512, C, Tp,
                                     G, "raw", 0, 0, per)
    with jax.enable_x64(False):
        compiled = jax.jit(call).lower(
            *_line_args(one_chip, C, Tp, per)).compile()
    assert "s64[" not in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < S * C * 5, mem


def _store_args(sh, rows, C, Tp, residency="raw", per=0):
    """What a QUERY hands the whole fused program
    (fusedgrid.fused_program): the store's own arrays, every per-row
    operand ``[S]`` — the casts, the pack and the reshapes are the
    program's."""
    var = decodereg.variant(residency)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)  # noqa: E731
    args = ([sds((rows, C), var.block_dtype)]
            + [sds((rows,), f32)] * var.row_operands
            + [sds((rows,), i32), sds((rows,), i32)])
    if per:
        args += [sds((rows,), i32), sds((rows, C), jnp.int8)]
    We = fusedgrid.EDGE_SLOTS // per * Tp if per else Tp
    # a line plan's ``ohe`` holds picks only and goes up as int8
    args += [sds((C, Tp), bf16), sds((C, We), jnp.int8 if per else bf16),
             sds((1, Tp), i32), sds((1, Tp), i32), sds((1, Tp), i32)]
    return args + ([sds((8, Tp), i32)] if per else [])


def _no_column_and_no_temp(compiled, rows):
    """No per-row operand became a column (an ``[S, 1]`` array is tiled (8,
    128) on the chip: a 512 MB relayout a query at 2^20 rows, read back a
    [Sb, 1] block a tile), and the program holds no temporary to speak
    of."""
    text = compiled.as_text()
    assert not re.search(r"\b[sf]32\[%d,1\]" % rows, text), \
        re.findall(r".*[sf]32\[%d,1\].*" % rows, text)[:3]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (64 << 20), mem


@pytest.mark.parametrize("fn,sumsq,G,residency,per,holes", [
    ("rate", False, 8, "raw", 0, False),            # grid: adhoc_cold's
    ("avg_over_time", False, 8, "raw", 0, False),
    ("sum_over_time", True, 8, "raw", 0, False),
    ("rate", False, 8, "raw", 2, False),            # line, packed: adhoc_prom's
    ("sum_over_time", True, 64, "raw", 2, False),
    ("rate", False, 8, "raw", 1, False),            # line, one slot a block
    ("rate", False, 8, "raw", 2, True),             # holes: adhoc_prom_miss's
    ("sum_over_time", True, 8, "raw", 2, True),
    ("rate", False, 8, "delta8", 0, False),         # f32 row operands turn too
    ("rate", False, 8, "quant16", 0, False),
])
def test_the_whole_fused_program_holds_no_column_and_no_temp(
        one_chip, fn, sumsq, G, residency, per, holes):
    """The program a query runs, from the store's ``[S]`` arrays (not the
    kernel from ready-made operands), at 2^20 x 768: the two ``copy
    s32[1048576,1]`` that every fused query ran before its Pallas call,
    and the 1 GB of temporaries they were, are gone."""
    C, Tp = 768, 128
    prog = fusedgrid.fused_program(fn, sumsq, WINDOW, IV, S, SB, C, Tp, G,
                                   residency, 0, 0, "pallas", per, holes)
    compiled = _compile(prog, _store_args(one_chip, S, C, Tp, residency, per))
    assert "s64[" not in compiled.as_text()
    _no_column_and_no_temp(compiled, S)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def _dots(jaxpr, C):
    """A branch's ``dot_general``s: ``(int8 x int8 -> int32, bf16 x bf16 ->
    f32, MXU passes of the tile's own products in bf16-pass equivalents)``
    — a product over the tile's ``C`` columns takes a pass a 128-lane block
    of its weight and ``SB`` rows of its left side (a value's byte pick has
    four times the tile's: fusedgrid.pick_exact), at half the price in
    int8; the group fold's products contract over the rows and are not
    counted."""
    i8 = b16 = 0
    passes = 0.0
    for e in _eqns(jaxpr):
        if e.primitive.name != "dot_general":
            continue
        lhs, rhs = (v.aval for v in e.invars)
        out, = (v.aval for v in e.outvars)
        kinds = (lhs.dtype, rhs.dtype, out.dtype)
        assert kinds in ((jnp.int8, jnp.int8, i32), (bf16, bf16, f32)), kinds
        assert e.params["precision"] in (
            jax.lax.Precision.DEFAULT,
            (jax.lax.Precision.DEFAULT, jax.lax.Precision.DEFAULT)), e
        i8, b16 = i8 + (lhs.dtype == jnp.int8), b16 + (lhs.dtype == bf16)
        if lhs.shape[1] == C:
            passes += (lhs.shape[0] / SB * rhs.shape[1] / 128
                       / (2 if lhs.dtype == jnp.int8 else 1))
    return i8, b16, passes


@pytest.mark.parametrize("fn,per,holes,tel,band", [
    # (int8 products, bf16 products, the tile's passes in bf16 equivalents)
    # of the telescoped branch and of the band form's: the PICKS are int8 —
    # the residuals as they are 1, the values' four bytes in one product 1;
    # hole mode: the four edge residuals 1, the two filled planes' values
    # 2, their distances and residuals 4 — and what is bf16 ADDS: the
    # fold's three pieces and its counts 4, the hole mode's validity band
    # 1, the band form's increments x band 3
    ("rate", 2, False, (2, 4, 7.5), (2, 7, 10.5)),      # adhoc_prom's
    ("increase", 1, False, (2, 4, 15), (2, 7, 18)),
    ("delta", 2, False, (2, 4, 7.5), (2, 7, 10.5)),
    ("rate", 2, True, (7, 5, 12), (7, 8, 15)),          # adhoc_prom_miss's
    ("rate", 1, True, (7, 5, 17), (7, 8, 20)),
    ("delta", 2, True, (7, 5, 12), (7, 8, 15)),
])
def test_a_line_rate_program_compiles_with_both_forms_of_its_tile(
        one_chip, fn, per, holes, tel, band):
    """The telescoped tile and the band form are both in the program, each
    under its branch (fusedgrid.fallen_fold), the band form three bf16
    products the dearer (a product over ``ohe``'s blocks is one
    ``dot_general``); every pick is ``int8 x int8 -> int32`` and no bf16
    product has a one-hot for its weight (fusedgrid.pick_exact); the count
    of tiles that ran the band form is the last output, ``[1]`` i32 in
    SMEM. And the whole still holds no ``copy``, no ``[S, 1]`` array and no
    temporary."""
    C, Tp, G = 768, 128, 8
    prog = fusedgrid.fused_program(fn, False, WINDOW, IV, S, SB, C, Tp, G,
                                   "raw", 0, 0, "pallas", per, holes)
    args = _store_args(one_chip, S, C, Tp, "raw", per)
    with jax.enable_x64(False):
        jaxpr = jax.make_jaxpr(prog)(*args)
    call, = (e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call")
    branches = [[_dots(b.jaxpr, C) for b in e.params["branches"]]
                for e in call.params["jaxpr"].eqns
                if e.primitive.name == "cond"]
    # (program_id == 0; the tile before fell: nothing | telescoped; this
    # one fell: nothing | band), branches listed false first
    none = (0, 0, 0.0)
    assert branches == [[none, none], [tel, none], [none, band]]
    # no conversion of the int8 residual tile to a float is left, at once
    # or by way of int32 (the parent's ``res.astype(i32).astype(f32)``)
    converts = [e for e in _eqns(call.params["jaxpr"])
                if e.primitive.name == "convert_element_type"]
    of_int8 = {e.outvars[0] for e in converts
               if e.invars[0].aval.dtype == jnp.int8}
    floated = [e for e in converts
               if jnp.issubdtype(e.params["new_dtype"], jnp.floating)
               and (e.invars[0].aval.dtype == jnp.int8
                    or e.invars[0] in of_int8)]
    assert of_int8 and not floated, floated
    outs = call.params["grid_mapping"].block_mappings_output
    assert [str(o.transformed_block_aval) for o in outs] == [
        "Ref<vmem>{float32[8,128]}"] * 2 + ["Ref<smem>{int32[1]}"]
    compiled = _compile(prog, args)
    text = compiled.as_text()
    assert "s64[" not in text and not re.search(r"\bcopy\(", text)
    _no_column_and_no_temp(compiled, S)
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("rows", [8, 64, 512])
def test_a_one_tile_store_compiles_for_v5e_too(one_chip, rows):
    """Sb = S: the [1, S] row turns to a column at any S % 8 == 0."""
    prog = fusedgrid.fused_program("rate", False, WINDOW, IV, rows, rows,
                                   768, 128, 8, "raw", 0, 0, "pallas", 2)
    _compile(prog, _store_args(one_chip, rows, 768, 128, "raw", 2))


def test_dense_flush_of_residuals_runs_in_place(one_chip):
    """The flush of a line store at 2^20 x 768: the per-row select writes
    the int8 residuals and the f32 values in place, each in a program of
    its own; no s64 block (and none of its two u32 planes) exists."""
    from filodb_tpu.core import chunkstore
    C = 768
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    for dt in (jnp.int8, f32):
        compiled = chunkstore._dense_set.lower(
            sds((S, C), dt), sds((S,), i32), sds((S,), dt)).compile()
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= S * C * jnp.dtype(dt).itemsize
        assert mem.temp_size_in_bytes < (64 << 20), mem
        assert "s64[" not in compiled.as_text()


@pytest.mark.parametrize("dd_dtype", [jnp.int8, jnp.int16])
def test_hist_kernel_compiles_for_v5e_without_copying_the_store(one_chip,
                                                                dd_dtype):
    """B = 64 buckets, 2^16 series x 768 cells (3.2 GB at i8). The resident
    [S, C, B] block must reach the kernel as a relabelling of its own HBM
    layout: no temp — the old row-major tile made XLA transpose and lane-pad
    the whole store per query (8.6 GB of temp at i8, out of memory at i16)."""
    Sh, C, Tp, B, G = 1 << 16, 768, 128, 64, 8
    assert fusedresident.hist_fusable(Sh, C, Tp, B, G)
    Sb = fusedresident.hist_rows_per_tile(Sh)
    call = fusedresident.build_hist_pallas(
        "rate", WINDOW, IV, Sh, Sb, C, Tp, B, G, False,
        jnp.dtype(dd_dtype).itemsize)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    args = [sds((Sh,), i32), sds((Sh,), i32), sds((Sh, C, B), dd_dtype),
            sds((Sh, B), f32), sds((C, Tp), f32), sds((C, Tp), f32),
            sds((1, Tp), i32), sds((1, Tp), i32), sds((1, Tp), i32)]
    # the wrapper's own spelling (fusedresident._hist_map_program)
    compiled = _compile(
        lambda n, g, dd, fd, *rest: call(n, g, dd.transpose(0, 2, 1), fd,
                                         *rest), args)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < (64 << 20), mem


@pytest.mark.parametrize("variant, c0, Ck, T", [
    ("pallas", 0, 768, 64),    # a 2 h range: every column
    ("pallas", 512, 256, 64),  # the last 15 min: two 128-column blocks
    ("pallas", 0, 768, 128),   # past 64 steps: the weight is two bands wide
    ("pallas", 0, 768, 160),   # ... and its halves meet inside a lane tile
    ("xla", 0, 768, 64),       # the twin from the same tiling plan
])
def test_raw_hist_kernel_compiles_for_v5e_with_no_store_sized_temp(
        one_chip, variant, c0, Ck, T):
    """histdev_raw_32k: 2^15 series x 768 cells x 64 buckets of raw f32,
    6.44 GB resident. The served map phase (fusedresident.raw_hist_map_body:
    the cast, the [S, C, B] -> [S, B, C] relabelling, the kernel — one
    packed matmul a tile, the correction's under a ``when``, the count of
    those in SMEM) must hold no [S, C, B]-sized temporary — the untiled
    composition holds four — nor a copy of the active columns: its temp
    stays under 64 MiB (Mosaic holds the kernel itself to the scoped-VMEM
    limit build_raw_hist_pallas states, at most VMEM_CAP)."""
    Sh, C, B, G = 1 << 15, 768, 64, 8
    Tp, N = -(-T // 128) * 128, -(-2 * T // 128) * 128
    assert fusedresident.raw_hist_fusable(Sh, C, T, B, G)
    Sb = fusedresident.raw_hist_rows_per_tile(Sh)
    body = fusedresident.raw_hist_map_body(variant, "rate", WINDOW, IV, Sh,
                                           Sb, C, Tp, N, B, G, c0, Ck)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    args = [sds((Sh, C, B), f32), sds((Sh,), i32), sds((Sh,), i32),
            sds((1,), i32), sds((Ck, N), jnp.bfloat16),
            sds((Ck, Tp), jnp.bfloat16), sds((1, Ck), i32),
            sds((1, Tp), i32), sds((1, Tp), i32), sds((1, Tp), i32)]
    with jax.enable_x64(False):
        compiled = jax.jit(body).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (variant == "pallas")
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > Sh * C * B * 4
    assert mem.temp_size_in_bytes < (64 << 20), mem


def test_raw_hist_finish_compiles_for_v5e_inside_a_querys_patience(one_chip):
    """The raw tier's f64 finish at histdev_raw_32k's shapes. The TPU
    emulates f64, and its compiler took 108 s over this finish when the
    buckets were cumulated by ``cumsum`` (under a second by shifted adds):
    a cold server answered its first query with 504. The bound is generous
    to the machine and far under the scan's time; no loop may come back
    either (a contraction ran as five, 140 device events a query)."""
    import time
    G, T, Tp, B = 8, 64, 128, 64
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    acc = sds((G, B, Tp), f32)
    t0 = time.perf_counter()
    compiled = jax.jit(fusedresident.raw_hist_finish(G, T, B)).lower(
        sds((), jnp.float64), sds((B,), jnp.float64), acc, acc, acc).compile()
    assert time.perf_counter() - t0 < 45.0
    assert " while(" not in compiled.as_text()


def test_dense_flush_of_a_histogram_block_runs_in_place(one_chip):
    """The flush of histdev_raw_32k's 6.44 GB bucket block: the per-row
    select (chunkstore._dense_set) aliases its donated block and holds no
    temp, where the one-program scatter asks for 6 GB beside it."""
    from filodb_tpu.core import chunkstore
    Sh, C, B = 1 << 15, 768, 64
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = chunkstore._dense_set.lower(
        sds((Sh, C, B), f32), sds((Sh,), i32), sds((Sh, B), f32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= Sh * C * B * 4
    assert mem.temp_size_in_bytes < (64 << 20), mem


def _mesh_fused_compiled(topo, residency):
    """One pjit ``dist_fused`` / ``dist_fused_narrow`` program lowered and
    compiled for the described 2x2 from operands in the serving leaf's own
    form (``DistributedStore``): four shards of 2^18 x 768, a device's block
    of each ``[NDEV * S, ...]`` global its shard's resident array, the
    window plan replicated, explicit NamedShardings both ways, the Mosaic
    kernel inside shard_map."""
    per, C, Tp, G = 1 << 18, 768, 128, 8
    mesh = Mesh(np.asarray(topo.devices), ("shard",))
    nd = mesh.devices.size
    assert nd == 4
    sh = NamedSharding(mesh, P("shard"))
    rep = NamedSharding(mesh, P())
    sds = jax.ShapeDtypeStruct
    rows = lambda dt: sds((nd * per,), dt, sharding=sh)  # noqa: E731
    plan = (sds((C, Tp), bf16, sharding=rep), sds((C, Tp), bf16, sharding=rep),
            sds((1, Tp), i32, sharding=rep), sds((1, Tp), i32, sharding=rep),
            sds((1, Tp), i32, sharding=rep))
    if residency == "raw":
        impl = distributed._dist_fused_aggregate_impl
        fn = lambda *a: impl("rate", "sum", G, mesh, WINDOW, IV, per, C, Tp,  # noqa: E731
                             0, 0, "pallas", *a)
        specs = distributed._FUSED_IN_SPECS
        args = ((sds((nd * per, C), f32, sharding=sh),),
                (rows(i32),), (rows(i32),)) + plan
    else:
        var = decodereg.variant(residency)
        impl = distributed._dist_fused_narrow_impl
        fn = lambda *a: impl("rate", "sum", G, mesh, WINDOW, IV, per, C, Tp,  # noqa: E731
                             residency, 0, 0, "pallas", *a)
        specs = distributed._FUSED_NARROW_IN_SPECS
        args = ((sds((nd * per, C), var.block_dtype, sharding=sh),),
                (tuple(rows(f32) for _ in range(var.row_operands)),),
                (rows(i32),), (rows(i32),)) + plan
    wrap = distributed._sharded_jit(mesh, specs, P("shard"))
    with jax.enable_x64(False):
        compiled = wrap(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled, per, C


def test_mesh_fused_program_compiles_for_four_chips(topo):
    compiled, per, C = _mesh_fused_compiled(topo, "raw")
    # each device holds its own shard and nothing of the others'
    assert compiled.memory_analysis().argument_size_in_bytes \
        < 1.05 * per * C * 4 + (8 << 20)
    # ... and turns no per-row operand of it to a column (256 MB of
    # temporaries a device, two copies a slot a query, before)
    _no_column_and_no_temp(compiled, per)


@pytest.mark.parametrize("residency", ["raw", "delta8"])
def test_mesh_fused_program_copies_no_block(topo, residency):
    """A device's block of the global IS the shard's resident array, so the
    program reads it in place (PR 46): no ``copy`` instruction at all — the
    ``copy f32[1,262144,768]`` of the chip's traces, 2.4 ms and 805 MB a
    query a chip, was the eager ``reshape((1, S, C))`` that stood in front
    of the ``[NDEV, S, C]`` assembly, a program of its own — and no
    temporary the size of a value block, nor of a hundredth of one."""
    compiled, per, C = _mesh_fused_compiled(topo, residency)
    text = compiled.as_text()
    copies = [ln.strip() for ln in text.splitlines()
              if re.search(r"= \S+ copy\(", ln)]
    assert not copies, copies[:3]
    assert not re.search(r"\[1,%d,%d\]" % (per, C), text)
    block = per * C * np.dtype(decodereg.variant(residency).block_dtype).itemsize
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < block // 100, mem
    assert mem.alias_size_in_bytes == 0, mem      # nothing donated


def test_a_narrow_gather_of_a_grid_store_takes_no_part_of_the_stamp_block(
        one_chip):
    """Eight rows of 2^20 x 768 (PR 41). The TPU has no 64-bit lanes: a
    program that takes the s64 stamp block as an operand splits ALL of it
    into two u32 planes — 3 GB of temporaries to hand back 48 KB — so the
    narrow leaf of a grid-form store derives its rows' stamps from their
    first stamps and gathers the values alone, in one program
    (``chunkstore._gather_grid``): no store-sized operand but the f32 block
    and the counts, no temporary."""
    from filodb_tpu.core import chunkstore
    C, P8 = 768, 8
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    with jax.enable_x64(True):
        i64 = jnp.int64
        taken = jax.jit(lambda ts, rid: jnp.take(ts, rid, axis=0)).lower(
            sds((S, C), i64), sds((P8,), i32)).compile()
        gathered = chunkstore._gather_grid.lower(
            sds((S, C), f32), sds((S,), i32), sds((3, P8), i64), C).compile()
    was = taken.memory_analysis()
    assert "X64Split" in taken.as_text()
    assert was.temp_size_in_bytes >= S * C * 4          # a whole u32 plane
    text, mem = gathered.as_text(), gathered.memory_analysis()
    assert f"s64[{S},{C}]" not in text and f"u32[{S},{C}]" not in text
    assert mem.temp_size_in_bytes < (1 << 20), mem
    assert mem.argument_size_in_bytes < S * C * 4 + S * 4 + (1 << 16)
    assert mem.output_size_in_bytes < P8 * C * (8 + 4) + (1 << 12)


@pytest.mark.parametrize("P", [1, 8])
def test_a_gathered_leafs_one_program_compiles_for_v5e_with_no_block_temp(
        one_chip, P):
    """The one program of a narrow leaf (PR 42: ``exec._leaf_body``) at the
    size ``tsbs_single`` runs it — 1 or 8 rows of 2^20 x 768, gather with
    derived stamps, ``max_over_time`` over 64 padded steps, the slice to 61
    and the ``max`` aggregate's map phase — takes the f32 block and the
    counts as its only store-sized operands, the host's values as small
    ones, and holds no temporary of a block's size."""
    import functools

    from filodb_tpu.core import chunkstore
    from filodb_tpu.query import exec as qexec
    C, T, Tpad = 768, 61, 64
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    with jax.enable_x64(True):
        i64, f64 = jnp.int64, jnp.float64
        # what GatheredWindow._dispatch packs: the picked rows, the step
        # grid, the window, the function's two arguments, the group ids
        spec, ints, floats, _dev = qexec._pack_operands((
            np.zeros((3, P), np.int64), np.zeros(Tpad, np.int64),
            np.int64(60_000), np.float64(0), np.float64(0),
            np.zeros(P, np.int32)))
        body = functools.partial(
            qexec._leaf_body,
            lambda val, n, picked: chunkstore._gather_grid(val, n, picked, C),
            "periodic", "max_over_time", "max", 1, T, spec, 1)
        compiled = jax.jit(body).lower(
            (sds((S, C), f32), sds((S,), i32)), sds(ints.shape, i64),
            sds(floats.shape, f64), ()).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert f"s64[{S},{C}]" not in text and f"u32[{S},{C}]" not in text
    assert mem.temp_size_in_bytes < (64 << 20), mem
    assert mem.argument_size_in_bytes < S * C * 4 + S * 4 + (1 << 16)
    assert mem.output_size_in_bytes < (1 << 16)


# -- the delta8 store at 12 h (PR 44): 2^20 x 4,608 one-byte columns ------------

C12H = 4608


def test_the_flush_of_a_delta8_store_at_twelve_hours_runs_in_place(one_chip):
    """The append of ``tsbs_cpu_100k_12h``: the per-row select writes an
    int8 delta into the 4.83 GB block in place — no f32 or s64 block, no
    second copy."""
    from filodb_tpu.core import chunkstore
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = chunkstore._dense_set.lower(
        sds((S, C12H), jnp.int8), sds((S,), i32), sds((S,), jnp.int8)).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert mem.alias_size_in_bytes >= S * C12H
    assert mem.temp_size_in_bytes < (64 << 20), mem
    assert f"f32[{S},{C12H}]" not in text and "s64[" not in text


def test_a_delta8_store_ages_out_in_row_blocks(one_chip):
    """``compact`` of the in-place form: one block of BLOCK_ROWS rows a
    program, the block donated, the temporaries a block's (the shifted
    deltas and their indices), never the store's."""
    from filodb_tpu.core import chunkstore
    rows = chunkstore.BLOCK_ROWS
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = chunkstore._compact_delta_block.lower(
        sds((S, C12H), jnp.int8), sds((S,), f32), sds((S,), i32),
        sds((S,), i32), sds((), i32), rows).compile()
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert mem.alias_size_in_bytes >= S * C12H
    assert mem.temp_size_in_bytes < rows * C12H * 12, mem
    assert f"[{S},{C12H}]" not in text.replace(f"s8[{S},{C12H}]", "")
    # the sum that check_filled and a rebuild's mirrors read: no f32 block
    summed = chunkstore._row_last.lower(
        sds((S, C12H), jnp.int8), sds((S,), f32)).compile()
    assert summed.memory_analysis().temp_size_in_bytes < (256 << 20)
    assert f"f32[{S},{C12H}]" not in summed.as_text()


@pytest.mark.parametrize("P", [1, 8])
def test_a_gathered_leaf_over_a_delta8_store_decodes_inside_its_one_program(
        one_chip, P):
    """The one program of ``tsbs_single_12h``'s leaf: 1 or 8 rows of 2^20 x
    4,608 gathered as int8, decoded (anchor + running sum, the pool laid
    over), their stamps derived, ``max_over_time`` over 736 padded steps,
    the slice to 721 and the ``max`` aggregate's map phase. Its store-sized
    operand is the int8 block alone, and it holds no temporary of a block's
    size."""
    import functools

    from filodb_tpu.core import chunkstore
    from filodb_tpu.query import exec as qexec
    T, Tpad = 721, 736
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    with jax.enable_x64(True):
        i64, f64 = jnp.int64, jnp.float64
        spec, ints, floats, _dev = qexec._pack_operands((
            np.zeros((3, P), np.int64), np.zeros(Tpad, np.int64),
            np.int64(60_000), np.float64(0), np.float64(0),
            np.zeros(P, np.int32)))
        body = functools.partial(
            qexec._leaf_body,
            lambda dv, anchor, pool, slot, n, picked:
            chunkstore._gather_grid_delta(dv, anchor, pool, slot, n, picked,
                                          C12H),
            "periodic", "max_over_time", "max", 1, T, spec, 1)
        compiled = jax.jit(body).lower(
            (sds((S, C12H), jnp.int8), sds((S,), f32), sds((1, C12H), f32),
             sds((S,), i32), sds((S,), i32)), sds(ints.shape, i64),
            sds(floats.shape, f64), ()).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert f"f32[{S},{C12H}]" not in text and f"s64[{S}," not in text
    assert mem.temp_size_in_bytes < (64 << 20), mem
    assert mem.argument_size_in_bytes < S * C12H + 3 * S * 4 + (1 << 16)
    assert mem.output_size_in_bytes < (1 << 16)


def test_the_fill_of_a_delta8_store_walks_a_row_block_a_program(one_chip):
    """``benchmark/data/tsbs_cpu_d8/fill.py`` at the deployment's size: one
    block of 2^16 rows walked 4,415 scrapes, its int8 deltas written into
    the donated block; the temporaries are a block's copies, under 1.5 GB
    beside 4.83 resident."""
    import importlib
    fill = importlib.import_module("benchmark.data.tsbs_cpu_d8.fill")
    walk_rows, _fill_n = fill._programs(4416, fill.ROWS)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    compiled = walk_rows.lower(
        sds((S, C12H), jnp.int8), sds((S,), i32), sds((), jnp.uint32),
        sds((), i32)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= S * C12H
    assert mem.temp_size_in_bytes < (1536 << 20), mem


# -- births: a store in time-aligned cells that holds rows born late ---------------

@pytest.mark.parametrize("fn,sumsq,G,variant", [
    ("rate", False, 8, "pallas"),            # adhoc_churn's four texts
    ("avg_over_time", False, 8, "pallas"),
    ("sum_over_time", True, 8, "pallas"),
    ("count_over_time", False, 8, "pallas"),  # the read-back's count probe
    ("rate", False, 64, "pallas"),
    ("rate", False, 8, "xla"),                # the twin
])
def test_the_births_mode_of_the_fused_grid_program_compiles_for_v5e(
        one_chip, fn, sumsq, G, variant):
    """promdev_churn_1m's program at 2^20 x 768 (fusedgrid.tile_contrib's
    births mode): the grid program's operands and one more, the store's
    ``born [S]``, packed above the count inside the program — no s64, no
    column, no temporary the grid program has not."""
    C, Tp = 768, 128
    prog = fusedgrid.fused_program(fn, sumsq, WINDOW, IV, S, SB, C, Tp, G,
                                   "raw", 0, 0, variant, 0, False, True)
    args = _store_args(one_chip, S, C, Tp)
    args.insert(3, jax.ShapeDtypeStruct((S,), i32, sharding=one_chip))
    if variant == "xla":
        with jax.enable_x64(False):
            compiled = jax.jit(prog).lower(*args).compile()
        assert "s64[" not in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < S * C * 5
        return
    compiled = _compile(prog, args)
    assert "s64[" not in compiled.as_text()
    _no_column_and_no_temp(compiled, S)


def test_the_fill_of_a_churned_store_runs_in_place(one_chip):
    """``benchmark/data/churn/fill.py`` at the deployment's size: values and
    stamps of every row from its birth to its end in donated elementwise
    programs — the value block in place with no temporary to speak of, the
    stamp block with the s64 split and nothing else (under 2 GB is asked of
    every other program of this store; the split is 6 GB, the flush's
    own)."""
    import importlib
    fill = importlib.import_module("benchmark.data.churn.fill")
    fill_val, fill_ts, fill_n = fill.programs()
    C = 768
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    with jax.enable_x64(True):
        val = fill_val.lower(sds((S, C), f32), sds((S,), jnp.uint32),
                             sds((S,), i32), sds((S,), i32),
                             sds((), jnp.uint32)).compile()
        ts = fill_ts.lower(sds((S, C), jnp.int64), sds((S,), i32),
                           sds((S,), i32), sds((), jnp.int64)).compile()
    mem = val.memory_analysis()
    assert mem.alias_size_in_bytes >= S * C * 4
    assert mem.temp_size_in_bytes < (2 << 30), mem
    mem = ts.memory_analysis()
    assert mem.alias_size_in_bytes >= S * C * 8
    assert mem.temp_size_in_bytes < S * C * 8 + (1 << 30), mem


def test_the_marks_before_a_birth_are_written_in_place(one_chip):
    """``chunkstore._mark_unborn`` at 2^20 x 768, the one program a birth
    adds to a flush: elementwise and donated, the value block in place."""
    from filodb_tpu.core import chunkstore
    C = 768
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)  # noqa: E731
    with jax.enable_x64(True):
        val = chunkstore._mark_unborn.lower(
            sds((S, C), f32), sds((S,), i32), 0, False).compile()
    mem = val.memory_analysis()
    assert mem.alias_size_in_bytes >= S * C * 4
    assert mem.temp_size_in_bytes < (64 << 20), mem
