"""Fused single-pass grid kernel: rate + cross-series aggregation in one read.

The north-star query ``sum(rate(metric[5m]))`` over a grid-aligned shard is
HBM-bound: the value store ([S, C] f32, gigabytes) dwarfs every other operand.
The two-step path (ops/gridfns.py ``_grid_kernel`` then
ops/aggregators.partial_aggregate) costs ~2.3 passes over HBM because XLA
materializes the per-cell increments and the [S, T] rate matrix between the
elementwise stage and the band matmuls.

This Pallas kernel streams the store once: for each [Sb, C] row tile it
  1. computes counter-corrected increments in VMEM (relu of adjacent diffs —
     a reset cell contributes 0, ref RateFunctions.scala extrapolatedRate),
  2. runs BOTH band products on the MXU while the tile is resident
     (``inc @ band_open`` for window deltas, ``v @ onehot_lo`` for the raw
     first-sample values needed by the counter zero-clamp),
  3. applies the Prometheus extrapolation algebra elementwise [Sb, T],
  4. folds the tile straight into per-group partial state ([G, T] sum/count
     via a one-hot MXU matmul) accumulated across the sequential row grid —
     the [S, T] rate matrix never exists in HBM.

Partial-state layout matches ops.aggregators.partial_aggregate so results
combine across shards/batches with combine_partials / the mesh psum path.

Numerics are identical to the two-step f32 path: same masks, same band
operands, same extrapolation expressions, f32 accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.tracing import SPAN_QUERY_KERNEL, span
from . import decodereg, gridfns

FUSED_FNS = {"rate", "increase", "delta"}
# window-aggregation shapes of the fused tier (ISSUE 9): the same one-pass
# select+decode+window+fold plan serves avg_over_time/sum_over_time-into-
# reduce dashboards — closed band instead of the open one, cnt >= 1 presence
FUSED_WINDOW_FNS = {"sum_over_time", "avg_over_time", "count_over_time"}
FUSED_OPS = {"sum", "avg", "count", "group", "stddev", "stdvar"}


def pallas_interpret() -> bool:
    """THE decision whether a Pallas kernel is compiled or interpreted:
    Mosaic compiles on a TPU backend, anything else (the CPU of the tests)
    runs the kernel body under ``interpret=True``. Every call site asks
    here, and :func:`kernel_tag` carries the answer into plan-cache keys
    and exec paths, so an interpreted kernel never passes for a compiled
    one in any output."""
    return jax.default_backend() != "tpu"


def kernel_tag(variant: str) -> str:
    """Name of the program a fused-tier backend ``variant`` ("pallas" |
    "xla") runs as HERE: "pallas" means compiled by Mosaic and nothing
    else; the interpreted kernel is "pallas-interpret"."""
    if variant == "pallas" and pallas_interpret():
        return "pallas-interpret"
    return variant


def _roundup(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def tile_contrib(fn: str, window_ms: int, interval_ms: int, c0: int,
                 v, n, band, ohlo, lo, hi, rel, roll):
    """Shared per-tile window math of the fused tier: decoded values
    ``v [Sb, Ca]`` -> ``(contrib [Sb, Tp]`` with absent cells zeroed,
    ``okf [Sb, Tp]`` presence as f32). ONE definition per tiling plan for
    BOTH backends: the Pallas kernel body reads its VMEM refs and calls
    this; the XLA-fused twin (ops/fusedresident.py) scans the same row
    tiles through it — variant parity is by construction, not discipline.
    ``roll`` abstracts the backend's shift primitive (pltpu.roll in-kernel,
    jnp.roll in the scan); the wrapped column's garbage is masked either
    way. ``band`` is the OPEN band for the rate family and the CLOSED band
    for the window-aggregation fns (host_operands builds the right one)."""
    f32 = jnp.float32
    Sb, Ca = v.shape
    lcol = jax.lax.broadcasted_iota(jnp.int32, (Sb, Ca), 1)
    col = lcol + c0                                           # global cell
    valid = col < n
    v = jnp.where(valid, v, 0.0)

    last_cell = n - 1                                         # [Sb, 1]
    f_idx = jnp.maximum(lo, 0)                                # [1, Tp]
    l_idx = jnp.minimum(hi, last_cell)                        # [Sb, Tp]
    cnt = jnp.maximum(l_idx - f_idx + 1, 0)
    cnt_f = cnt.astype(f32)

    if fn in FUSED_WINDOW_FNS:
        ok = cnt >= 1
        if fn == "count_over_time":
            return jnp.where(ok, cnt_f, 0.0), ok.astype(f32)
        s = jnp.dot(v, band, preferred_element_type=f32)      # closed band
        if fn == "avg_over_time":
            s = s / cnt_f
        return jnp.where(ok, s, 0.0), ok.astype(f32)

    is_counter = fn != "delta"
    # increments: valid cells are a prefix of each row, so cell c has a valid
    # predecessor exactly when c > 0 and c is valid; roll's column-0 wraparound
    # is masked out by that same condition. With a column offset the local
    # column 0 wraps to the slice's LAST column — its increment is garbage but
    # never consumed (band rows at/below the first window edge are zero);
    # zero it anyway so no value-dependent surprise can leak
    prev = roll(v)
    raw = v - prev
    inc = jnp.maximum(raw, 0.0) if is_counter else raw
    mask = valid & (col > 0)
    if c0:
        mask &= lcol > 0
    inc = jnp.where(mask, inc, 0.0)

    delta = jnp.dot(inc, band, preferred_element_type=f32)    # [Sb, Tp]
    f_v = jnp.dot(v, ohlo, preferred_element_type=f32)

    relf = rel.astype(f32)                                    # [1, Tp]
    f_rel = (f_idx * interval_ms).astype(f32)
    l_rel = (l_idx * interval_ms).astype(f32)
    dur_start = (f_rel - (relf - window_ms)) / 1000.0
    dur_end = (relf - l_rel) / 1000.0
    sampled = (l_rel - f_rel) / 1000.0
    avg_dur = sampled / (cnt_f - 1.0)
    if is_counter:
        safe = jnp.where(delta > 0, delta, 1.0)
        dur_zero = jnp.where(delta > 0, sampled * (f_v / safe), jnp.inf)
        dur_start = jnp.where((delta > 0) & (f_v >= 0) & (dur_zero < dur_start),
                              dur_zero, dur_start)
    thresh = avg_dur * 1.1
    extrap = sampled
    extrap = extrap + jnp.where(dur_start < thresh, dur_start, avg_dur / 2)
    extrap = extrap + jnp.where(dur_end < thresh, dur_end, avg_dur / 2)
    scaled = delta * (extrap / sampled)
    if fn == "rate":
        scaled = scaled * (1000.0 / window_ms)

    ok = cnt >= 2
    return jnp.where(ok, scaled, 0.0), ok.astype(f32)


# back-compat alias: the quant16 decode now lives in the shared decode-
# variant registry (ops/decodereg.py) next to its delta/hist siblings
decode_narrow_tile = decodereg.decode_quant16


def _kernel_body(fn: str, needs_sumsq: bool, window_ms: int, interval_ms: int,
                 Sb: int, Ca: int, Tp: int, G: int, residency: str, c0: int,
                 *refs):
    """``Ca`` is the streamed column width and ``c0`` its global offset into
    the store: a sub-range query streams (and matmuls) only its active
    columns (see active_columns); full-range queries have c0=0, Ca=C.
    ``residency`` names the decode variant (ops/decodereg.py) — the value
    block plus its per-row operands decode to f32 in VMEM per tile."""
    var = decodereg.variant(residency)
    R = var.row_operands
    val_ref = refs[0]
    rowrefs = refs[1:1 + R]
    (n_ref, gid_ref, band_ref, ohlo_ref,
     lo_ref, hi_ref, rel_ref, sum_ref, cnt_ref, *maybe_sumsq) = refs[1 + R:]
    i = pl.program_id(0)
    f32 = jnp.float32

    # decode in VMEM: the registered pallas twin of the residency variant
    v = var.pallas(val_ref[:], *(r[:] for r in rowrefs))      # [Sb, Ca]
    n = n_ref[:]                                              # [Sb, 1] i32
    # i32 shift: x64 mode would lower an i64 operand, which
    # tpu.dynamic_rotate rejects
    contrib, okf = tile_contrib(
        fn, window_ms, interval_ms, c0, v, n, band_ref[:], ohlo_ref[:],
        lo_ref[:], hi_ref[:], rel_ref[:],
        roll=lambda x: pltpu.roll(x, jnp.int32(1), 1))

    # per-group fold on the MXU: [G, Sb] one-hot x [Sb, Tp]
    gid = gid_ref[:]                                          # [Sb, 1] i32
    gcol = jax.lax.broadcasted_iota(jnp.int32, (Sb, G), 1)
    oh = (gcol == gid).astype(f32)                            # [Sb, G]
    dn = (((0,), (0,)), ((), ()))
    psum = jax.lax.dot_general(oh, contrib, dn, preferred_element_type=f32)
    pcnt = jax.lax.dot_general(oh, okf, dn, preferred_element_type=f32)

    @pl.when(i == 0)
    def _():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        cnt_ref[:] = jnp.zeros_like(cnt_ref)
        if needs_sumsq:
            maybe_sumsq[0][:] = jnp.zeros_like(maybe_sumsq[0])

    sum_ref[:] += psum
    cnt_ref[:] += pcnt
    if needs_sumsq:
        psq = jax.lax.dot_general(oh, contrib * contrib, dn,
                                  preferred_element_type=f32)
        maybe_sumsq[0][:] += psq


@functools.lru_cache(maxsize=64)
def build_pallas(fn: str, needs_sumsq: bool, window_ms: int, interval_ms: int,
                 S: int, Sb: int, C: int, Tp: int, G: int, interpret: bool,
                 residency: str = "raw", c0: int = 0, Ck: int = 0):
    """The raw (traceable) fused-kernel pallas_call — also invoked inside
    ``shard_map`` by the mesh executor (parallel/distributed.py), where each
    shard runs this same map phase on its resident block and the partial
    state crosses the ICI collective (ref: AggrOverRangeVectors.scala:62 —
    the identical map phase runs on every data node). ``residency`` names
    the decode variant (ops/decodereg.py): the value operand is that
    variant's narrow block plus its per-row operands (quant16: vmin/scale;
    delta16/delta8: anchor), decoded to f32 in VMEM per tile.

    ``(c0, Ca)`` describe the active column range (see active_columns): when
    it covers less than the full store, the kernel's value block starts at
    column ``c0`` and spans only ``Ca`` columns — HBM bytes and MXU MACs
    scale with the query's range, not the store's retention — and the band
    operands arrive pre-sliced to [Ca, Tp]. full_columns variants (the
    delta cumsum telescopes from cell 0) require c0=0."""
    var = decodereg.variant(residency)
    assert not var.full_columns or c0 == 0, (residency, c0)
    n_out = 3 if needs_sumsq else 2
    Ca = Ck if Ck else C
    out_shape = tuple(jax.ShapeDtypeStruct((G, Tp), jnp.float32)
                      for _ in range(n_out))
    body = functools.partial(_kernel_body, fn, needs_sumsq, window_ms,
                             interval_ms, Sb, Ca, Tp, G, residency, c0)
    acc_spec = pl.BlockSpec((G, Tp), lambda i: (0, 0), memory_space=pltpu.VMEM)
    const = functools.partial(pl.BlockSpec, index_map=lambda i: (0, 0),
                              memory_space=pltpu.VMEM)
    row = lambda shape: pl.BlockSpec(shape, lambda i: (i, 0),  # noqa: E731
                                     memory_space=pltpu.VMEM)
    kcol = c0 // Ca                       # active_columns guarantees c0 % Ca == 0
    in_specs = [pl.BlockSpec((Sb, Ca), lambda i: (i, kcol),
                             memory_space=pltpu.VMEM)]
    in_specs += [row((Sb, 1))] * var.row_operands   # vmin/scale or anchor
    in_specs += [
        row((Sb, 1)), row((Sb, 1)),
        const((Ca, Tp)), const((Ca, Tp)),
        const((1, Tp)), const((1, Tp)), const((1, Tp)),
    ]
    # scoped VMEM, stated from the footprint instead of the 16 MiB default:
    # the value tile and both bands double-buffered, the accumulators, and
    # the f32 working set of tile_contrib (decoded tile, shifted copy,
    # increments; a dozen [Sb, Tp] planes). At the caps (C=1024, Tp=512,
    # G=64) with exact f32 contractions the default runs out ("Ran out of
    # memory in memory space vmem", compiled for v5e)
    footprint = (2 * (Sb * Ca * jnp.dtype(var.block_dtype).itemsize
                      + 2 * Ca * Tp * 4)
                 + 2 * n_out * G * Tp * 4
                 + 4 * Sb * Ca * 4 + 12 * Sb * Tp * 4)
    return pl.pallas_call(
        body,
        grid=(S // Sb,),
        in_specs=in_specs,
        out_specs=tuple(acc_spec for _ in range(n_out)),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(VMEM_CAP, max(32 << 20, 2 * footprint))),
        interpret=interpret,
    )


def active_columns(C: int, lo: np.ndarray, hi: np.ndarray) -> tuple[int, int]:
    """(c0, Ca): the aligned store-column range the query actually reads —
    first-sample selects need cell max(0, lo.min()); window sums need cells
    (lo, hi]. Everything outside contributes nothing, so a sub-range query
    (a "last 30m" dashboard panel over hours of retention) streams and
    matmuls only its own columns. Constraint: the value block's offset must
    be a multiple of its width (Pallas block indexing), so Ca grows in
    128-steps until an aligned start covers the range — worst case the full
    store (c0=0, Ca=C), typical dashboards a small suffix of it. C must be
    a multiple of 128; callers get (0, C) otherwise."""
    if C % 128 != 0 or len(lo) == 0:
        return 0, C
    first = max(0, int(lo.min()))
    last = min(C - 1, int(hi.max()))
    if last < first:                      # empty windows: minimal block
        last = first
    c1 = _roundup(last + 1, 128)
    Ca = c1 - (first // 128) * 128
    while Ca < C:
        c0 = (first // Ca) * Ca
        # the block must cover [c0, c1) AND stay inside the store: for a
        # non-power-of-two C the last aligned block start can overhang the
        # store edge (e.g. C=640, Ca=384 -> c0=384, c0+Ca=768), which would
        # under-slice the band operand and read value columns past C
        if c0 + Ca >= c1 and c0 + Ca <= C:
            return c0, Ca
        Ca += 128
    return 0, C


def build_xla_tiles(fn: str, needs_sumsq: bool, window_ms: int,
                    interval_ms: int, S: int, Sb: int, C: int, Tp: int,
                    G: int, residency: str = "raw", c0: int = 0, Ck: int = 0):
    """XLA-fused twin of :func:`build_pallas`, built from the SAME tiling
    plan: one ``lax.scan`` walks the identical [Sb, Ca] row tiles through
    the identical :func:`tile_contrib` math and accumulates the same [G, Tp]
    partial state — one compiled program, intermediates bounded by one tile,
    the [S, T] matrix never materializes in HBM. Selected per
    ``query.fused_kernels`` (ops/fusedresident.py); signature-compatible
    with build_pallas's returned call so the mesh route swaps them freely.
    ``residency`` picks the registered xla decode twin (ops/decodereg.py)
    applied per tile — the full [S, C] f32 block never materializes on
    this variant either."""
    f32 = jnp.float32
    var = decodereg.variant(residency)
    assert not var.full_columns or c0 == 0, (residency, c0)
    R = var.row_operands
    Ca = Ck if Ck else C
    nt = S // Sb
    dn = (((0,), (0,)), ((), ()))
    roll = lambda x: jnp.roll(x, 1, axis=1)  # noqa: E731 — tile-local wrap,
    # masked in tile_contrib exactly like pltpu.roll's

    def fold(carry, xs, band, ohlo, lo, hi, rel):
        blk_t, *rest = xs
        v = var.xla(blk_t, *rest[:R])
        n_t, g_t = rest[R], rest[R + 1]
        contrib, okf = tile_contrib(fn, window_ms, interval_ms, c0,
                                    v, n_t, band, ohlo, lo, hi, rel, roll)
        gcol = jax.lax.broadcasted_iota(jnp.int32, (Sb, G), 1)
        oh = (gcol == g_t).astype(f32)
        out = (carry[0] + jax.lax.dot_general(oh, contrib, dn,
                                              preferred_element_type=f32),
               carry[1] + jax.lax.dot_general(oh, okf, dn,
                                              preferred_element_type=f32))
        if needs_sumsq:
            out += (carry[2] + jax.lax.dot_general(
                oh, contrib * contrib, dn, preferred_element_type=f32),)
        return out, None

    def run_tiles(tiles, band, ohlo, lo, hi, rel):
        init = tuple(jnp.zeros((G, Tp), f32)
                     for _ in range(3 if needs_sumsq else 2))
        outs, _ = jax.lax.scan(
            lambda c, xs: fold(c, xs, band, ohlo, lo, hi, rel), init, tiles)
        return outs

    def call(blk, *rest):
        # rest: R per-row decode operands, n2, g2, then the 5 band/edge ops;
        # active columns sliced like the pallas block index map
        rows, n2, g2 = rest[:R], rest[R], rest[R + 1]
        tiles = ((blk[:, c0:c0 + Ca].reshape(nt, Sb, Ca),)
                 + tuple(r.reshape(nt, Sb, 1) for r in rows)
                 + (n2.reshape(nt, Sb, 1), g2.reshape(nt, Sb, 1)))
        return run_tiles(tiles, *rest[R + 2:])
    return call


def _build_call(fn: str, needs_sumsq: bool, window_ms: int, interval_ms: int,
                S: int, Sb: int, C: int, Tp: int, G: int,
                residency: str = "raw", c0: int = 0, Ck: int = 0,
                variant: str = "pallas"):
    """The compiled fused program via the explicit plan cache (query/
    plancache.py) — its key IS this signature: fn/op statics, the padded
    [S, C, Tp, G] shape buckets, the ``residency`` decode variant
    ("raw" | "quant16" | "delta16" | "delta8"), and the backend ``variant``
    as :func:`kernel_tag` names it ("pallas" | "pallas-interpret" | "xla")
    — every (residency, backend) pair is a distinct program and caches as a
    distinct kernel variant."""
    from ..query.plancache import plan_cache
    R = decodereg.variant(residency).row_operands

    def build():
        if variant == "xla":
            call = build_xla_tiles(fn, needs_sumsq, window_ms, interval_ms,
                                   S, Sb, C, Tp, G, residency, c0, Ck)
        else:
            call = build_pallas(fn, needs_sumsq, window_ms, interval_ms,
                                S, Sb, C, Tp, G, variant != "pallas",
                                residency, c0, Ck)

        # one dispatch per query: dtype casts and [S] -> [S, 1] reshapes live
        # inside the jit — every extra dispatch is a host round trip of its
        # own beside the kernel's
        if residency != "raw":
            def wrapped(blk, *rest):
                rows = tuple(r.reshape(S, 1) for r in rest[:R])
                n, gids = rest[R], rest[R + 1]
                return call(blk, *rows,
                            n.astype(jnp.int32).reshape(S, 1),
                            gids.astype(jnp.int32).reshape(S, 1),
                            *rest[R + 2:])
        else:
            def wrapped(val, n, gids, *ops):
                return call(val.astype(jnp.float32),
                            n.astype(jnp.int32).reshape(S, 1),
                            gids.astype(jnp.int32).reshape(S, 1), *ops)
        return wrapped

    return plan_cache.program(
        "fused-grid",
        (fn, needs_sumsq, window_ms, interval_ms, S, Sb, C, Tp, G,
         residency, c0, Ck, variant), build)


def pad_edges(lo: np.ndarray, hi: np.ndarray, rel: np.ndarray,
              window_ms: int, Tp: int):
    """Step-edge operands padded to the kernel's Tp grid as [1, Tp] i32:
    lo zero-padded, hi padded with -1 (an empty window — cnt clamps to 0
    so padded steps contribute nothing), rel zero-padded. One definition
    for every fused tier (scalar here, hist in ops/fusedresident.py) —
    the sentinel values are kernel contracts, not formatting."""
    T = len(rel)
    assert abs(rel).max(initial=0) < 2**31 and window_ms < 2**31
    lo_p = np.zeros(Tp, np.int32); lo_p[:T] = lo
    hi_p = np.full(Tp, -1, np.int32); hi_p[:T] = hi
    rel_p = np.zeros(Tp, np.int32); rel_p[:T] = rel
    return (lo_p.reshape(1, Tp), hi_p.reshape(1, Tp), rel_p.reshape(1, Tp))


def host_operands(C: int, Tp: int, out_ts: np.ndarray, window_ms: int,
                  base_ts: int, interval_ms: int, fn_kind: str = "rate",
                  full_cols: bool = False):
    """Band/one-hot/edge operands as host arrays + active column range:
    (band, ohlo, lo[1,Tp], hi[1,Tp], rel[1,Tp], c0, Ck) — shared by the
    single-chip upload cache below and the mesh path (which replicates them
    across shard devices). For a sub-range query the band/ohlo rows are
    sliced to the active [c0, c0+Ck) columns (the tiled kernel streams
    only those store tiles); full-range queries keep [C, Tp] operands.
    ``fn_kind`` picks the band form: "rate" builds the OPEN band the
    increment matmul needs, "window" the CLOSED band of the *_over_time
    fns (tile_contrib consumes whichever matches its fn). ``full_cols``
    bypasses active-column slicing — required by full_columns decode
    variants whose per-tile decode telescopes from cell 0."""
    T = len(out_ts)
    lo, hi = gridfns.grid_edges(out_ts, window_ms, base_ts, interval_ms)
    rel = out_ts - base_ts
    lo_p, hi_p, rel_p = pad_edges(lo, hi, rel, window_ms, Tp)
    band = np.zeros((C, Tp), np.float32)
    band[:, :T] = gridfns.band_matrix(C, lo, hi, fn_kind == "rate",
                                      np.float32)
    ohlo = np.zeros((C, Tp), np.float32)
    ohlo[:, :T] = gridfns.onehot_matrix(C, np.maximum(lo, 0), np.float32)
    c0, Ca = (0, C) if full_cols else active_columns(C, lo, hi)
    if Ca < C:
        band = np.ascontiguousarray(band[c0:c0 + Ca])
        ohlo = np.ascontiguousarray(ohlo[c0:c0 + Ca])
    return (band, ohlo, lo_p, hi_p, rel_p, c0, Ca)


@functools.lru_cache(maxsize=32)
def _device_operands(C: int, Tp: int, out_ts_key: bytes, window_ms: int,
                     base_ts: int, interval_ms: int, fn_kind: str = "rate",
                     full_cols: bool = False):
    """Band/one-hot/edge operands on device, cached per query shape — the
    upload matters: repeated host->device transfers of the [C, Tp] bands per
    row-batch are megabytes per query that never change."""
    out_ts = np.frombuffer(out_ts_key, np.int64)
    *arrs, c0, Ck = host_operands(C, Tp, out_ts, window_ms, base_ts,
                                  interval_ms, fn_kind, full_cols)
    return tuple(jnp.asarray(a) for a in arrs) + (c0, Ck)


# conservative VMEM-driven caps for the fused path; beyond them callers must
# take the two-step route (which switches to segment_sum for large G)
MAX_GROUPS = 64          # matches aggregators.MATMUL_GROUP_LIMIT
MAX_STEPS = 512          # Tp cap: resident [C, Tp] bands + [Sb, Tp] tiles
MAX_CAPACITY = 1024      # C cap: [Sb, C] row tile + bands
# ceiling of the scoped-VMEM limit a fused kernel asks Mosaic for (a v5e has
# 128 MiB of VMEM; each builder states its own footprint below this)
VMEM_CAP = 96 << 20


def fusable(S: int, C: int, T: int, num_groups: int) -> bool:
    """Shape gate: the kernel keeps its operands resident in VMEM."""
    return (C <= MAX_CAPACITY
            and _roundup(max(T, 1), 128) <= MAX_STEPS
            and num_groups <= MAX_GROUPS
            and (S % 512 == 0 or (S <= 512 and S % 8 == 0)))


class PaddedPartials:
    """Device-resident padded kernel outputs, fetched lazily: the leaf holds
    the shard lock while dispatching — blocking there on a device_get would
    stall every ingest/query thread for the whole streaming pass. resolve()
    runs at present/merge time, outside the lock."""

    def __init__(self, outs, op: str, num_groups: int, T: int):
        self._outs = outs
        self._op = op
        self._ng = num_groups
        self._T = T

    def parts_of(self, outs) -> dict:
        """Partial dict from ALREADY-FETCHED outputs (callers batching many
        bundles into one device_get use this instead of resolve())."""
        s, c = outs[0][:self._ng, :self._T], outs[1][:self._ng, :self._T]
        if self._op in ("count", "group"):
            return {"count": c}
        parts = {"sum": s, "count": c}
        if len(outs) > 2:
            parts["sumsq"] = outs[2][:self._ng, :self._T]
        return parts

    def resolve(self) -> dict:
        with span(SPAN_QUERY_KERNEL, phase="fetch"):
            outs = jax.device_get(self._outs)
        return self.parts_of(outs)


def fused_grid_aggregate(op: str, fn: str, val, n, gids, num_groups: int,
                         out_ts: np.ndarray, window_ms: int,
                         base_ts: int, interval_ms: int, fetch: bool = True,
                         narrow=None, variant: str = "pallas"):
    """One-pass ``op(fn(metric[window]))`` partials over a grid-aligned block.

    val [S, C] f32 (S a multiple of 512 or a power of two), n [S] i32 valid
    counts, gids [S] i32 dense group ids (< num_groups). Returns the same
    partial-state dict as ``aggregators.partial_aggregate(op, ...)`` with
    [num_groups, T] arrays, combinable via ``combine_partials`` / psum.
    With ``fetch=False`` returns a :class:`PaddedPartials` whose ``resolve()``
    does the (blocking) host fetch later. ``narrow=(kind, operands)`` streams
    a registered narrow block (ops/decodereg.py) instead of ``val``: kind
    names the decode variant ("quant16" | "delta16" | "delta8") and
    ``operands = (block, *row_operands)`` its device arrays — 1/4 to 1/2 the
    HBM bytes; the caller must already have zeroed ``n`` for rows whose
    narrow encoding is not bit-exact.
    """
    assert fn in FUSED_FNS | FUSED_WINDOW_FNS and op in FUSED_OPS
    if narrow is not None:
        kind, nops = narrow
        S, C = nops[0].shape
    else:
        kind, nops = "raw", None
        S, C = val.shape
    T = len(out_ts)
    assert fusable(S, C, T, num_groups), (S, C, T, num_groups)
    Tp = _roundup(max(T, 1), 128)
    Sb = 512 if S % 512 == 0 else (S if S <= 512 else None)
    G = _roundup(max(num_groups, 8), 8)

    band, ohlo, lo_d, hi_d, rel_d, c0, Ck = _device_operands(
        C, Tp, np.ascontiguousarray(np.asarray(out_ts, np.int64)).tobytes(),
        int(window_ms), int(base_ts), int(interval_ms),
        "window" if fn in FUSED_WINDOW_FNS else "rate",
        decodereg.variant(kind).full_columns)

    needs_sumsq = op in ("stddev", "stdvar")
    call = _build_call(fn, needs_sumsq, int(window_ms), int(interval_ms),
                       S, Sb, C, Tp, G, kind, c0, Ck, kernel_tag(variant))
    # the framework runs with x64 on (int64 timestamps); Mosaic rejects the
    # i64 scalars x64 tracing injects (grid index maps, roll shifts), and the
    # kernel itself is pure f32/i32 — so trace the call with x64 off.
    # The span's tags are what ties a device event to its query and gives
    # the bytes the kernel streams from inside (rows x cols from c0 on)
    with span(SPAN_QUERY_KERNEL, phase="dispatch",
              kernel=kernel_tag(variant), rows=S, c0=c0, cols=Ck, steps=T,
              groups=num_groups), jax.enable_x64(False):
        if nops is not None:
            outs = call(*nops, jnp.asarray(n), jnp.asarray(gids),
                        band, ohlo, lo_d, hi_d, rel_d)
        else:
            outs = call(val, jnp.asarray(n), jnp.asarray(gids),
                        band, ohlo, lo_d, hi_d, rel_d)
    # partial state is tiny ([G, Tp]): ONE host fetch finishes the query — the
    # slice/present/combine chain as device ops would cost a round-trip each
    padded = PaddedPartials(outs, op, num_groups, T)
    return padded.resolve() if fetch else padded


@functools.lru_cache(maxsize=8)
def zero_gids(S: int):
    """Cached device zeros for single-group (global) aggregation — uploading
    a fresh [S] int32 per query is a 4 MB host->device transfer for 1M
    series."""
    return jnp.zeros(S, jnp.int32)
