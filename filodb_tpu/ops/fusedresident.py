"""Fused compressed-resident query kernels: the single-pass execution tier.

Reference role: the reference FiloDB's performance core is hand-rolled
columnar kernels (NibblePack, 2D-delta, XOR) that compute ON compressed data
in place — select, decode, window function, and aggregation run as one
iterator chain per chunk (PAPER.md §0; doc/compression.md). This module is
the TPU analog for the top query shapes: delta reconstruction, bucket-cumsum
commutation, the range function, and the segment reduce execute as ONE
device program per shape, with no intermediate f32 materialization of the
decoded store — per-tile state lives in registers/VMEM.

The registry below keys three fused shapes, each implemented TWICE from the
same tiling plan and selected at plan time by ``query.fused_kernels``:

  shape           query pattern                       tile math shared by
  --------------  ----------------------------------  --------------------
  rate_sum        sum/avg/...(rate|increase|delta)    fusedgrid.tile_contrib
  window_reduce   sum/...(avg_over_time|sum_over_time fusedgrid.tile_contrib
                  |count_over_time)
  hist_quantile   histogram_quantile(q, sum(fn(h[w])) hist_series_contrib
                  over i8/i16 2D-delta-resident blocks  (this module)

Backends per shape:
  * ``pallas`` — a Pallas kernel streaming [Sb, ...] row tiles; on CPU it
    runs under ``pl.pallas_call(..., interpret=True)`` so tier-1 exercises
    the real kernel body, and the compiled Mosaic path lights up on TPU.
  * ``xla`` — an XLA-fused fallback built from the SAME tiling plan: one
    ``lax.scan`` walks the identical tiles through the identical tile math
    (variant parity by construction). This is also the portable path for
    backends without Pallas.
  * ``off`` — the composed two-step chain (grid kernel + segment reduce
    with the intermediate [S, T(,B)] matrix), the A/B baseline.

Both variants of a shape are DISTINCT kernel variants in the process-global
compiled-plan cache (query/plancache.py): the variant name is part of the
key, so switching modes never aliases programs and warmup covers whichever
variant will serve.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.metrics import (FILODB_QUERY_FUSED_FALLBACK,
                             FILODB_QUERY_FUSED_SERVED, registry)
from . import decodereg, fusedgrid, gridfns
from .fusedgrid import dot_exact01

MODES = ("off", "xla", "pallas")

# process-global execution mode, like the plan cache and the tracer: every
# serving path (in-process exec, fused-hist engine route, mesh collectives,
# warmup) must agree on the variant or warm programs would miss at serve
# time. Set once at startup from ``query.fused_kernels`` (standalone.py);
# tests flip it under try/finally.
_mode: str = "pallas"

HIST_FUSED_FNS = frozenset({"rate", "increase", "delta"})
MAX_BUCKETS = 64    # [Sb, B, C] tile + [G, B, Tp] accumulators stay in VMEM

# the declarative registry: shape name -> (window fns, reduce ops) it serves.
# exec.py / engine.py consult it for plan-time eligibility; the bench suite
# and warmup iterate it so every shape is covered by measurement and
# pre-tracing alike.
FUSED_SHAPES = {
    "rate_sum": (frozenset(fusedgrid.FUSED_FNS),
                 frozenset(fusedgrid.FUSED_OPS)),
    "window_reduce": (frozenset(fusedgrid.FUSED_WINDOW_FNS),
                      frozenset(fusedgrid.FUSED_OPS)),
    "hist_quantile": (HIST_FUSED_FNS, frozenset({"sum"})),
}


def mode() -> str:
    """The active fused-kernel mode ("off" | "xla" | "pallas")."""
    return _mode


def set_mode(m: str) -> None:
    """Select the fused-kernel tier (config: ``query.fused_kernels``)."""
    global _mode
    if m not in MODES:
        raise ValueError(f"query.fused_kernels must be one of {MODES}, "
                         f"got {m!r}")
    _mode = m


def tag() -> str:
    """The active mode as exec paths and plan-cache keys name it: "xla",
    "pallas" (compiled by Mosaic) or "pallas-interpret" — see
    fusedgrid.kernel_tag."""
    return fusedgrid.kernel_tag(_mode)


def scalar_shape_of(fn: str) -> str | None:
    """Registry shape serving a scalar window fn, or None."""
    if fn in fusedgrid.FUSED_FNS:
        return "rate_sum"
    if fn in fusedgrid.FUSED_WINDOW_FNS:
        return "window_reduce"
    return None


def count_served(shape: str) -> None:
    registry.counter(FILODB_QUERY_FUSED_SERVED,
                     {"shape": shape, "mode": _mode}).increment()


def count_fall_tiles(falls) -> int:
    """Fetch the raw hist tier's [1] count of tiles that ran the correction
    matmul (fused_hist_quantile_raw), add it to the registry, return it."""
    return fusedgrid.count_fall_tiles(falls, "hist", _mode)


def count_fallback(shape: str) -> None:
    """A query matched a fused shape but fell back to the composed path
    (shape gate, group cap, off-grid store, ...)."""
    registry.counter(FILODB_QUERY_FUSED_FALLBACK, {"shape": shape}).increment()


# ---------------------------------------------------------------------------
# scalar shapes (rate_sum / window_reduce): thin mode dispatch over the two
# backends that share ops/fusedgrid.tile_contrib and its tiling plan
# ---------------------------------------------------------------------------

def scalar_aggregate(op: str, fn: str, val, n, gids, num_groups: int,
                     out_ts: np.ndarray, window_ms: int, base_ts: int,
                     interval_ms: int, fetch: bool = True, narrow=None,
                     line=None, holes: bool = False, born=None,
                     born_late: int = 0):
    """Mode-routed one-pass ``op(fn(metric[w]))`` partials (see
    fusedgrid.fused_grid_aggregate for operand contracts;
    ``narrow=(kind, operands)`` streams a registered narrow block —
    ops/decodereg.py — decoded in VMEM per tile). Caller checked
    eligibility and guarantees ``mode() != "off"``."""
    assert _mode != "off"
    out = fusedgrid.fused_grid_aggregate(
        op, fn, val, n, gids, num_groups, out_ts, window_ms, base_ts,
        interval_ms, fetch=fetch, narrow=narrow, variant=_mode, line=line,
        holes=holes, born=born, born_late=born_late)
    count_served(scalar_shape_of(fn) or "rate_sum")
    return out


# ---------------------------------------------------------------------------
# hist_quantile: fused histogram_quantile over i8/i16 2D-delta-resident
# [S, C, B] blocks — the narrow dd state streams through static matmuls and
# ONE bucket cumsum per tile; the decoded f32 store never exists
# ---------------------------------------------------------------------------

_roundup = fusedgrid._roundup


# accumulators are [G, B, Tp] f32 in VMEM, twice (sum, count): the gate
# keeps G * Tp * B * 4 bytes per accumulator at or below 2 MiB
MAX_HIST_ACC_CELLS = 1 << 19


def hist_rows_per_tile(S: int) -> int:
    """Series per grid step of the hist kernel. One series' [B, C] dd frame
    is 48 KiB at 64 x 768 i8 (96 KiB at i16), so 16 of them double-buffered
    stay under 3 MiB; the old 512-row [Sb, C, B] tile was 24 MiB for one
    buffer before lane padding, and never met a TPU."""
    return 16 if S % 16 == 0 else 8


def hist_fusable(S: int, C: int, T: int, B: int, num_groups: int) -> bool:
    """Shape gate: per-tile operands + [G, B, Tp] accumulators stay in VMEM.
    Unlike the scalar tier there is no active-column slicing: the quantile's
    first-sample prefix bands need every column from cell 0."""
    Tp = _roundup(max(T, 1), 128)
    G = _roundup(max(num_groups, 8), 8)
    return (C <= fusedgrid.MAX_CAPACITY
            and Tp <= fusedgrid.MAX_STEPS
            and G * Tp * B <= MAX_HIST_ACC_CELLS
            and num_groups <= fusedgrid.MAX_GROUPS
            and 0 < B <= MAX_BUCKETS
            and (S % 1024 == 0 or (S <= 512 and S % 8 == 0)))


def hist_series_contrib(fn: str, window_ms: int, interval_ms: int,
                        x, fd_row, n, band_open, prefix_lo, lo, hi, rel):
    """Shared per-series math of the hist_quantile shape: one series'
    decoded 2D-delta frames ``x [B, C]`` — buckets on sublanes, cells on
    lanes — (+ ``fd_row [1, B]`` first-frame bucket deltas, ``n`` its valid
    count, an i32 scalar) -> ``(contrib, okb)`` both ``[B, Tp]``. Steps ride
    the lanes, so the per-step edge vectors stay ``[1, Tp]`` rows and
    nothing is transposed or reshaped across the lane axis in the kernel
    (Mosaic lowers neither for a 64-wide minor dimension). Both backends
    call this — the Pallas body per series of its VMEM tile, the XLA twin
    inside its scan.

    The bucket-cumsum commutation (ops/gridfns.py narrow-hist notes): the
    window delta of cumulative buckets equals ``cumsum_b(dd @ band_open)``
    and the first-sample value ``cumsum_b(first_d + dd @ prefix_lo)`` —
    every reduction is LINEAR in the frames, so the matmuls read the NARROW
    dd encoding directly. The bucket cumsum is a lower-triangular matmul
    (Mosaic has no cumsum primitive); every operand of these four matmuls
    is an integer below 2^24 times a 0/1 weight, so at HIGHEST precision
    they are exact in any accumulation order. The per-(series, step)
    extrapolation algebra is _grid_hist_kernel_narrow's, elementwise."""
    f32 = jnp.float32
    hp = jax.lax.Precision.HIGHEST
    B = x.shape[0]
    db = jnp.dot(x, band_open, precision=hp,
                 preferred_element_type=f32)                  # [B, Tp]
    fb = jnp.dot(x, prefix_lo, precision=hp, preferred_element_type=f32)
    r = jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
    tri = (c <= r).astype(f32)                                # cumsum over b
    # the row of first-frame deltas as a column, without a transpose: mask
    # its sublane broadcast with the identity and reduce along lanes
    fd_col = jnp.sum(jnp.where(r == c, jnp.broadcast_to(fd_row, (B, B)), 0.0),
                     axis=1, keepdims=True)                   # [B, 1]
    delta = jnp.dot(tri, db, precision=hp, preferred_element_type=f32)
    f_v = jnp.dot(tri, fd_col + fb, precision=hp,
                  preferred_element_type=f32)
    return hist_extrapolate(fn, window_ms, interval_ms, delta, f_v, n,
                            lo, hi, rel)


def hist_extrapolate(fn: str, window_ms: int, interval_ms: int,
                     delta, f_v, n, lo, hi, rel):
    """One series' per-bucket window deltas ``delta [B, Tp]`` and
    first-sample values ``f_v [B, Tp]`` (``n`` its valid count, an i32
    scalar; ``lo``/``hi``/``rel`` the ``[1, Tp]`` step edges) ->
    ``(contrib, okb)`` both ``[B, Tp]``: the per-(series, step)
    extrapolation algebra of _grid_hist_kernel, elementwise. Shared by the
    narrow tier above and the raw tier below: what differs between them is
    only how ``delta`` and ``f_v`` come out of the resident block."""
    f32 = jnp.float32
    last_cell = n - 1                                         # scalar
    f_idx = jnp.maximum(lo, 0)                                # [1, Tp]
    l_idx = jnp.minimum(hi, last_cell)
    cnt = jnp.maximum(l_idx - f_idx + 1, 0)
    cnt_f = cnt.astype(f32)
    relf = rel.astype(f32)
    f_rel = (f_idx * interval_ms).astype(f32)
    l_rel = (l_idx * interval_ms).astype(f32)
    dur_start = (f_rel - (relf - window_ms)) / 1000.0         # [1, Tp]
    dur_end = (relf - l_rel) / 1000.0
    sampled = (l_rel - f_rel) / 1000.0
    avg_dur = sampled / (cnt_f - 1.0)
    thresh = avg_dur * 1.1
    if fn != "delta":
        # per-bucket counter zero-clamp — same expressions as the composed
        # narrow kernel (_grid_hist_kernel_narrow)
        dur_zero = jnp.where(delta > 0, sampled * (f_v / delta), jnp.inf)
        ds = jnp.broadcast_to(dur_start, delta.shape)
        ds = jnp.where((delta > 0) & (f_v >= 0) & (dur_zero < ds),
                       dur_zero, ds)
        extrap = sampled \
            + jnp.where(ds < thresh, ds, avg_dur / 2) \
            + jnp.where(dur_end < thresh, dur_end, avg_dur / 2)
        factor = extrap / sampled                             # [B, Tp]
    else:
        extrap = sampled \
            + jnp.where(dur_start < thresh, dur_start, avg_dur / 2) \
            + jnp.where(dur_end < thresh, dur_end, avg_dur / 2)
        factor = extrap / sampled                             # [1, Tp]
    scaled = delta * factor
    if fn == "rate":
        scaled = scaled * (1000.0 / window_ms)

    ok = cnt >= 2                                             # [1, Tp]
    contrib = jnp.where(ok, scaled, 0.0)                      # [B, Tp]
    okb = jnp.broadcast_to(ok, contrib.shape).astype(f32)
    return contrib, okb


def _hist_kernel_body(fn: str, window_ms: int, interval_ms: int, Sb: int,
                      per: int, G: int, n_ref, gid_ref, dd_ref, fd_ref,
                      band_ref, plo_ref, lo_ref, hi_ref, rel_ref, sum_ref,
                      cnt_ref):
    """One grid step = ``Sb`` series: n/gid are SMEM scalars, so a series
    folds into its group's [B, Tp] accumulator by a dynamic first-axis
    index — the fold is sequential in series order, which the XLA twin
    repeats add for add. One SMEM scalar block spans ``per`` consecutive
    grid steps (XLA lays a rank-1 i32 array out in tiles of 1024, and Mosaic
    wants the SMEM block to match it — or to be the whole array)."""
    base = (pl.program_id(0) % per) * Sb
    @pl.when(pl.program_id(0) == 0)
    def _():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        cnt_ref[:] = jnp.zeros_like(cnt_ref)

    def one_series(s, carry):
        n = n_ref[base + s]
        g = gid_ref[base + s]

        # empty slots and excluded rows (cohort pool: gid out of range)
        # contribute nothing — skip their matmuls altogether
        @pl.when((n > 0) & (g >= 0) & (g < G))
        def _():
            # i8/i16 decode in VMEM via the registered hist twin
            x = decodereg.decode_hist(dd_ref[s], None)        # [B, C]
            contrib, okb = hist_series_contrib(
                fn, window_ms, interval_ms, x, fd_ref[pl.ds(s, 1), :], n,
                band_ref[:], plo_ref[:], lo_ref[:], hi_ref[:], rel_ref[:])
            sum_ref[g] += contrib
            cnt_ref[g] += okb
        return carry

    jax.lax.fori_loop(0, Sb, one_series, 0)


def _pad(x: int, m: int) -> int:
    return _roundup(max(x, 1), m)


@functools.lru_cache(maxsize=32)
def build_hist_pallas(fn: str, window_ms: int, interval_ms: int, S: int,
                      Sb: int, C: int, Tp: int, B: int, G: int,
                      interpret: bool, dd_bytes: int = 1):
    """The raw (traceable) fused hist-quantile map-phase pallas_call: grid
    over [Sb] series tiles of the dd block, [G, B, Tp] partial-state
    accumulators resident in VMEM across the sequential grid. Operands: n
    [S] i32 and gids [S] i32 (SMEM), dd as [S, B, C] — cells on the lane
    axis, which is how the TPU already lays a resident [S, C, 64] block out
    in HBM (minor-to-major {1,2,0}: asked for row-major [S, C, B] instead,
    XLA transposes the whole store into a 2x lane-padded copy per query,
    8.6 GB of temp at 2^16 x 768 x 64 i8) — first_d [S, B], bands [C, Tp],
    edges [1, Tp]. The scoped-VMEM limit is stated explicitly from the tile
    footprint (everything double-buffered) instead of leaning on the
    16 MiB default."""
    sblk = 1024 if S % 1024 == 0 else S
    per = sblk // Sb
    body = functools.partial(_hist_kernel_body, fn, window_ms, interval_ms,
                             Sb, per, G)
    acc = pl.BlockSpec((G, B, Tp), lambda i: (0, 0, 0),
                       memory_space=pltpu.VMEM)
    const = functools.partial(pl.BlockSpec, index_map=lambda i: (0, 0),
                              memory_space=pltpu.VMEM)
    scalars = pl.BlockSpec((sblk,), lambda i: (i // per,),
                           memory_space=pltpu.SMEM)
    in_specs = [
        scalars, scalars,                                       # n, gid
        pl.BlockSpec((Sb, B, C), lambda i: (i, 0, 0),
                     memory_space=pltpu.VMEM),                  # dd
        pl.BlockSpec((Sb, B), lambda i: (i, 0),
                     memory_space=pltpu.VMEM),                  # first_d
        const((C, Tp)), const((C, Tp)),                         # bands
        const((1, Tp)), const((1, Tp)), const((1, Tp)),         # lo, hi, rel
    ]
    Cp, Bp = _pad(C, 128), _pad(B, 32)
    footprint = 2 * (Sb * Bp * Cp * dd_bytes                    # dd tile
                     + _pad(Sb, 8) * _pad(B, 128) * 4           # first_d
                     + 2 * _pad(C, 8) * Tp * 4                  # bands
                     + 2 * G * Bp * Tp * 4)                     # accumulators
    # + the per-series f32 working set (decoded frame, matmul results)
    footprint += Bp * Cp * 4 + 12 * Bp * Tp * 4
    return pl.pallas_call(
        body,
        grid=(S // Sb,),
        in_specs=in_specs,
        out_specs=(acc, acc),
        out_shape=tuple(jax.ShapeDtypeStruct((G, B, Tp), jnp.float32)
                        for _ in range(2)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(fusedgrid.VMEM_CAP,
                                 max(32 << 20, 2 * footprint))),
        interpret=interpret,
    )


def build_hist_xla_tiles(fn: str, window_ms: int, interval_ms: int, S: int,
                         Sb: int, C: int, Tp: int, B: int, G: int):
    """XLA twin of :func:`build_hist_pallas`: one lax.scan walks the series
    in the same order through the same hist_series_contrib and folds each
    into its group's accumulator with the same adds; intermediates are
    bounded by one series."""
    f32 = jnp.float32

    def call(n, gids, dd, first_d, band, plo, lo, hi, rel):
        def fold(carry, xs):
            n_s, g_s, dd_s, fd_s = xs
            contrib, okb = hist_series_contrib(
                fn, window_ms, interval_ms, decodereg.decode_hist(dd_s, None),
                fd_s, n_s, band, plo, lo, hi, rel)
            live = (n_s > 0) & (g_s >= 0) & (g_s < G)
            gi = jnp.clip(g_s, 0, G - 1)
            return (carry[0].at[gi].add(jnp.where(live, contrib, 0.0)),
                    carry[1].at[gi].add(jnp.where(live, okb, 0.0))), None

        init = (jnp.zeros((G, B, Tp), f32), jnp.zeros((G, B, Tp), f32))
        outs, _ = jax.lax.scan(fold, init,
                               (n, gids, dd, first_d[:, None, :]))
        return outs

    return call


def _hist_operands(C: int, Tp: int, out_ts: np.ndarray, window_ms: int,
                   base_ts: int, interval_ms: int):
    """Host operand build for the hist tier: open band for window deltas,
    prefix band selecting v at the lo cells (cells [1..l0] — needs every
    column from 0, hence no active-column slicing here), padded edges."""
    T = len(out_ts)
    lo, hi = gridfns.grid_edges(out_ts, window_ms, base_ts, interval_ms)
    rel = out_ts - base_ts
    lo_p, hi_p, rel_p = fusedgrid.pad_edges(lo, hi, rel, window_ms, Tp)
    band = np.zeros((C, Tp), np.float32)
    band[:, :T] = gridfns.band_matrix(C, lo, hi, True, np.float32)
    l0 = np.maximum(lo, 0)
    plo = np.zeros((C, Tp), np.float32)
    plo[:, :T] = gridfns.band_matrix(C, np.zeros(T, np.int64),
                                     np.minimum(l0, C - 1), True, np.float32)
    return (band, plo, lo_p, hi_p, rel_p)


@functools.lru_cache(maxsize=32)
def _hist_device_operands(C: int, Tp: int, out_ts_key: bytes, window_ms: int,
                          base_ts: int, interval_ms: int):
    out_ts = np.frombuffer(out_ts_key, np.int64)
    return tuple(jnp.asarray(a) for a in _hist_operands(
        C, Tp, out_ts, window_ms, base_ts, interval_ms))


def _hist_map_program(variant: str, fn: str, window_ms: int, interval_ms: int,
                      S: int, Sb: int, C: int, Tp: int, B: int, G: int,
                      dd_dtype: str):
    """The cached map-phase program. ``variant`` is fusedgrid.kernel_tag's
    name — "xla" | "pallas" | "pallas-interpret" — and part of the key: the
    backends, and a compiled and an interpreted kernel, are distinct
    programs. Wrapped so dtype casts and reshapes ride the one dispatch."""
    from ..query.plancache import plan_cache

    def build():
        if variant == "xla":
            call = build_hist_xla_tiles(fn, window_ms, interval_ms,
                                        S, Sb, C, Tp, B, G)
        else:
            call = build_hist_pallas(fn, window_ms, interval_ms, S, Sb, C,
                                     Tp, B, G, variant != "pallas",
                                     jnp.dtype(dd_dtype).itemsize)

        def wrapped(dd, first_d, n, gids, band, plo, lo, hi, rel):
            # [S, C, B] -> [S, B, C]: on the TPU a relabelling of the
            # resident block's own layout, not a copy (build_hist_pallas)
            return call(n.astype(jnp.int32), gids.astype(jnp.int32),
                        dd.transpose(0, 2, 1), first_d, band, plo, lo, hi,
                        rel)
        return wrapped

    return plan_cache.program(
        "fusedres-hist",
        (variant, fn, window_ms, interval_ms, S, Sb, C, Tp, B, G, dd_dtype),
        build)


def _hist_finish_program(G: int, T: int, Tp: int, B: int, has_corr: bool,
                         nles: int):
    """The shared finish: slice the padded [G, Tp*B] partials to the true
    steps, fold the cohort-pool correction partials in, mask empty groups,
    and run the f64 Prometheus quantile — numerically identical to the
    composed narrow path's finish (same histogram_quantile program)."""
    from ..query.plancache import plan_cache

    def build():
        def fin(q, les, psum, pcnt, corr_sum, corr_cnt):
            # kernel layout [G, B, Tp] -> aggregators layout [G, T*B]
            # (flat index t*B + b); a few KiB, transposed here by XLA
            ps = psum.transpose(0, 2, 1)[:, :T, :].reshape(G, T * B)
            pc = pcnt.transpose(0, 2, 1)[:, :T, :].reshape(G, T * B)
            if has_corr:
                ps = ps + corr_sum
                pc = pc + corr_cnt
            summed = jnp.where(pc == 0, jnp.nan, ps)
            return gridfns.histogram_quantile(q, les,
                                              summed.reshape(G, T, B))
        return fin

    return plan_cache.program("fusedres-hist-finish",
                              (G, T, Tp, B, has_corr, nles), build)


def fused_hist_quantile_resident(q: float, les, dd, first_d, n, gids,
                                 num_groups: int, out_ts: np.ndarray,
                                 window_ms: int, fn: str, base_ts: int,
                                 interval_ms: int, corr=None,
                                 variant: str | None = None):
    """histogram_quantile(q, sum by(...)(fn(m[w]))) over a hist-resident
    store, map phase per the active mode: per-bucket window deltas, group
    fold, and quantile with the [S, C, B] f32 decode never materialized.
    ``corr=(sum, cnt)`` carries cohort-pool rows' partials ([G, T*B], those
    rows' gids excluded here). Returns the [G, T] device array."""
    assert fn in HIST_FUSED_FNS
    S, C, B = dd.shape
    T = len(out_ts)
    G = _roundup(max(num_groups, 8), 8)
    assert hist_fusable(S, C, T, B, G), (S, C, T, B, G)
    Tp = _roundup(max(T, 1), 128)
    Sb = hist_rows_per_tile(S)
    variant = variant or _mode
    assert variant in ("xla", "pallas")
    variant = fusedgrid.kernel_tag(variant)

    band, plo, lo_d, hi_d, rel_d = _hist_device_operands(
        C, Tp, np.ascontiguousarray(np.asarray(out_ts, np.int64)).tobytes(),
        int(window_ms), int(base_ts), int(interval_ms))
    prog = _hist_map_program(variant, fn, int(window_ms), int(interval_ms),
                             S, Sb, C, Tp, B, G, str(dd.dtype))
    # x64 tracing injects i64 scalars Mosaic rejects (grid index maps); the
    # map phase is pure f32/i32 — trace it with x64 off, exactly like the
    # scalar fused tier. The f64 quantile finish traces under default x64.
    with jax.enable_x64(False):
        psum, pcnt = prog(dd, first_d, jnp.asarray(n), jnp.asarray(gids),
                          band, plo, lo_d, hi_d, rel_d)
    if corr is None:
        z = jnp.zeros((G, T * B), jnp.float32)
        corr_sum = corr_cnt = z
        has_corr = False
    else:
        corr_sum, corr_cnt = corr
        if corr_sum.shape[0] != G:
            # the engine builds corr partials at its pow2 group bucket,
            # which sits below this kernel's 8-aligned G for small group
            # counts — pad with empty groups (they are masked by pc == 0
            # and sliced off by the caller's [:num_groups_true])
            pad = ((0, G - corr_sum.shape[0]), (0, 0))
            corr_sum = jnp.pad(corr_sum, pad)
            corr_cnt = jnp.pad(corr_cnt, pad)
        has_corr = True
    fin = _hist_finish_program(G, T, Tp, B, has_corr, int(les.shape[0]))
    return fin(jnp.float64(q), jnp.asarray(les), psum, pcnt,
               corr_sum, corr_cnt)


# ---------------------------------------------------------------------------
# hist_quantile over a RAW f32 [S, C, B] block (store.compressed_residency:
# off, the shipped default): the same shape, streamed in row tiles. The
# untiled composition (gridfns._grid_hist_kernel + partial_aggregate) builds
# a masked copy, a shifted copy and two increment blocks, each [S, C, B] f32
# — four times the store beside the store; it stays as the parity reference
# and the path for shapes outside the gate below.
# ---------------------------------------------------------------------------

def raw_hist_rows_per_tile(S: int) -> int:
    """Series per grid step of the raw hist kernel: one series' [B, C] f32
    frame is 192 KiB at 64 x 768, so 16 of them (3 MiB, double-buffered)
    with their masked copy as scratch stay under 16 MiB, and the tile's
    16 x 64 = 1024 rows fill the MXU's streaming side."""
    return 16 if S % 16 == 0 else 8


def raw_hist_fusable(S: int, C: int, T: int, B: int, num_groups: int) -> bool:
    """Shape gate of the raw tier: the narrow tier's VMEM bounds, and whole
    sublane tiles of buckets (the tile is reshaped [Sb, B, Ca] ->
    [Sb * B, Ca] for its matmuls, which is a relabelling only then)."""
    return hist_fusable(S, C, T, B, num_groups) and B % 8 == 0


def raw_hist_weights(C: int, out_ts: np.ndarray, window_ms: int,
                     base_ts: int, interval_ms: int):
    """Host operands of the raw tier, from fusedgrid.host_operands' edges
    and in the kernel's order: ``(last, w, band, used, lo, hi, rel, c0,
    Ca)``.

    ``w [Ca, N]``, entries -1/0/1, carries BOTH of a tile's products
    through one matmul of the values. A step's window delta is a
    telescoped sum: over cells ``(f, h]``, ``f = max(lo, 0)``, ``h`` =
    ``hi`` clipped to the store's last column, ``sum(v[c] - v[c - 1]) =
    v[h] - v[f]``; so columns ``0..T-1`` hold ``onehot(h) - onehot(f)`` (a
    zero column where the window is empty, ``h <= f``) and columns
    ``N/2..N/2+T-1`` the first sample's ``onehot(f)``, gridfns.
    onehot_matrix's. ``N = roundup(2T, 128)``: up to 64 steps both halves
    share the 128 columns ONE band took.

    What the telescoped sum leaves out — the counter clip's drops, and a
    row that ends inside a window — is raw_hist_corr's, summed over the
    same cells by ``band [Ca, Tp]``, gridfns.band_matrix's open band;
    ``used [1, Ca]`` marks the cells some window sums (cell 0 has no
    predecessor and is never one) and ``last [1]`` is the last of them: a
    fall elsewhere, or a row that ends past it, needs no correction. Rows
    are sliced to the active columns ``[c0, c0 + Ca)``; edges padded as
    fusedgrid.pad_edges."""
    T = len(out_ts)
    Tp, N = _roundup(max(T, 1), 128), _roundup(max(2 * T, 1), 128)
    lo, hi = gridfns.grid_edges(out_ts, window_ms, base_ts, interval_ms)
    lo_p, hi_p, rel_p = fusedgrid.pad_edges(lo, hi, out_ts - base_ts,
                                            window_ms, Tp)
    f, h = np.maximum(lo, 0), np.minimum(hi, C - 1)
    first = gridfns.onehot_matrix(C, f, np.float32)
    w = np.zeros((C, N), np.float32)
    w[:, :T] = np.where(h > f, gridfns.onehot_matrix(C, h, np.float32)
                        - first, 0.0)
    w[:, N // 2:N // 2 + T] = first
    band = np.zeros((C, Tp), np.float32)
    band[:, :T] = gridfns.band_matrix(C, lo, hi, True, np.float32)
    band[0] = 0.0
    c0, Ca = fusedgrid.active_columns(C, lo, hi)
    w, band = w[c0:c0 + Ca], band[c0:c0 + Ca]
    used = band.any(axis=1)
    last = np.array([c0 + np.flatnonzero(used).max(initial=-1)], np.int32)
    return (last, w, band, used.astype(np.int32).reshape(1, Ca),
            lo_p, hi_p, rel_p, c0, Ca)


@functools.lru_cache(maxsize=32)
def _raw_hist_device_operands(C: int, out_ts_key: bytes, window_ms: int,
                              base_ts: int, interval_ms: int):
    """raw_hist_weights on the device, cached per query shape as
    fusedgrid._device_operands; the weights go up as bf16 (dot_exact01),
    rounded on the host: no program is compiled for it."""
    out_ts = np.frombuffer(out_ts_key, np.int64)
    last, w, band, *rest, c0, Ca = raw_hist_weights(
        C, out_ts, window_ms, base_ts, interval_ms)
    return (jnp.asarray(last),
            *(jnp.asarray(a.astype(jnp.bfloat16)) for a in (w, band)),
            *(jnp.asarray(a) for a in rest), c0, Ca)


def raw_hist_values(c0: int, x, n):
    """One series' raw cumulative buckets over the active columns ``x
    [B, Ca]`` (buckets on sublanes, cells on lanes, ``c0`` the first
    column's cell) and its valid count ``n`` -> the values with absent
    cells zeroed, whose differences the packed weight telescopes."""
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1) + c0
    return jnp.where(col < n, x, 0.0)


def raw_hist_drops(v, roll):
    """raw_hist_values' ``v [M, Ca]`` -> per cell how far it lies below
    its predecessor: above 0 where a counter fell (and at the cell after a
    row's last sample). What every tile is tested with; what a fall is
    worth is raw_hist_corr's, where one was seen."""
    return roll(v) - v


def raw_hist_corr(fn: str, c0: int, v, n, used, roll):
    """Per cell, what the function's own increment — the counter clip for
    ``rate`` / ``increase``: a reset cell adds 0; nothing past the row's
    last sample — holds beyond the plain difference of raw_hist_values'
    ``v`` that the packed weight telescopes: the drop at a reset, and the
    last sample's value at cell ``n``. Zero wherever no window sums the
    cell (raw_hist_weights' ``used [1, Ca]``); the roll's wrapped column
    is never a used one."""
    col = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) + c0
    raw = v - roll(v)
    inc = jnp.maximum(raw, 0.0) if fn != "delta" else raw
    return jnp.where(used != 0, jnp.where(col < n, inc, 0.0) - raw, 0.0)


def unpack_halves(d, Tp: int, roll):
    """The packed product ``d [M, N]`` -> ``(delta, f_v)``, both
    ``[M, Tp]`` with step t on lane t: the second half comes down by N/2
    lanes. Lanes past the steps hold the other half's numbers; their
    ``hi`` is -1 and hist_extrapolate masks them."""
    half = d.shape[1] // 2
    f_v = d[:, half:] if half == Tp else roll(d, half)
    return d[:, :Tp], f_v[:, :Tp]


def bucket_steps(contrib, roll):
    """A series' cumulative-bucket contribution ``[B, Tp]`` as bucket STEPS
    (bucket b minus bucket b - 1; bucket 0 as it is), which is what the raw
    tier folds: a group's accumulator then holds each bucket at the size of
    that bucket's own count, not of the cumulative count above it, and the
    finish cumulates in f64. histogram_quantile divides by ONE bucket's
    count: folded cumulative, a tail bucket holding a thousandth of a
    group's count keeps three digits fewer than the sum around it (a p99
    over 512 series a group came out 1.6e-4 off its f64 value)."""
    row = jax.lax.broadcasted_iota(jnp.int32, contrib.shape, 0)
    return contrib - jnp.where(row == 0, 0.0, roll(contrib))


def kahan_add(total, comp, x):
    """One compensated add (Kahan): ``(total, comp) + x`` -> the new pair;
    the exact sum is ``total - comp``. A plain f32 fold of 32,768 series
    rounds every add to the running sum's ulp, and series of one rate add
    nearly the same amount, so the roundings do not cancel: the global p90
    of histdev_raw_32k came out 0.4 of the deployment's tolerance off its
    f64 value, on the chip and on the CPU alike. Compensated, the fold is
    exact to a few ulps of the result whatever the count."""
    y = x - comp
    t = total + y
    return t, (t - total) - y


def _raw_hist_kernel_body(fn: str, window_ms: int, interval_ms: int, Sb: int,
                          per: int, G: int, c0: int, n_ref, gid_ref, last_ref,
                          val_ref, w_ref, band_ref, used_ref, lo_ref, hi_ref,
                          rel_ref, sum_ref, comp_ref, cnt_ref, falls_ref,
                          x_scr, d_scr, f_scr):
    """One grid step = ``Sb`` series in three passes over the VMEM tile:
    mask each series (its count is an SMEM scalar), ONE packed matmul over
    all ``Sb * B`` bucket rows, then extrapolate each series and fold its
    bucket steps into its group's [B, Tp] accumulator (a compensated
    pair), in series order. Only a tile in which a used cell fell, or a
    row ends under a window, turns its values into raw_hist_corr's and
    takes a second matmul, and counts itself in ``falls_ref``. SMEM blocks
    as in _hist_kernel_body."""
    base = (pl.program_id(0) % per) * Sb
    B, Ca = val_ref.shape[1], val_ref.shape[2]
    Tp = band_ref.shape[1]

    @pl.when(pl.program_id(0) == 0)
    def _():
        sum_ref[:] = jnp.zeros_like(sum_ref)
        comp_ref[:] = jnp.zeros_like(comp_ref)
        cnt_ref[:] = jnp.zeros_like(cnt_ref)
        falls_ref[0] = 0

    def roll1(a):
        return pltpu.roll(a, jnp.int32(1), 1)

    def prepare(s, ends):
        n = n_ref[base + s]
        x_scr[s] = raw_hist_values(c0, val_ref[s], n)
        return ends | ((n > c0) & (n <= last_ref[0])).astype(jnp.int32)

    def tile():
        return x_scr[:].reshape(Sb * B, Ca)

    ends = jax.lax.fori_loop(0, Sb, prepare, jnp.int32(0))
    x = tile()
    delta, f_v = unpack_halves(
        dot_exact01(x, w_ref[:]), Tp,
        lambda a, k: pltpu.roll(a, jnp.int32(k), 1))
    d_scr[:] = delta.reshape(Sb, B, Tp)
    f_scr[:] = f_v.reshape(Sb, B, Tp)
    if fn == "delta":                     # no clip: nothing falls
        fell = ends > 0
    else:
        # beside the matmul, not in the loop before it: the rolls then run
        # while the MXU does (4 ms of a 16 ms query over 768 columns
        # otherwise, on the v5e)
        drops = fusedgrid.sublane_max(raw_hist_drops(x, roll1))
        fell = (ends > 0) | (jnp.max(
            jnp.where(used_ref[:] != 0, drops, 0.0)) > 0.0)

    @pl.when(fell)
    def _():
        def correct(s, carry):
            x_scr[s] = raw_hist_corr(fn, c0, x_scr[s], n_ref[base + s],
                                     used_ref[:], roll1)
            return carry

        jax.lax.fori_loop(0, Sb, correct, 0)
        d_scr[:] += dot_exact01(tile(), band_ref[:]).reshape(Sb, B, Tp)
        falls_ref[0] += 1

    def fold(s, carry):
        n = n_ref[base + s]
        g = gid_ref[base + s]

        @pl.when((n > 0) & (g >= 0) & (g < G))
        def _():
            contrib, okb = hist_extrapolate(
                fn, window_ms, interval_ms, d_scr[s], f_scr[s], n,
                lo_ref[:], hi_ref[:], rel_ref[:])
            sum_ref[g], comp_ref[g] = kahan_add(
                sum_ref[g], comp_ref[g], bucket_steps(
                    contrib, lambda a: pltpu.roll(a, jnp.int32(1), 0)))
            cnt_ref[g] += okb
        return carry

    jax.lax.fori_loop(0, Sb, fold, 0)


def build_raw_hist_pallas(fn: str, window_ms: int, interval_ms: int, S: int,
                          Sb: int, C: int, Tp: int, N: int, B: int, G: int,
                          interpret: bool, c0: int, Ca: int):
    """The raw (traceable) map-phase pallas_call of the raw hist tier: grid
    over [Sb] series tiles of the f32 block, three [G, B, Tp] accumulators
    (bucket-step sums, their compensation, series counts) in VMEM across
    the sequential grid, and the count of tiles that took the correction
    matmul, [1] i32 in SMEM. Operands: n and gids [S] i32 and ``last [1]``
    (SMEM), the block as [S, B, C] (the resident [S, C, 64] block's own HBM
    layout, see build_hist_pallas), raw_hist_weights' bf16 ``w [Ca, N]``
    and ``band [Ca, Tp]``, ``used [1, Ca]``, edges [1, Tp]. ``(c0, Ca)`` is the active
    column range (fusedgrid.active_columns): a sub-range query streams and
    multiplies only its own columns — unlike the narrow tier, whose frames
    telescope from cell 0. Cached by its caller's plan-cache entry."""
    sblk = 1024 if S % 1024 == 0 else S
    per = sblk // Sb
    body = functools.partial(_raw_hist_kernel_body, fn, window_ms,
                             interval_ms, Sb, per, G, c0)
    acc = pl.BlockSpec((G, B, Tp), lambda i: (0, 0, 0),
                       memory_space=pltpu.VMEM)
    const = functools.partial(pl.BlockSpec, index_map=lambda i: (0, 0),
                              memory_space=pltpu.VMEM)
    scalars = pl.BlockSpec((sblk,), lambda i: (i // per,),
                           memory_space=pltpu.SMEM)
    count = pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM)
    kcol = c0 // Ca                     # active_columns: c0 % Ca == 0
    in_specs = [
        scalars, scalars, count,                                # n, gid, last
        pl.BlockSpec((Sb, B, Ca), lambda i: (i, 0, kcol),
                     memory_space=pltpu.VMEM),                  # the block
        const((Ca, N)), const((Ca, Tp)), const((1, Ca)),        # w, band, used
        const((1, Tp)), const((1, Tp)), const((1, Tp)),         # lo, hi, rel
    ]
    Cp, Bp = _pad(Ca, 128), _pad(B, 8)
    tile = Sb * Bp * Cp * 4
    footprint = (2 * tile                                       # the block
                 + tile + 2 * Sb * Bp * Tp * 4                  # scratch
                 + 2 * _pad(Ca, 16) * (N + Tp) * 2              # w, band
                 + 2 * 3 * G * Bp * Tp * 4)                     # accumulators
    # + a matmul's working set: three bf16 pieces and the f32 remainder of
    # the [Sb * B, Ca] operand, its [Sb * B, N] product, and a dozen
    # [B, Tp] planes of the fold
    footprint += 3 * tile + Sb * Bp * N * 4 + 12 * Bp * Tp * 4
    return pl.pallas_call(
        body,
        grid=(S // Sb,),
        in_specs=in_specs,
        out_specs=(acc, acc, acc, count),
        out_shape=(*(jax.ShapeDtypeStruct((G, B, Tp), jnp.float32)
                     for _ in range(3)),
                   jax.ShapeDtypeStruct((1,), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((Sb, B, Ca), jnp.float32),
                        pltpu.VMEM((Sb, B, Tp), jnp.float32),
                        pltpu.VMEM((Sb, B, Tp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=min(fusedgrid.VMEM_CAP,
                                 max(32 << 20, 2 * footprint))),
        interpret=interpret,
    )


def build_raw_hist_xla_tiles(fn: str, window_ms: int, interval_ms: int,
                             S: int, Sb: int, C: int, Tp: int, N: int,
                             B: int, G: int, c0: int, Ca: int):
    """XLA twin of :func:`build_raw_hist_pallas` from the same tiling plan:
    a loop over the same [Sb, B, Ca] tiles (sliced out of the resident
    block one at a time, never the active columns of the whole store),
    through the same raw_hist_values, dot_exact01, unpack_halves and
    hist_extrapolate, raw_hist_corr's matmul under a ``cond`` on the same
    test, folding series by series with the same compensated adds. Unlike
    the other tiers' twins the two are not bit-equal: the compensation
    keeps the last bit of every contribution, where the compilers differ
    (a multiply contracted with the subtraction after it, or not) — they
    agree to a few f32 ulps of the answer."""
    f32 = jnp.float32

    def call(n, gids, last, blk, w, band, used, lo, hi, rel):
        def roll1(a):
            return jnp.roll(a, 1, axis=1)

        values_of = jax.vmap(lambda x, n_s: raw_hist_values(c0, x, n_s))
        corr_of = jax.vmap(lambda v, n_s: raw_hist_corr(fn, c0, v, n_s, used,
                                                        roll1))

        def matmul(x, wt):
            return dot_exact01(x.reshape(Sb * B, Ca), wt)

        def extrap_one(d, f, n_s):
            contrib, okb = hist_extrapolate(fn, window_ms, interval_ms, d, f,
                                            n_s, lo, hi, rel)
            return bucket_steps(contrib,
                                lambda a: jnp.roll(a, 1, axis=0)), okb
        extrap = jax.vmap(extrap_one)

        def tile(i, carry):
            *acc, falls = carry
            n_t = jax.lax.dynamic_slice(n, (i * Sb,), (Sb,))
            g_t = jax.lax.dynamic_slice(gids, (i * Sb,), (Sb,))
            x_t = jax.lax.dynamic_slice(blk, (i * Sb, 0, c0), (Sb, B, Ca))
            v = values_of(x_t, n_t)
            fell = jnp.any((n_t > c0) & (n_t <= last[0]))
            if fn != "delta":
                drops = raw_hist_drops(v.reshape(Sb * B, Ca), roll1)
                fell |= jnp.any((used != 0) & (drops > 0.0))
            d, f = unpack_halves(matmul(v, w), Tp,
                                 lambda a, k: jnp.roll(a, k, axis=1))
            d = jax.lax.cond(
                fell, lambda d: d + matmul(corr_of(v, n_t), band),
                lambda d: d, d)
            contrib, okb = extrap(d.reshape(Sb, B, Tp), f.reshape(Sb, B, Tp),
                                  n_t)
            live = (n_t > 0) & (g_t >= 0) & (g_t < G)
            gi = jnp.clip(g_t, 0, G - 1)

            def fold(acc, xs):
                g_s, live_s, c_s, k_s = xs
                t, comp = kahan_add(acc[0][g_s], acc[1][g_s], c_s)
                return (acc[0].at[g_s].set(jnp.where(live_s, t, acc[0][g_s])),
                        acc[1].at[g_s].set(jnp.where(live_s, comp,
                                                     acc[1][g_s])),
                        acc[2].at[g_s].add(jnp.where(live_s, k_s, 0.0))), None
            acc = jax.lax.scan(fold, tuple(acc), (gi, live, contrib, okb))[0]
            return (*acc, falls + fell.astype(jnp.int32))

        init = (*(jnp.zeros((G, B, Tp), f32) for _ in range(3)),
                jnp.zeros((1,), jnp.int32))
        return jax.lax.fori_loop(0, S // Sb, tile, init)

    return call


def raw_hist_map_body(variant: str, fn: str, window_ms: int,
                      interval_ms: int, S: int, Sb: int, C: int, Tp: int,
                      N: int, B: int, G: int, c0: int, Ca: int):
    """The traceable map phase of the raw tier as it is served: ``variant``
    is fusedgrid.kernel_tag's name ("xla" | "pallas" | "pallas-interpret"),
    and dtype casts and the relabelling ride the one dispatch."""
    if variant == "xla":
        call = build_raw_hist_xla_tiles(fn, window_ms, interval_ms, S, Sb, C,
                                        Tp, N, B, G, c0, Ca)
    else:
        call = build_raw_hist_pallas(fn, window_ms, interval_ms, S, Sb, C,
                                     Tp, N, B, G, variant != "pallas", c0,
                                     Ca)

    def wrapped(val, n, gids, last, w, band, used, lo, hi, rel):
        # [S, C, B] -> [S, B, C]: a relabelling of the resident block's own
        # layout on the TPU (build_hist_pallas)
        return call(n.astype(jnp.int32), gids.astype(jnp.int32), last,
                    val.astype(jnp.float32).transpose(0, 2, 1),
                    w, band, used, lo, hi, rel)
    return wrapped


def raw_hist_finish(G: int, T: int, B: int):
    """The raw tier's traceable finish: the compensated bucket-step sums
    (``psum - pcomp``) of the true steps, cumulated over the buckets in
    f64, empty groups masked, then the f64 Prometheus quantile of
    _hist_finish_program (the same histogram_quantile program). The bucket
    cumulation is log2(B) shifted adds, not ``cumsum``: the TPU emulates
    f64, and its compiler takes 108 s over an f64 scan of this size (15 s
    over the bare one; 4 s over a triangular contraction, which then runs
    as five loops of small operations, 140 device events a query) and
    under a second over the adds — a query waits 60 s for its answer."""
    def fin(q, les, psum, pcomp, pcnt):
        f64 = jnp.float64
        x = (psum.astype(f64) - pcomp.astype(f64))[:, :, :T]      # [G, B, T]
        k = 1
        while k < B:                                              # along B
            x = x + jnp.pad(x, ((0, 0), (k, 0), (0, 0)))[:, :B, :]
            k *= 2
        some = pcnt.transpose(0, 2, 1)[:, :T, :] > 0
        return gridfns.histogram_quantile(
            q, les, jnp.where(some, x.transpose(0, 2, 1), jnp.nan))
    return fin


def fused_hist_quantile_raw(q: float, les, val, n, gids, num_groups: int,
                            out_ts: np.ndarray, window_ms: int, fn: str,
                            base_ts: int, interval_ms: int,
                            variant: str | None = None):
    """histogram_quantile(q, sum by(...)(fn(m[w]))) over a RAW f32
    ``[S, C, B]`` block, map phase per the active mode: no [S, C, B]-sized
    temporary exists. Operands as fused_hist_quantile_resident's, with the
    block itself in place of the 2D-delta state and raw_hist_weights'
    packed weight in place of the two bands. Returns ``(out, fall_tiles,
    tags)``, both device arrays NOT fetched — the caller dispatches under
    the shard lock and fetches outside it: the [G, T] answer and the [1]
    count of tiles that ran the correction matmul — and the dispatch's
    shape as the ``query.exec.kernel`` span's tags (``packed``: the one
    weight is narrower than two bands)."""
    assert fn in HIST_FUSED_FNS
    S, C, B = val.shape
    T = len(out_ts)
    G = _roundup(max(num_groups, 8), 8)
    assert raw_hist_fusable(S, C, T, B, G), (S, C, T, B, G)
    Sb = raw_hist_rows_per_tile(S)
    variant = variant or _mode
    assert variant in ("xla", "pallas")
    variant = fusedgrid.kernel_tag(variant)

    *ops, c0, Ca = _raw_hist_device_operands(
        C, np.ascontiguousarray(np.asarray(out_ts, np.int64)).tobytes(),
        int(window_ms), int(base_ts), int(interval_ms))
    Tp, N = ops[2].shape[1], ops[1].shape[1]          # band's, w's
    # a kernel variant of its own in the plan cache, as _hist_map_program
    from ..query.plancache import plan_cache
    key = (variant, fn, int(window_ms), int(interval_ms), S, Sb, C, Tp, N, B,
           G, c0, Ca)
    prog = plan_cache.program("fusedres-hist-raw", key,
                              lambda: raw_hist_map_body(*key))
    with jax.enable_x64(False):       # as fused_hist_quantile_resident
        psum, pcomp, pcnt, falls = prog(val, jnp.asarray(n),
                                        jnp.asarray(gids), *ops)
    fin = plan_cache.program("fusedres-hist-raw-finish",
                             (G, T, Tp, B, int(les.shape[0])),
                             lambda: raw_hist_finish(G, T, B))
    out = fin(jnp.float64(q), jnp.asarray(les), psum, pcomp, pcnt)
    return out, falls, {"kernel": variant, "rows": S, "c0": c0, "cols": Ca,
                        "steps": T, "groups": num_groups, "buckets": B,
                        "variant": "hist-raw", "packed": int(N < 2 * Tp)}
