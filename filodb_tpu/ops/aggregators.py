"""Cross-series aggregation: the map/reduce over [P, T] result matrices.

Reference: query/.../exec/AggrOverRangeVectors.scala (RowAggregator framework:
Sum/Min/Max/Count/Avg/Stddev/Stdvar/TopK/BottomK/CountValues/Quantile with
map -> reduce -> present phases, plus the row-major ``fastReduce`` path).

TPU-native shape: grouping labels are resolved host-side to dense group ids [P];
the reduce is one ``segment_sum``-family call over the series axis — the same
O(P*T) data-parallel pass regardless of group count. Across shards the partial
[G, T] matrices reduce further via ``psum`` on the mesh (parallel/).

NaN convention: NaN marks a missing sample; aggregates exclude NaN and emit NaN
for groups with no present samples at a step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BASIC_OPS = ("sum", "min", "max", "avg", "count", "stddev", "stdvar", "group")


@functools.partial(jax.jit, static_argnums=(0, 3))
def segment_aggregate(op: str, values, group_ids, num_groups: int):
    """values [P, T] f64 (NaN=missing), group_ids int32 [P] -> [G, T].

    For avg/stddev/stdvar returns the *present* final value; for mesh-distributed
    reduces use ``partial_aggregate``/``combine_partials`` instead so partial sums
    survive the cross-shard psum.
    """
    parts = partial_aggregate(op, values, group_ids, num_groups)
    return present_partials(op, parts)


MATMUL_GROUP_LIMIT = 64   # one-hot [G, S] matmul reduce up to this many groups


def partial_aggregate(op: str, values, group_ids, num_groups: int,
                      stable: bool = False):
    """Map phase: per-group partial state tensors, each [G, T] (ref: RowAggregator
    .map/.reduceAggregate). Partials are psum/min/max-combinable across shards.

    TPU note: scatter-based ``segment_sum`` is ~50x slower than a matmul reduce
    on TPU, so for small group counts (the common dashboard shape: sum()/by(dc))
    sums ride an MXU one-hot matmul [G, S] @ [S, T]; large-G reduces keep
    segment_sum.

    ``stable=True`` forces the segment_sum reduce for every group count: the
    scatter-add folds rows in ROW ORDER, each output column independently, so
    the result is invariant under the padded-T step bucket AND under row
    padding (padded/excluded rows contribute exact 0.0) — the bit-stability
    the composed two-step path and the mesh reduction schedule require. The
    one-hot matmul's contraction order is tiling-dependent (it may
    reassociate with T), which is exactly the PR 13 fold-order caveat.
    """
    present = ~jnp.isnan(values)
    zeroed = jnp.where(present, values, 0.0)
    acc = values.dtype if values.dtype in (jnp.float32, jnp.float64) else jnp.float64

    if not stable and num_groups <= MATMUL_GROUP_LIMIT:
        onehot = (group_ids[None, :] == jnp.arange(num_groups, dtype=group_ids.dtype)[:, None]
                  ).astype(acc)                                   # [G, S]
        def gsum(x):
            return onehot @ x
    else:
        def gsum(x):
            return jax.ops.segment_sum(x, group_ids, num_groups)

    cnt = gsum(present.astype(acc))
    if op in ("count", "group"):
        return {"count": cnt}
    if op == "sum":
        return {"sum": gsum(zeroed), "count": cnt}
    if op == "min":
        v = jnp.where(present, values, jnp.inf)
        return {"min": jax.ops.segment_min(v, group_ids, num_groups), "count": cnt}
    if op == "max":
        v = jnp.where(present, values, -jnp.inf)
        return {"max": jax.ops.segment_max(v, group_ids, num_groups), "count": cnt}
    if op == "avg":
        return {"sum": gsum(zeroed), "count": cnt}
    if op in ("stddev", "stdvar"):
        return {"sum": gsum(zeroed), "sumsq": gsum(zeroed * zeroed), "count": cnt}
    raise ValueError(f"not a basic segment op: {op}")


def resolve_partials(parts):
    """Normalize a partials carrier: a fused program's handle, not fetched
    (diagnostics.Dispatched), resolves to its host dict here — at present/
    merge time, outside any shard lock."""
    return parts.resolve() if hasattr(parts, "resolve") else parts


def _xp_of(*dicts):
    """numpy for host partials, jnp for device partials. Partial state is
    tiny ([G, T]); once fetched to host, finishing in numpy avoids device
    round-trips (a host sync costs a dispatch round trip each). Mixed inputs
    resolve to host."""
    vals = [v for d in dicts for v in d.values()]
    if vals and all(isinstance(v, jax.Array) for v in vals):
        return jnp
    return np


def combine_partials(op: str, a, b) -> dict:
    """Reduce phase across shards (host or psum path)."""
    a, b = resolve_partials(a), resolve_partials(b)
    xp = _xp_of(a, b)
    if xp is not jnp:
        a = jax.device_get(a)
        b = jax.device_get(b)
    out = {}
    for k in a:
        if k == "min":
            out[k] = xp.minimum(a[k], b[k])
        elif k == "max":
            out[k] = xp.maximum(a[k], b[k])
        else:
            out[k] = a[k] + b[k]
    return out


def present_partials(op: str, parts):
    """Present phase: partial state -> final [G, T] values (NaN where empty)."""
    parts = resolve_partials(parts)
    xp = _xp_of(parts)
    cnt = parts["count"]
    empty = cnt == 0
    cnt = xp.where(empty, 1.0, cnt)  # avoid 0/0 noise; result masked below
    if op == "count":
        return xp.where(empty, xp.nan, cnt)
    if op == "group":
        return xp.where(empty, xp.nan, 1.0)
    if op == "sum":
        return xp.where(empty, xp.nan, parts["sum"])
    if op == "min":
        return xp.where(empty, xp.nan, parts["min"])
    if op == "max":
        return xp.where(empty, xp.nan, parts["max"])
    if op == "avg":
        return xp.where(empty, xp.nan, parts["sum"] / cnt)
    if op in ("stddev", "stdvar"):
        mean = parts["sum"] / cnt
        import contextlib
        guard = (np.errstate(invalid="ignore", divide="ignore")
                 if xp is not jnp else contextlib.nullcontext())
        with guard:
            var = xp.maximum(parts["sumsq"] / cnt - mean * mean, 0.0)
            r = var if op == "stdvar" else xp.sqrt(var)
        return xp.where(empty, xp.nan, r)
    raise ValueError(op)


# ---- mergeable quantile sketch (ref: AggrOverRangeVectors quantile uses a
# t-digest; the TPU-native shape is a DDSketch-style log-bucketed histogram:
# fixed [G, B, T] count tensors that psum/merge exactly and bound the
# RELATIVE error of the presented quantile by (gamma-1)/(gamma+1)) ----------

SKETCH_GAMMA = 1.04            # rel. error (gamma-1)/(gamma+1) ~ 1.96%
SKETCH_MIN = 1e-12             # values below collapse into the zero bucket
SKETCH_BUCKETS = 2048          # per sign: covers 1e-12 .. ~7e22 at gamma=1.04
# layout: [0..B) negative buckets (mirrored, descending magnitude),
#         [B] zero, (B..2B] positive buckets
SKETCH_WIDTH = 2 * SKETCH_BUCKETS + 1


def quantile_sketch(values, group_ids, num_groups: int):
    """Map phase: [P, T] values -> [G, W, T] log-bucket counts (host numpy).

    Mergeable across shards by addition (or psum). NaN values are absent.
    """
    vals = np.asarray(values, np.float64)
    gids = np.asarray(group_ids)
    P, T = vals.shape
    B = SKETCH_BUCKETS
    lg = np.log(SKETCH_GAMMA)
    mag = np.abs(vals)
    with np.errstate(divide="ignore", invalid="ignore"):
        bi = np.ceil(np.log(mag / SKETCH_MIN) / lg)
        bi = np.nan_to_num(bi, nan=1.0, posinf=B - 1, neginf=1.0)
    # outermost slot of each sign is reserved for true +/-Inf samples
    bi = np.clip(bi, 1, B - 1).astype(np.int64)
    idx = np.where(mag <= SKETCH_MIN, B,
                   np.where(vals > 0, B + bi, B - bi))      # [P, T]
    idx = np.where(np.isposinf(vals), 2 * B, idx)
    idx = np.where(np.isneginf(vals), 0, idx)
    present = ~np.isnan(vals)
    counts = np.zeros((num_groups, SKETCH_WIDTH, T), np.float32)
    t_idx = np.broadcast_to(np.arange(T)[None, :], (P, T))
    g_idx = np.broadcast_to(gids[:, None], (P, T))
    np.add.at(counts, (g_idx[present], idx[present], t_idx[present]), 1.0)
    return counts


def present_quantile_sketch(counts, q: float):
    """[G, W, T] counts -> [G, T] phi-quantile estimates.

    PromQL semantics: rank = q*(n-1) with linear interpolation between the
    two straddling order statistics; each order statistic is located in the
    sketch and represented by its bucket's geometric midpoint, so the
    per-value relative error stays bounded by (gamma-1)/(gamma+1) ~ 1%."""
    G, W, T = counts.shape
    B = SKETCH_BUCKETS
    total = counts.sum(axis=1)                               # [G, T]
    rank = np.maximum(q, 0.0) * np.maximum(total - 1, 0)     # PromQL phi rank
    lo_r = np.floor(rank)
    frac = rank - lo_r
    cum = np.cumsum(counts, axis=1)
    # order statistic at 0-indexed rank r sits in the first bucket whose
    # cumulative count reaches r+1
    sel_lo = (cum < lo_r[:, None, :] + 1 - 1e-9).sum(axis=1)
    sel_hi = (cum < np.minimum(lo_r + 2, np.maximum(total, 1))[:, None, :]
              - 1e-9).sum(axis=1)
    sel_lo = np.clip(sel_lo, 0, W - 1)
    sel_hi = np.clip(sel_hi, 0, W - 1)
    # bucket -> representative value; outermost slots are true +/-Inf
    k = np.arange(W, dtype=np.float64)
    pos = k - B
    mags = SKETCH_MIN * np.power(SKETCH_GAMMA, np.abs(pos)) * 2 / (1 + SKETCH_GAMMA)
    rep = np.sign(pos) * mags
    rep[B] = 0.0
    rep[0] = -np.inf
    rep[W - 1] = np.inf
    lo_v, hi_v = rep[sel_lo], rep[sel_hi]
    with np.errstate(invalid="ignore"):
        interp = lo_v * (1 - frac) + hi_v * frac
    # integral ranks and equal straddles take the value directly — the
    # interpolation form would produce inf*0 = NaN for +/-Inf samples
    out = np.where((frac == 0) | (lo_v == hi_v), lo_v, interp)
    out = np.where(total > 0, out, np.nan)
    if q < 0:
        out = np.where(total > 0, -np.inf, np.nan)
    if q > 1:
        out = np.where(total > 0, np.inf, np.nan)
    return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def topk_mask(values, group_ids, num_groups: int, k: int, bottom: bool = False):
    """Per-step top-k filter: True where values[p, t] is among the k largest
    (smallest for bottomk) present values of its group at step t.

    Rank-within-group computed by counting, per element, how many group members
    beat it — O(P^2 T) pairwise within groups would be too big, so we instead
    compute per-element rank via sort: argsort per column with a composite key
    (group, -value) and positional counting.
    """
    P, T = values.shape
    neg = jnp.where(jnp.isnan(values), -jnp.inf if not bottom else jnp.inf, values)
    sortval = -neg if not bottom else neg
    # composite sort: primary group, secondary value
    order = jnp.lexsort((sortval, group_ids[:, None] * jnp.ones((1, T), jnp.int32)), axis=0)
    # rank within group: position since the group's first row in sorted order
    g_sorted = jnp.take_along_axis(group_ids[:, None] * jnp.ones((1, T), jnp.int32), order, axis=0)
    idx = jnp.arange(P)[:, None] * jnp.ones((1, T), jnp.int32)
    # first occurrence index of each group per column
    is_first = jnp.concatenate([jnp.ones((1, T), bool), g_sorted[1:] != g_sorted[:-1]], axis=0)
    first_pos = jnp.where(is_first, idx, 0)
    first_pos = jax.lax.associative_scan(jnp.maximum, first_pos, axis=0)
    rank_sorted = idx - first_pos
    # scatter ranks back to original row positions
    rank = _scatter_rows(rank_sorted, order, P)
    present = ~jnp.isnan(values)
    return (rank < k) & present


def _scatter_rows(src, order, P):
    """out[order[i, t], t] = src[i, t]."""
    T = src.shape[1]
    cols = jnp.broadcast_to(jnp.arange(T)[None, :], src.shape)
    out = jnp.zeros_like(src)
    return out.at[order.reshape(-1), cols.reshape(-1)].set(src.reshape(-1))


@functools.partial(jax.jit, static_argnums=(2,))
def group_quantile(values, group_ids, num_groups: int, q):
    """Cross-series quantile per group per step (ref: QuantileRowAggregator uses
    t-digest; we compute the exact quantile — a strictly better answer the TPU
    can afford because the whole matrix is resident).

    Sort rows by (group, value) per column, then linearly interpolate at rank
    q*(k-1) inside each group's contiguous run.
    """
    P, T = values.shape
    big = jnp.where(jnp.isnan(values), jnp.inf, values)
    gcol = group_ids[:, None] * jnp.ones((1, T), jnp.int32)
    order = jnp.lexsort((big, gcol), axis=0)
    v_sorted = jnp.take_along_axis(big, order, axis=0)
    present = ~jnp.isnan(values)
    cnt = jax.ops.segment_sum(present.astype(jnp.int32), group_ids, num_groups)  # [G, T]
    # start position of each group's run per column = cumulative counts of all rows
    # (incl. missing, which sort to +inf *within the group run*) — compute from
    # total group sizes instead
    gsize = jax.ops.segment_sum(jnp.ones_like(group_ids, jnp.int32), group_ids, num_groups)
    gstart = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(gsize)[:-1]])  # [G]
    rank = q * jnp.maximum(cnt.astype(jnp.float64) - 1.0, 0.0)                   # [G, T]
    lo = jnp.floor(rank).astype(jnp.int32)
    hi = jnp.minimum(lo + 1, jnp.maximum(cnt - 1, 0))
    frac = rank - lo

    def take_rank(r):  # r: [G, T] rank within group -> gather from v_sorted
        pos = jnp.clip(gstart[:, None] + r, 0, P - 1)               # [G, T]
        return jnp.take_along_axis(v_sorted, pos, axis=0)

    v_lo = take_rank(lo)
    v_hi = take_rank(hi)
    res = v_lo + (v_hi - v_lo) * frac
    return jnp.where(cnt == 0, jnp.nan, res)
