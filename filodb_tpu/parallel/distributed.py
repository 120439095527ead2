"""Distributed query execution over a jax device mesh.

Reference: the Akka scatter-gather plane — ExecPlans Kryo-dispatched to per-shard
QueryActors, partial aggregates reduced on the calling node
(coordinator/.../queryengine2/QueryEngine.scala:59-67, query/.../exec/ExecPlan.scala
NonLeafExecPlan.dispatchRemotePlan, client/Serializer.scala Kryo wire).

TPU-native replacement: shards live on mesh devices ("shard" axis); every
``dist_*`` collective below is a thin wrapper over ONE global-view sharded
executable per padded query shape — select -> decode -> window -> segment
reduce -> cross-shard fold lower as a single program, so XLA overlaps decode
compute against the reduce collectives. The collective *is* the
scatter-gather: no serialization, no per-shard dispatch.

One program form: the per-shard body (the fused tiling plan / the two-step
kernels) wraps in ``shard_map`` and jits with EXPLICIT
``in_shardings``/``out_shardings`` (``NamedSharding`` per operand). No
operand is donated: the store's blocks are the store's, and the group-id
rows and the window plan are kept from query to query (:class:`MeshLeafMemo`).
Declaring both sides is mandatory: implicit propagation would silently
re-gather sharded store operands (filolint ``mesh-sharding-undeclared``
enforces this statically). The virtual CPU mesh of the test suite compiles
and runs the same form the chip serves.

Reduction schedule: float partial sums do NOT psum — psum's fold order is
implementation-defined and may reassociate per shape, and an in-program f32
fold rounds differently from the host reduce's float64 accumulator. Instead
each device returns its stacked per-slot partial state and the caller folds
on host in SHARD order (slot-major, device-minor) with the same float64
accumulation and presenter as the scatter-gather merge
(exec._merge_partials) — the mesh result is bit-equal to the host path, and
stable across padded-T step buckets (the PR 13 fold-order caveat, closed
here together with exec.py's stable segment reduce). Sketch counts remain
psum'd: they are small integers in f32, exact under any summation order.

The same partial-aggregate format as the in-process path (ops/aggregators.py)
crosses the collective, so single-chip and multi-chip execution share semantics.

Deliberately NOT lowered here: count_values — its partial state is keyed by
rendered value strings (no fixed-size device layout to all_gather), and the
host merge it rides carries only [distinct values] rows across shards, so a
hashed-value-bucket device layout has nothing measured to win. Cross-HOST
peers (shards owned by other OS processes) take the HTTP data plane instead:
query/wire.py ships per-peer batched envelopes and co-located reduces (see
query/planner.py _collapse_remote) — the collectives below cover co-resident
shards only.
"""

from __future__ import annotations

import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import aggregators, decodereg, fusedgrid, fusedresident, rangefns
from ..utils.metrics import (FILODB_QUERY_MESH_FALLBACK,
                             FILODB_QUERY_MESH_PREPARED,
                             FILODB_QUERY_MESH_SERVED, registry)


def make_mesh(devices=None, axis: str = "shard") -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (axis,))


def count_mesh_served(route: str) -> None:
    registry.counter(FILODB_QUERY_MESH_SERVED, {"route": route}).increment()


def count_mesh_fallback(reason: str) -> None:
    """A mesh-eligible dispatch fell back to the host scatter-gather path
    AFTER eligibility (cold data paging, order-stat caps, ...)."""
    registry.counter(FILODB_QUERY_MESH_FALLBACK,
                     {"reason": reason}).increment()


def count_mesh_prepared(part: str, outcome: str) -> None:
    registry.counter(FILODB_QUERY_MESH_PREPARED,
                     {"part": part, "outcome": outcome}).increment()


def _is_pspec(x) -> bool:
    return isinstance(x, P)


def _sharded_jit(mesh: Mesh, in_specs, out_specs):
    """The mesh programs' jit applicator: every ``PartitionSpec`` leaf in
    the operand trees becomes an explicit ``NamedSharding`` on ``mesh`` and
    BOTH ``in_shardings`` and ``out_shardings`` are declared (the jax_graft
    pattern — SNIPPETS.md [2]/[3]; an implicit side would silently
    re-gather sharded store operands through host memory)."""
    def to_shardings(tree):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                            is_leaf=_is_pspec)
    in_shardings = to_shardings(in_specs)
    out_shardings = to_shardings(out_specs)

    def wrap(fn):
        return jax.jit(fn, in_shardings=in_shardings,
                       out_shardings=out_shardings)
    return wrap


class DistributedStore:
    """Global sharded view over per-shard device stores.

    Each TimeSeriesShard's SeriesStore already lives on one mesh device; a
    slot's global array is ``[NDEV * S, ...]`` sharded on its FIRST axis over
    the "shard" mesh axis, assembled with
    ``make_array_from_single_device_arrays``: a device's block IS the
    shard's resident array. No program runs and no byte moves — the
    ``[NDEV, S, C]`` form this had before PR 46 put an eager
    ``reshape((1, S, C))`` in front of the assembly, which is a program
    whose result cannot alias its operand: one copy of the whole value block
    a shard a query. Inside ``shard_map`` a ``per_device`` body therefore
    reads its ``[S, ...]`` block as it lies.

    Everything here is host work on array handles, so it is what a mesh leaf
    does under the shard locks (query/engine.py ``_try_mesh``): capture,
    assemble, one pjit call. What costs a trip through the runtime — the
    group-id rows' upload, the window plan — comes ready or from
    :class:`MeshLeafMemo`.

    Shards-per-device >= 1: with ``ns == slots * ndev`` shards placed
    round-robin (shard i on device i % ndev — standalone's placement), slot j
    assembles the global array of shards ``[j*ndev + d for d]``; programs
    loop the per-device slot blocks at trace time and reduce locally before
    the collective. No concatenation — per-slot views stay zero-copy."""

    def __init__(self, mesh: Mesh, shards):
        self.mesh = mesh
        self.shards = shards
        ns = len(shards)
        ndev = mesh.devices.size
        assert ns % ndev == 0, "shards must divide evenly over mesh devices"
        self.slots = ns // ndev
        self.ndev = ndev
        s0 = shards[0].store
        self.S, self.C = s0.S, s0.C
        self.sharding = NamedSharding(mesh, P("shard"))

    def _global(self, per_shard_arrays):
        """One slot's global over the devices' own blocks (device order)."""
        a0 = per_shard_arrays[0]
        return jax.make_array_from_single_device_arrays(
            (len(per_shard_arrays) * a0.shape[0],) + a0.shape[1:],
            self.sharding, list(per_shard_arrays))

    def _slot(self, j: int):
        return self.shards[j * self.ndev:(j + 1) * self.ndev]

    def arrays(self):
        """Per-slot tuples of (ts, val, n) global arrays. Narrow-resident
        shards contribute TRANSIENT decodes (ts_block/value_block run on the
        shard's own device, so placement is unchanged) — the general
        collectives read the same f32/i64 view either way; the fused route
        streams the compressed state instead via :meth:`narrow_arrays`. A
        line store's holes are taken out (``closed_arrays``): these
        programs read every row's samples as a sorted prefix."""
        out = []
        for j in range(self.slots):
            closed = [s.store.closed_arrays() for s in self._slot(j)]
            out.append(tuple(self._global([c[k] for c in closed])
                             for k in range(3)))
        return out

    def value_arrays(self):
        """Per-slot (val, n) global arrays — the fused route never reads ts,
        so narrow-resident shards skip the i64 grid derivation entirely."""
        out = []
        for j in range(self.slots):
            ss = self._slot(j)
            out.append((self._global([s.store.value_block() for s in ss]),
                        self._global([s.store.n for s in ss])))
        return out

    def narrow_arrays(self):
        """``(kind, slots)`` where slots are per-slot (block, row_operands, n)
        global arrays of the narrow-resident state, or None unless EVERY
        shard is narrow-resident with the SAME decode variant and no live
        cohort-pool rows (a pool row would need a per-shard row-wise fix,
        and a mixed-variant fleet would need one program per kind — those
        stores take the transient-decode fused route instead). ``kind`` is
        the decode-variant name (ops/decodereg.py: quant16/delta16/delta8)
        and ``row_operands`` its per-series rows (vmin/scale or anchor)."""
        per_shard, kinds = [], set()
        for sh in self.shards:
            nd = sh.store.narrow_operands()
            if nd is None:
                return None
            kind, ops, ok = nd
            if (~ok & (sh.store.n_host > 0)).any():
                return None
            kinds.add(kind)
            per_shard.append(ops)
        if len(kinds) != 1:
            return None
        kind = kinds.pop()
        nrows = len(per_shard[0]) - 1
        out = []
        for j in range(self.slots):
            ss = self._slot(j)
            ops = per_shard[j * self.ndev:(j + 1) * self.ndev]
            out.append((
                self._global([o[0] for o in ops]),
                tuple(self._global([o[r] for o in ops])
                      for r in range(1, nrows + 1)),
                self._global([s.store.n for s in ss])))
        return kind, tuple(out)

    def place_gids(self, group_ids_per_shard) -> tuple:
        """One ``[S]`` int32 row per shard (shard order), each uploaded
        straight to its shard's own device: the rows a :class:`MeshLeafMemo`
        keeps. A row that is a device array already is taken as it is."""
        rows = []
        for sh, g in zip(self.shards, group_ids_per_shard):
            if not isinstance(g, jax.Array):
                # n is resident under every residency state (ts may be elided)
                g = jax.device_put(np.asarray(g, np.int32),
                                   next(iter(sh.store.n.devices())))
            rows.append(g)
        return tuple(rows)

    def global_gids(self, group_ids_per_shard):
        """Per-slot global ``[NDEV * S]`` gid arrays over the shards' rows
        (:meth:`place_gids` puts a host row on its device first). The mesh
        programs only read them: a memo's rows serve every query."""
        rows = self.place_gids(group_ids_per_shard)
        return [self._global(rows[j * self.ndev:(j + 1) * self.ndev])
                for j in range(self.slots)]


def _slot_matrix(fn, slot_tvn, slot_gids, out_ts, window_ms, a0, a1):
    """Yield the per-slot [S, T] matrix + [S] gids of THIS device's blocks
    (a device's share of a ``[NDEV * S, ...]`` global is its own block)."""
    for (ts, val, n), gids in zip(slot_tvn, slot_gids):
        acc = jnp.float64 if val.dtype == jnp.float64 else jnp.float32
        mat = rangefns._periodic(fn, ts, val, n, out_ts, window_ms,
                                 a0, a1, w_cap=256, acc=acc)
        yield mat, gids


def _stack_parts(slot_parts):
    """Per-device partial state, stacked [NSLOT, G, T] under a unit shard
    axis; ``out_specs=P("shard")`` concatenates the devices into one
    [NDEV, NSLOT, G, T] global per partial key. The cross-shard fold is
    deliberately NOT a device collective: psum's reduction order is
    implementation-defined (and shape-dependent), and an in-program f32
    fold rounds differently from the host reduce's f64 accumulator. The
    caller (fold_in_shard_order) folds these blocks on host in SHARD
    order — slot-major, device-minor, shard ``j*ndev + d`` — with the same
    float64 accumulation as the scatter-gather merge (exec._merge_partials),
    so the mesh answer is bit-EQUAL to the host-loop path, not merely
    allclose, and invariant across mesh program shapes."""
    return {k: jnp.stack([p[k] for p in slot_parts])[None]
            for k in slot_parts[0]}


def _dist_program(kernel: str, statics: tuple, slot_shapes: tuple, build,
                  mesh: Mesh, in_specs, out_specs):
    """Mesh twin of the in-process kernel routing: every ``dist_*``
    collective below is a per-key program in the SAME process-global
    compiled-plan cache (query/plancache.py), keyed on its statics plus the
    global-array slot shapes plus the mesh axes — it never aliases the
    per-shard in-process entries (distinct kernel names). A dashboard's
    first mesh query compiles here, every repeat (and every warmup-covered
    shape) hits.

    The entry jits with the explicit boundary shardings from
    ``_sharded_jit`` — both spec trees are REQUIRED parameters, the runtime
    twin of filolint's ``mesh-sharding-undeclared`` rule."""
    from ..query.plancache import plan_cache
    key = statics + slot_shapes + ("mesh", mesh.axis_names,
                                   mesh.devices.size)
    return plan_cache.program(
        kernel, key, build,
        wrap=_sharded_jit(mesh, in_specs, out_specs))


def _tvn_shapes(slot_tvn) -> tuple:
    return tuple((tuple(ts.shape), tuple(n.shape), str(val.dtype))
                 for ts, val, n in slot_tvn)


# in_shardings prefix trees for the two-step collectives: the call signature
# is (slot_tvn, slot_gids, out_ts, window_ms, a0, a1) — store operands ride
# the "shard" axis, step grid and window args replicate
_TWOSTEP_IN_SPECS = (P("shard"), P("shard"), P(), P(), P(), P())


def dist_aggregate(slot_tvn, slot_gids, out_ts, window_ms, a0, a1,
                   fn: str, op: str, num_groups: int, mesh: Mesh):
    return _dist_program(
        "dist-agg", (fn, op, num_groups, mesh, int(out_ts.shape[0])),
        _tvn_shapes(slot_tvn),
        lambda: functools.partial(_dist_aggregate_impl, fn, op, num_groups,
                                  mesh),
        mesh, in_specs=_TWOSTEP_IN_SPECS, out_specs=P("shard")
    )(slot_tvn, slot_gids, out_ts, window_ms, a0, a1)


def _dist_aggregate_impl(fn: str, op: str, num_groups: int, mesh: Mesh,
                         slot_tvn, slot_gids, out_ts, window_ms, a0, a1):
    """One compiled distributed query step: range function per resident slot
    block + STABLE segment partials per device, stacked for the host-order
    fold (fold_in_shard_order presents them with the SAME reduce + host
    presenter the scatter-gather path uses — bit parity by construction)."""

    def per_device(slot_tvn, slot_gids):
        slot_parts = []
        for mat, gids in _slot_matrix(fn, slot_tvn, slot_gids, out_ts,
                                      window_ms, a0, a1):
            slot_parts.append(aggregators.partial_aggregate(
                op, mat, gids, num_groups, stable=True))
        return _stack_parts(slot_parts)

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P("shard"), P("shard")),
        out_specs=P("shard"),
    )(slot_tvn, slot_gids)


def dist_quantile_sketch(slot_tvn, slot_gids, out_ts, window_ms, a0, a1,
                         fn: str, num_groups: int, mesh: Mesh):
    return _dist_program(
        "dist-sketch", (fn, num_groups, mesh, int(out_ts.shape[0])),
        _tvn_shapes(slot_tvn),
        lambda: functools.partial(_dist_quantile_sketch_impl, fn, num_groups,
                                  mesh),
        mesh, in_specs=_TWOSTEP_IN_SPECS, out_specs=P("shard")
    )(slot_tvn, slot_gids, out_ts, window_ms, a0, a1)


def _dist_quantile_sketch_impl(fn: str, num_groups: int, mesh: Mesh,
                               slot_tvn, slot_gids, out_ts, window_ms,
                               a0, a1):
    """Distributed quantile map phase: per-slot range function -> DDSketch
    log-bucket counts scattered on device -> psum over the shard axis.
    Bucketing matches ops/aggregators.quantile_sketch bit-for-bit (same
    gamma/width/edge rules) so the psum'd counts present identically to the
    host merge (ref: AggrOverRangeVectors t-digest partials crossing the
    reduce, :244). Counts are small integers in f32 — exact under ANY
    summation order, so psum needs no ordered-fold replacement here."""
    B = aggregators.SKETCH_BUCKETS
    W = aggregators.SKETCH_WIDTH
    lg = float(np.log(aggregators.SKETCH_GAMMA))

    def per_device(slot_tvn, slot_gids):
        T = out_ts.shape[0]
        counts = jnp.zeros((num_groups * W, T), jnp.float32)
        for mat, gids in _slot_matrix(fn, slot_tvn, slot_gids, out_ts,
                                      window_ms, a0, a1):
            matf = mat.astype(jnp.float64)
            mag = jnp.abs(matf)
            bi = jnp.ceil(jnp.log(mag / aggregators.SKETCH_MIN) / lg)
            bi = jnp.nan_to_num(bi, nan=1.0, posinf=B - 1, neginf=1.0)
            bi = jnp.clip(bi, 1, B - 1).astype(jnp.int32)
            idx = jnp.where(mag <= aggregators.SKETCH_MIN, B,
                            jnp.where(matf > 0, B + bi, B - bi))
            idx = jnp.where(jnp.isposinf(matf), 2 * B, idx)
            idx = jnp.where(jnp.isneginf(matf), 0, idx)
            # rows outside the selection carry an out-of-range gid; mask
            # BEFORE the id arithmetic (gid * W would overflow/wrap back
            # into range) and zero their scatter weight
            sel = gids < num_groups
            g = jnp.where(sel, gids, 0)
            w = jnp.where(jnp.isnan(matf) | ~sel[:, None], 0.0,
                          1.0).astype(jnp.float32)
            comb = g[:, None] * W + idx
            tix = jnp.broadcast_to(jnp.arange(T)[None, :], comb.shape)
            counts = counts.at[comb, tix].add(w)
        counts = jax.lax.psum(counts, "shard")
        return counts.reshape(1, num_groups, W, T)

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P("shard"), P("shard")),
        out_specs=P("shard"),
    )(slot_tvn, slot_gids)


def dist_topk(slot_tvn, slot_gids, out_ts, window_ms, a0, a1,
              fn: str, k: int, bottom: bool, num_groups: int, mesh: Mesh,
              ndev: int):
    return _dist_program(
        "dist-topk",
        (fn, k, bottom, num_groups, mesh, ndev, int(out_ts.shape[0])),
        _tvn_shapes(slot_tvn),
        lambda: functools.partial(_dist_topk_impl, fn, k, bottom, num_groups,
                                  mesh, ndev),
        mesh, in_specs=_TWOSTEP_IN_SPECS,
        out_specs=(P("shard"), P("shard"), P("shard"), P("shard"))
    )(slot_tvn, slot_gids, out_ts, window_ms, a0, a1)


def _dist_topk_impl(fn: str, k: int, bottom: bool, num_groups: int,
                    mesh: Mesh, ndev: int,
                    slot_tvn, slot_gids, out_ts, window_ms, a0, a1):
    """Distributed topk/bottomk: per-slot local top-k candidates, then ONE
    all_gather of the fixed-size [G, T, slots*k] candidate blocks and a
    global re-select — only k*shards candidates cross the ICI, never the
    [S, T] matrices (ref: TopKPartial crossing the reduce node). all_gather
    is device-ordered, so the candidate block order equals the host merge's
    shard order and ties resolve identically (top_k is index-stable).
    Returns (values, rows, shard_ids, present) each [G, T, k]; rows are
    store rows on the owning shard."""
    fmax = float(np.finfo(np.float64).max)
    fill = np.inf if bottom else -np.inf

    def per_device(slot_tvn, slot_gids):
        T = out_ts.shape[0]
        dev = jax.lax.axis_index("shard")
        vs, rs, ss, oks = [], [], [], []
        for j, (mat, gids) in enumerate(_slot_matrix(
                fn, slot_tvn, slot_gids, out_ts, window_ms, a0, a1)):
            matf = mat.astype(jnp.float64)
            valid = ~jnp.isnan(matf)
            # real +/-Inf must outrank empty (fill) slots on ties: clamp to
            # +/-DBL_MAX in the sort domain only (same rule as _map_topk)
            sortable = jnp.clip(matf, -fmax, fmax)
            kk = min(k, matf.shape[0])
            gv_l, gr_l, gok_l = [], [], []
            for gi in range(num_groups):
                m = (gids == gi)[:, None] & valid
                sv = jnp.where(m, sortable, fill)
                sv = -sv if bottom else sv
                _, topi = jax.lax.top_k(sv.T, kk)            # [T, kk]
                gv_l.append(jnp.take_along_axis(matf.T, topi, axis=1))
                gr_l.append(topi)
                gok_l.append(jnp.take_along_axis(m.T, topi, axis=1))
            vs.append(jnp.stack(gv_l))                       # [G, T, kk]
            rs.append(jnp.stack(gr_l))
            oks.append(jnp.stack(gok_l))
            ss.append(jnp.full((num_groups, T, kk),
                               j * ndev, jnp.int32) + dev)
        lv = jnp.concatenate(vs, axis=2)
        lr = jnp.concatenate(rs, axis=2).astype(jnp.int32)
        lsh = jnp.concatenate(ss, axis=2)
        lok = jnp.concatenate(oks, axis=2)
        gv = jnp.moveaxis(jax.lax.all_gather(lv, "shard"), 0, 2)
        gr = jnp.moveaxis(jax.lax.all_gather(lr, "shard"), 0, 2)
        gsh = jnp.moveaxis(jax.lax.all_gather(lsh, "shard"), 0, 2)
        gok = jnp.moveaxis(jax.lax.all_gather(lok, "shard"), 0, 2)
        C = gv.shape[2] * gv.shape[3]
        gv = gv.reshape(num_groups, T, C)
        gr = gr.reshape(num_groups, T, C)
        gsh = gsh.reshape(num_groups, T, C)
        gok = gok.reshape(num_groups, T, C)
        sv = jnp.where(gok, jnp.clip(gv, -fmax, fmax), fill)
        sv = -sv if bottom else sv
        kk2 = min(k, C)
        _, sel = jax.lax.top_k(sv, kk2)                      # [G, T, kk2]
        return (jnp.take_along_axis(gv, sel, axis=2)[None],
                jnp.take_along_axis(gr, sel, axis=2)[None],
                jnp.take_along_axis(gsh, sel, axis=2)[None],
                jnp.take_along_axis(gok, sel, axis=2)[None])

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P("shard"), P("shard")),
        out_specs=(P("shard"), P("shard"), P("shard"), P("shard")),
    )(slot_tvn, slot_gids)


def _fused_map_call(fn: str, needs_sumsq: bool, window_ms: int,
                    interval_ms: int, S: int, Sb: int, C: int, Tp: int,
                    G: int, residency: str, c0: int, Ck: int, variant: str):
    """The per-shard fused map-phase program by backend variant — the
    Pallas kernel or its XLA-fused scan twin (same tiling plan, same
    tile_contrib math; ops/fusedgrid.py). ``residency`` names the decode
    variant streamed through the kernel (ops/decodereg.py);
    ``query.fused_kernels`` picks the backend; ``variant`` is its
    fusedgrid.kernel_tag name ("xla" | "pallas" | "pallas-interpret") and
    rides the dist program's plan-cache key."""
    if variant == "xla":
        return fusedgrid.build_xla_tiles(fn, needs_sumsq, window_ms,
                                         interval_ms, S, Sb, C, Tp, G,
                                         residency=residency, c0=c0, Ck=Ck)
    return fusedgrid.build_pallas(fn, needs_sumsq, window_ms, interval_ms,
                                  S, Sb, C, Tp, G, variant != "pallas",
                                  residency=residency, c0=c0, Ck=Ck)


def _fused_parts(op: str, outs) -> dict:
    """The fused kernel's (sum, count, sumsq) tuple as a partial dict in the
    shared ops/aggregators format (count-only ops keep just the count)."""
    if op in ("count", "group"):
        return {"count": outs[1]}
    return dict(zip(("sum", "count", "sumsq"), outs))


# fused call signature: (slot_vals, slot_ns, slot_gids, band, ohlo, lo, hi,
# rel) — resident blocks and gids ride the shard axis; band/edge operands
# replicate (they are shape-cached per query, NEVER donated)
_FUSED_IN_SPECS = (P("shard"), P("shard"), P("shard"),
                   P(), P(), P(), P(), P())
# narrow call signature: (slot_blocks, slot_rows, slot_ns, slot_gids, band,
# ohlo, lo, hi, rel) — slot_rows is a NESTED tuple (one row-operand tuple
# per slot); the P("shard") spec is a pytree prefix that broadcasts over it,
# so one spec tree serves every decode variant's row count
_FUSED_NARROW_IN_SPECS = (P("shard"), P("shard"), P("shard"), P("shard"),
                          P(), P(), P(), P(), P())


def dist_fused_aggregate(slot_vals, slot_ns, slot_gids, band, ohlo, lo, hi, rel,
                         fn: str, op: str, num_groups: int, mesh: Mesh,
                         window_ms: int, interval_ms: int,
                         S: int, C: int, Tp: int, c0: int = 0, Ck: int = 0,
                         variant: str = "pallas"):
    return _dist_program(
        "dist-fused",
        (fn, op, num_groups, mesh, window_ms, interval_ms, S, C, Tp, c0, Ck,
         variant),
        tuple(str(v.dtype) for v in slot_vals),
        lambda: functools.partial(_dist_fused_aggregate_impl, fn, op,
                                  num_groups, mesh, window_ms, interval_ms,
                                  S, C, Tp, c0, Ck, variant),
        mesh, in_specs=_FUSED_IN_SPECS, out_specs=P("shard")
    )(slot_vals, slot_ns, slot_gids, band, ohlo, lo, hi, rel)


def _dist_fused_aggregate_impl(fn: str, op: str, num_groups: int, mesh: Mesh,
                               window_ms: int, interval_ms: int,
                               S: int, C: int, Tp: int, c0: int, Ck: int,
                               variant: str,
                               slot_vals, slot_ns, slot_gids, band, ohlo,
                               lo, hi, rel):
    """Fused single-pass map phase on every resident slot block, partial
    state stacked for the host-order fold — the multi-chip twin of
    ``fusedgrid.fused_grid_aggregate`` (ref: AggrOverRangeVectors.scala:62 —
    the same AggregateMapReduce map phase runs identically on every data
    node; fold_in_shard_order IS the reduce node, in the host merge's
    shard order and precision). Band/edge operands are replicated; each
    device streams only its resident [S, C] blocks, one kernel pass per
    slot."""
    needs_sumsq = op in ("stddev", "stdvar")
    Sb = 512 if S % 512 == 0 else S
    call = _fused_map_call(fn, needs_sumsq, window_ms, interval_ms,
                           S, Sb, C, Tp, num_groups, "raw", c0, Ck, variant)

    def per_device(slot_vals, slot_ns, slot_gids, band, ohlo, lo, hi, rel):
        slot_parts = []
        for val, n, gids in zip(slot_vals, slot_ns, slot_gids):
            o = call(val.astype(jnp.float32),
                     fusedgrid.lane_major(n.astype(jnp.int32), Sb),
                     fusedgrid.lane_major(gids.astype(jnp.int32), Sb),
                     band, ohlo, lo, hi, rel)
            slot_parts.append(_fused_parts(op, o))
        return _stack_parts(slot_parts)

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("shard"), P(), P(), P(), P(), P()),
        out_specs=P("shard"),
        # pallas_call emits ShapeDtypeStructs without varying-mesh-axis
        # annotations; the kernel is per-shard-local so vma checking adds
        # nothing here
        check_vma=False,
    )(slot_vals, slot_ns, slot_gids, band, ohlo, lo, hi, rel)


def dist_fused_aggregate_narrow(slot_blocks, slot_rows, slot_ns,
                                slot_gids, band, ohlo, lo, hi, rel,
                                fn: str, op: str, num_groups: int, mesh: Mesh,
                                window_ms: int, interval_ms: int,
                                S: int, C: int, Tp: int,
                                kind: str = "quant16", c0: int = 0,
                                Ck: int = 0, variant: str = "pallas"):
    return _dist_program(
        "dist-fused-narrow",
        (fn, op, num_groups, mesh, window_ms, interval_ms, S, C, Tp, kind,
         c0, Ck, variant),
        tuple(str(b.dtype) for b in slot_blocks),
        lambda: functools.partial(_dist_fused_narrow_impl, fn, op,
                                  num_groups, mesh, window_ms, interval_ms,
                                  S, C, Tp, kind, c0, Ck, variant),
        mesh, in_specs=_FUSED_NARROW_IN_SPECS, out_specs=P("shard")
    )(slot_blocks, slot_rows, slot_ns, slot_gids, band, ohlo, lo, hi, rel)


def _dist_fused_narrow_impl(fn: str, op: str, num_groups: int, mesh: Mesh,
                            window_ms: int, interval_ms: int,
                            S: int, C: int, Tp: int, kind: str,
                            c0: int, Ck: int, variant: str,
                            slot_blocks, slot_rows, slot_ns,
                            slot_gids, band, ohlo, lo, hi, rel):
    """Narrow twin of :func:`dist_fused_aggregate`: every shard's resident
    narrow state (i16 quantized, or i16/i8 integer deltas off a per-series
    anchor — ops/decodereg.py names the variant) streams straight through
    the fused map kernel (1-2 bytes per sample over the HBM bus, decode in
    VMEM — ops/narrow.py) and the partial state folds over the shard axis
    in shard order. Compressed-resident stores stay mesh-eligible without
    ever materializing their f32 blocks."""
    needs_sumsq = op in ("stddev", "stdvar")
    Sb = 512 if S % 512 == 0 else S
    call = _fused_map_call(fn, needs_sumsq, window_ms, interval_ms,
                           S, Sb, C, Tp, num_groups, kind, c0, Ck, variant)

    def per_device(slot_blocks, slot_rows, slot_ns, slot_gids,
                   band, ohlo, lo, hi, rel):
        slot_parts = []
        for blk, rows, n, gids in zip(slot_blocks, slot_rows, slot_ns,
                                      slot_gids):
            o = call(blk, *(fusedgrid.lane_major(r, Sb) for r in rows),
                     fusedgrid.lane_major(n.astype(jnp.int32), Sb),
                     fusedgrid.lane_major(gids.astype(jnp.int32), Sb),
                     band, ohlo, lo, hi, rel)
            slot_parts.append(_fused_parts(op, o))
        return _stack_parts(slot_parts)

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P("shard"), P("shard"), P("shard"), P("shard"),
                  P(), P(), P(), P(), P()),
        out_specs=P("shard"),
        check_vma=False,
    )(slot_blocks, slot_rows, slot_ns, slot_gids, band, ohlo, lo, hi, rel)


def _takes_fused(fn: str, op: str, S: int, C: int, T: int, G: int) -> bool:
    """The query's and the shape's side of the fused gate (the stores' side
    is ``MeshQueryExecutor._fused_grid``)."""
    return (fusedresident.tag() != "off"
            and fn in fusedgrid.FUSED_FNS | fusedgrid.FUSED_WINDOW_FNS
            and op in fusedgrid.FUSED_OPS
            and fusedgrid.fusable(S, C, T, G))


def _steps(out_ts: np.ndarray):
    """The step grid bucketed (padded to a multiple of 32, repeating the
    last step): the general programs jit-compile per output shape and ad-hoc
    dashboards would otherwise recompile per query — the same compile-space
    bucketing as the in-process path. A HOST array: it goes up replicated
    inside the one pjit call."""
    from ..query.exec import _pad_steps
    return _pad_steps(np.asarray(out_ts, np.int64))


def _host_args(window_ms: int, args) -> tuple:
    """(window, a0, a1) as host scalars of the general programs' call."""
    return (np.int64(window_ms), np.float64(args[0]), np.float64(args[1]))


def _plan_key(C: int, out_ts: np.ndarray, window_ms: int, base_ts: int,
              interval_ms: int, fn: str, kind: str) -> tuple:
    """:func:`_mesh_operands`' arguments after the mesh for one query on one
    grid: all the query's but ``(base_ts, interval_ms)`` and the decode
    variant ``kind``, which the stores decide."""
    Tp = (max(len(out_ts), 1) + 127) // 128 * 128
    return (C, Tp,
            np.ascontiguousarray(np.asarray(out_ts, np.int64)).tobytes(),
            int(window_ms), int(base_ts), int(interval_ms),
            "window" if fn in fusedgrid.FUSED_WINDOW_FNS else "rate",
            decodereg.variant(kind).full_columns)


@functools.lru_cache(maxsize=32)
def _mesh_operands(mesh: Mesh, C: int, Tp: int, out_ts_key: bytes,
                   window_ms: int, base_ts: int, interval_ms: int,
                   fn_kind: str, full_cols: bool):
    """The window plan of one step grid (``fusedgrid.host_operands``: band,
    one-hot and edge operands, megabytes that only the grid decides) placed
    REPLICATED on the mesh, as the fused programs' ``in_shardings`` ask for
    it — the mesh twin of ``fusedgrid._device_operands``, whose arrays sit
    on the default device and would be spread to the others inside every
    dispatch. Cached per grid; an ad-hoc explorer never repeats one, so the
    leaf asks for it BEFORE it takes the shard locks
    (:meth:`MeshLeafMemo.prepare_plan`) and hits here under them."""
    *arrs, c0, Ck = fusedgrid.host_operands(
        C, Tp, np.frombuffer(out_ts_key, np.int64), window_ms, base_ts,
        interval_ms, fn_kind, full_cols)
    return tuple(jax.device_put(arrs, NamedSharding(mesh, P()))) + (c0, Ck)


class MeshLeafMemo:
    """What a mesh leaf computes from the QUERY, the INDEX state or the
    STORES' shape and from no sample, kept by the engine from one query to
    the next so that the leaf holds every shard's lock for validation, array
    capture and ONE pjit call (query/engine.py ``_try_mesh``). Each trip
    through the runtime under four locks is a hold of all four — and the
    rate of a lock-bound mesh is 1000 / hold.

    - **The window plan.** Its key is the query's except for the stores'
      grid and decode variant. ``seen`` is what the last fused dispatch
      observed of those; :meth:`prepare_plan`, called before the locks,
      builds the plan for it. Under the locks the executor's own lookup then
      hits (``plan=ready``); a grid or variant that moved meanwhile builds
      there as it always did (``plan=built``).
    - **The group-id rows.** The shared numbering of the groups and the
      shards' dense rows depend only on the shards' kept ``ShardSelection``s
      (core/selection.py: one object a selector an index state) and on
      ``(by, without)``: an LRU of ``GIDS`` entries keyed by the selections'
      identity holds the group keys and the rows, each resident on its own
      shard's device. An entry keeps its selections alive, so an identity is
      never reused while it is a key. A selection the shard does not keep
      (``stamp is None``: narrow, a time mask that bites, recovering) is
      never a key.

    :meth:`gids` and :meth:`keep_gids` run under every shard's lock (one
    mesh leaf at a time); :meth:`prepare_plan` runs under none and touches
    only ``seen``, one tuple swapped whole."""

    # (selector, grouping) pairs kept: 4 B a series a chip each, and the
    # selections of an index state that has passed until they age out
    GIDS = 8

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.seen = None    # (S, C, base_ts, interval_ms, decode variant)
        self._gids: OrderedDict = OrderedDict()

    def __len__(self) -> int:
        return len(self._gids)

    def prepare_plan(self, fn: str, op: str, out_ts: np.ndarray,
                     window_ms: int):
        """Build (or find) the window plan of this query on the grid the
        last fused dispatch saw; the key to hand the executor, None where
        nothing was seen yet or the query takes no fused program."""
        seen = self.seen
        if seen is None:
            return None
        S, C, base_ts, interval_ms, kind = seen
        if not _takes_fused(fn, op, S, C, len(out_ts), 8):
            return None
        key = _plan_key(C, out_ts, window_ms, base_ts, interval_ms, fn, kind)
        _mesh_operands(self.mesh, *key)
        return key

    @staticmethod
    def _gids_key(selections, by, without):
        if any(sel.stamp is None for sel in selections):
            return None
        return tuple(map(id, selections)), tuple(by), tuple(without)

    def gids(self, selections, by, without):
        """``(group keys, device rows)`` kept for these selections under this
        grouping, None where there is none (or can be none: a bypass)."""
        key = self._gids_key(selections, by, without)
        kept = self._gids.get(key)
        if kept is None:
            return None
        self._gids.move_to_end(key)
        return kept[1:]

    def keep_gids(self, selections, by, without, keys, rows) -> bool:
        """Keep what a leaf just built; False for a bypass (never kept)."""
        key = self._gids_key(selections, by, without)
        if key is None:
            return False
        self._gids[key] = (tuple(selections), tuple(keys), tuple(rows))
        if len(self._gids) > self.GIDS:
            self._gids.popitem(last=False)
        return True


def fold_in_shard_order(op: str, num_groups: int, T: int | None,
                        host: dict) -> np.ndarray:
    """The mesh route's reduce node, on FETCHED partial state: what the
    dispatch's handle (``diagnostics.Dispatched``) answers with. The engine
    dispatches under the shard locks but fetches outside them (same
    contract as the in-process leaf: a slow collective must not stall
    ingest on every shard for its full wall time).

    The mesh program returns UNFOLDED partial state (dict of
    [NDEV, NSLOT, G, T] globals — each device's stacked per-slot partials);
    this folds them in SHARD order (slot-major, device-minor: shard
    ``j*ndev + d``) with the same float64 accumulation as the scatter-gather
    merge (exec._merge_partials), then presents with the SAME
    ``aggregators.present_partials`` host presenter the host-loop reduce
    uses — so the presented values carry no device/host dtype-promotion or
    fold-order skew and match the host path bit-for-bit."""
    merged: dict[str, np.ndarray] = {}
    for name, g in host.items():          # g: [NDEV, NSLOT, G, T]
        ndev, nslot = g.shape[0], g.shape[1]
        acc = g[0, 0].astype(np.float64)  # shard 0 seeds, exactly as the
        for j in range(nslot):            # host merge's first base does
            for d in range(ndev):
                if j == 0 and d == 0:
                    continue
                a = g[d, j]               # shard j*ndev + d
                if name == "min":
                    acc = np.minimum(acc, a)
                elif name == "max":
                    acc = np.maximum(acc, a)
                else:
                    acc = acc + a
        merged[name] = acc
    vals = aggregators.present_partials(op, merged)[:num_groups]
    return vals[:, :T] if T is not None else vals


def _present_sketch(num_groups: int, T: int, q: float, counts) -> np.ndarray:
    # ``counts``: the first device's copy of the psummed sketch, fetched
    return aggregators.present_quantile_sketch(
        counts[0][:num_groups, :, :T], q)


def _present_topk(num_groups: int, T: int, outs):
    v, r, sh, ok = (o[0][:num_groups] for o in outs)
    # [G, T, k] -> [G, k, T]; un-padded steps only
    mv = np.moveaxis(v, 2, 1)[:, :, :T]
    return (np.where(np.moveaxis(ok, 2, 1)[:, :, :T], mv, np.nan),
            np.moveaxis(sh, 2, 1)[:, :, :T],
            np.moveaxis(r, 2, 1)[:, :, :T],
            np.moveaxis(ok, 2, 1)[:, :, :T])


class MeshQueryExecutor:
    """Runs aggregation queries over a DistributedStore (used by the engine when
    a mesh is configured; falls back to in-process scatter-gather otherwise).

    Routing: when the query is fusable (rate/increase/delta into
    sum/avg/count/group/stddev/stdvar), every shard store is f32,
    grid-aligned to one common (base, interval) with a single uniform start
    cohort, and the shapes fit the fused kernel's VMEM gate, the per-shard
    map phase runs the single-pass fused Pallas kernel; otherwise the
    general two-step kernels. ``last_path`` records the route taken,
    ``last_block`` the fused kernel's column block ``(c0, columns)`` and
    ``last_plan`` whether its window plan came ``ready`` (the key the
    caller prepared before the locks) or was ``built`` here — both None on
    every other route. Every method is host work on handles and ONE pjit
    call: the step grid, window and arguments of the general programs go in
    as host values of that call. Each returns what the caller's dispatch
    handle holds (``diagnostics.Dispatched.holds``): ``(device outputs, the
    pure function from those outputs, fetched, to the answer)``."""

    def __init__(self, dstore: DistributedStore,
                 memo: MeshLeafMemo | None = None):
        self.dstore = dstore
        self.memo = memo
        self.last_path: str | None = None
        self.last_block: tuple[int, int] | None = None
        self.last_plan: str | None = None

    def _fused_grid(self):
        """Common (base_ts, interval_ms) when every shard qualifies for the
        fused map phase, else None."""
        grids = set()
        for sh in self.dstore.shards:
            st = sh.store
            if st is None or st.dtype != jnp.float32:
                return None
            gi = st.grid_info()
            if gi is None:
                return None
            kind, off = st.grid_cohorts()
            # (a row born late in time-aligned cells: the mesh's fused
            # programs have no births mode, the general ones read the
            # rows moved left, ``closed_arrays``)
            if kind != "uniform" or off != 0 or st.born_late:
                return None
            grids.add(gi)
        return grids.pop() if len(grids) == 1 else None

    def aggregate(self, fn: str, op: str, out_ts: np.ndarray, window_ms: int,
                  group_ids_per_shard: list[np.ndarray], num_groups: int,
                  args=(0.0, 0.0), fetch: bool = True, prepared=None):
        slot_gids = tuple(self.dstore.global_gids(group_ids_per_shard))
        G = _pow2(num_groups)
        S, C, T = self.dstore.S, self.dstore.C, len(out_ts)
        variant = fusedresident.tag()
        grid = (self._fused_grid() if _takes_fused(fn, op, S, C, T, G)
                else None)
        if grid is not None:
            base_ts, interval_ms = grid
            # narrow-resident shards stream their 1-2B/sample state through
            # the fused kernel; stores with cohort-pool rows (or raw
            # residency) feed it the f32 view instead (a transient decode
            # per shard when compressed — bit-identical by the round-trip
            # contract). Resolved BEFORE the band operands: delta variants
            # decode via a column-prefix cumsum, so they pin full columns
            narrow = self.dstore.narrow_arrays()
            kind = narrow[0] if narrow is not None else "raw"
            # the [C, Tp] bands are megabytes that only the step grid
            # decides: ready where the caller prepared this very key before
            # it took the locks, built (and uploaded) here otherwise
            key = _plan_key(C, out_ts, window_ms, base_ts, interval_ms, fn,
                            kind)
            self.last_plan = "ready" if key == prepared else "built"
            count_mesh_prepared("plan", self.last_plan)
            band, ohlo, lo, hi, rel, c0, Ck = _mesh_operands(
                self.dstore.mesh, *key)
            if self.memo is not None:
                self.memo.seen = (S, C, base_ts, interval_ms, kind)
            Tp = key[1]
            with jax.enable_x64(False):
                if narrow is not None:
                    slots = narrow[1]
                    out = dist_fused_aggregate_narrow(
                        tuple(t[0] for t in slots),
                        tuple(t[1] for t in slots),
                        tuple(t[2] for t in slots),
                        slot_gids, band, ohlo, lo, hi, rel,
                        fn, op, G, self.dstore.mesh, int(window_ms),
                        int(interval_ms), S, C, Tp, kind, c0, Ck, variant)
                else:
                    slot_vn = tuple(self.dstore.value_arrays())
                    out = dist_fused_aggregate(
                        tuple(t[0] for t in slot_vn),
                        tuple(t[1] for t in slot_vn),
                        slot_gids, band, ohlo, lo, hi, rel,
                        fn, op, G, self.dstore.mesh, int(window_ms),
                        int(interval_ms), S, C, Tp, c0, Ck, variant)
            fusedresident.count_served(
                fusedresident.scalar_shape_of(fn) or "rate_sum")
            # exec-path keeps the historical "fused"/"fused-narrow" names
            # for the default pallas backend; the xla twin is suffixed
            sfx = "-xla" if variant == "xla" else ""
            self.last_path = ("fused-narrow" if narrow is not None
                              else "fused") + sfx
            self.last_block = (int(c0), int(Ck))
        else:
            slot_tvn = tuple(self.dstore.arrays())
            out_eval, T = _steps(out_ts)
            out = dist_aggregate(slot_tvn, slot_gids, out_eval,
                                 *_host_args(window_ms, args),
                                 fn, op, G, self.dstore.mesh)
            self.last_path = "twostep"
        answer = functools.partial(fold_in_shard_order, op, num_groups, T)
        return answer(jax.device_get(out)) if fetch else (out, answer)

    def quantile(self, fn: str, out_ts: np.ndarray, window_ms: int,
                 group_ids_per_shard: list[np.ndarray], num_groups: int,
                 q: float, args=(0.0, 0.0)):
        """Distributed quantile: sketch counts psum over the mesh; the answer
        presents [G, T] on host (same presenter as the in-process
        SketchPartial merge)."""
        slot_tvn = tuple(self.dstore.arrays())
        slot_gids = tuple(self.dstore.global_gids(group_ids_per_shard))
        out_eval, T = _steps(out_ts)
        # pow2-bucket the group count: a churning by() cardinality must not
        # compile a fresh program per distinct G (same rule as aggregate())
        Gp = _pow2(num_groups)
        out = dist_quantile_sketch(slot_tvn, slot_gids, out_eval,
                                   *_host_args(window_ms, args), fn, Gp,
                                   self.dstore.mesh)
        self.last_path = "sketch"
        return (out.addressable_shards[0].data,
                functools.partial(_present_sketch, num_groups, T, q))

    def topk(self, fn: str, out_ts: np.ndarray, window_ms: int,
             group_ids_per_shard: list[np.ndarray], num_groups: int,
             k: int, bottom: bool, args=(0.0, 0.0)):
        """Distributed topk/bottomk: local candidates + ONE all_gather of
        fixed-size blocks + global re-select, all on the mesh. The answer is
        (values [G, k, T], shard_ids, rows, present) — the caller maps
        (shard, row) back to series keys."""
        slot_tvn = tuple(self.dstore.arrays())
        slot_gids = tuple(self.dstore.global_gids(group_ids_per_shard))
        out_eval, T = _steps(out_ts)
        Gp = _pow2(num_groups)    # compile-space bucketing, as aggregate()
        outs = dist_topk(slot_tvn, slot_gids, out_eval,
                         *_host_args(window_ms, args), fn, int(k),
                         bool(bottom), Gp, self.dstore.mesh, self.dstore.ndev)
        self.last_path = "topk"
        return (tuple(o.addressable_shards[0].data for o in outs),
                functools.partial(_present_topk, num_groups, T))


def warm_mesh_shape(fn: str, op: str, S: int, C: int, steps: int,
                    step_ms: int, window_ms: int, interval_ms: int,
                    groups: int, dtype, grid: bool = True,
                    residency: str = "raw") -> None:
    """Pre-trace the mesh ``dist_*`` programs for one dashboard shape
    (``query.warmup_shapes`` entries with ``mesh: true`` — plancache.warmup
    calls this). Warms the general two-step program always and the fused
    program (the ACTIVE ``query.fused_kernels`` variant) when the shape
    qualifies, with operands in the serving leaf's own form (a device's
    ``[S, ...]`` block of a ``[NDEV * S, ...]`` global, the window plan
    replicated on the mesh, host step grid and scalars), so the warmed
    executable is the serving executable.
    ``residency`` names a decode variant
    (ops/decodereg.py) to warm the narrow-streaming program for in addition
    to the raw one — the first dashboard hit on a compressed-resident fleet
    then compiles nothing."""
    mesh = make_mesh()
    ndev = mesh.devices.size
    if ndev < 2:
        return
    sharding = NamedSharding(mesh, P("shard"))
    devs = list(mesh.devices.ravel())

    def gput(block_shape, dt):
        arrs = [jax.device_put(jnp.zeros(block_shape, dt), d) for d in devs]
        return jax.make_array_from_single_device_arrays(
            (ndev * block_shape[0],) + block_shape[1:], sharding, arrs)

    out_ts = np.int64(window_ms) + np.arange(steps, dtype=np.int64) * step_ms
    out_eval, _T = _steps(out_ts)
    Gp = _pow2(groups)
    val = gput((S, C), dtype)
    n = gput((S,), jnp.int32)
    ts = gput((S, C), jnp.int64)
    gids = gput((S,), jnp.int32)

    dist_aggregate(((ts, val, n),), (gids,), out_eval,
                   *_host_args(window_ms, (0.0, 0.0)),
                   fn, op, Gp, mesh)
    variant = fusedresident.tag()
    if (grid and dtype == jnp.float32
            and _takes_fused(fn, op, S, C, steps, Gp)):
        key = _plan_key(C, out_ts, window_ms, 0, interval_ms, fn, "raw")
        Tp = key[1]
        band, ohlo, lo, hi, rel, c0, Ck = _mesh_operands(mesh, *key)
        with jax.enable_x64(False):
            dist_fused_aggregate(
                (val,), (n,), (gids,), band, ohlo, lo, hi, rel,
                fn, op, Gp, mesh, int(window_ms), int(interval_ms),
                S, C, Tp, c0, Ck, variant)
            if residency != "raw":
                var = decodereg.variant(residency)
                bandn, ohlon, lon, hin, reln, c0n, Ckn = _mesh_operands(
                    mesh, *_plan_key(C, out_ts, window_ms, 0, interval_ms,
                                     fn, residency))
                blk = gput((S, C), var.block_dtype)
                rows = tuple(gput((S,), jnp.float32)
                             for _ in range(var.row_operands))
                dist_fused_aggregate_narrow(
                    (blk,), (rows,), (n,), (gids,),
                    bandn, ohlon, lon, hin, reln,
                    fn, op, Gp, mesh, int(window_ms), int(interval_ms),
                    S, C, Tp, residency, c0n, Ckn, variant)


def _pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p *= 2
    return p
