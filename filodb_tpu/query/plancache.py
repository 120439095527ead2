"""Compiled-plan cache: the query-serving fast path's program store.

Reference: QueryEngine2's materializer serves dashboard-scale concurrency by
reusing materialized plans; here the expensive artifact is the traced+compiled
XLA program, so the cache holds exactly those. Every query-path kernel entry
point (in-process PSM/grid/fused, the segment reduce, and the mesh
``dist_*`` collectives — query/exec.py, ops/, parallel/distributed.py)
funnels through :meth:`CompiledPlanCache.program` with a key derived from the
PADDED plan shape: ``_pow2`` row/group buckets, ``_pad_steps`` step buckets,
fn/op, dtype, and the residency mode (narrow/hist variants are distinct
kernels, so residency is part of the key by construction). Remote-leaf
execution runs the same exec.py code on the peer, so all three serving paths
share one process-global cache.

Design: each entry owns a PRIVATE ``jax.jit`` wrapper whose statics are
pre-bound via closure. That makes the cache honest in all three directions:

  * hit    — the entry's jit wrapper is reused; nothing re-traces (its
             internal dispatch cache already holds the executable);
  * miss   — a fresh wrapper traces and compiles on first call, under the
             ``query.compile`` span (span count == compile count, the
             compile-count test harness's substrate);
  * evict  — dropping the entry drops the only reference to its wrapper and
             therefore the compiled executable: the capacity bound actually
             bounds retained program memory, unlike jax's unbounded
             per-function caches.

Keys are a SHARING hint, not a correctness contract: if two call sites ever
disagree with a key about shapes, the entry's own jit wrapper re-traces on
the aval mismatch — results are always correct, only the accounting coarsens.
The ``traces`` counter increments INSIDE the traced body (Python side effects
run at trace time only), so it counts real traces, not cache bookkeeping.
"""

from __future__ import annotations

import threading
import time

from collections import OrderedDict

from ..utils.metrics import (FILODB_QUERY_COMPILE_CACHE_EVICTIONS,
                             FILODB_QUERY_COMPILE_CACHE_HITS,
                             FILODB_QUERY_COMPILE_CACHE_MISSES, registry)
from ..utils.tracing import SPAN_QUERY_COMPILE, span

DEFAULT_CAPACITY = 256


class _Entry:
    __slots__ = ("call", "compiled")

    def __init__(self):
        self.call = None
        self.compiled = False


class CompiledPlanCache:
    """Capacity-bounded LRU of per-shape compiled query programs."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = max(1, int(capacity))
        self._entries: OrderedDict[tuple, _Entry] = OrderedDict()
        self._lock = threading.Lock()
        # real trace count: incremented from INSIDE traced bodies (trace-time
        # Python execution), so a retrace the key bucketing missed still
        # counts — the compile-count tests read this, not misses
        self.traces = 0
        self._hits = registry.counter(FILODB_QUERY_COMPILE_CACHE_HITS)
        self._misses = registry.counter(FILODB_QUERY_COMPILE_CACHE_MISSES)
        self._evictions = registry.counter(
            FILODB_QUERY_COMPILE_CACHE_EVICTIONS)

    def _note_trace(self) -> None:
        with self._lock:
            self.traces += 1

    def program(self, kernel: str, key: tuple, build, wrap=None):
        """The cached program for ``(kernel, *key)``; on miss, ``build()``
        returns the pure Python callable (statics pre-bound) this entry
        jits. The returned callable's FIRST invocation runs under the
        ``query.compile`` span — trace + compile + first execution.

        ``wrap`` overrides the default ``jax.jit`` applicator: the mesh
        ``dist_*`` programs pass a sharded-jit closure (explicit
        ``in_shardings``/``out_shardings`` + donation, built where the mesh
        is known — parallel/distributed.py) so the global-view executable
        still rides this cache's hit/trace/span accounting. The CALLER must
        key such entries distinctly (the mesh in ``key``): the cache
        cannot see that two builds wrap differently."""
        import jax
        full = (kernel, *key)
        with self._lock:
            e = self._entries.get(full)
            if e is not None:
                self._entries.move_to_end(full)
                self._hits.increment()
                return e.call
        # build outside the lock: tracing/compiling a racing duplicate is
        # wasted work, never wrong (each wrapper is self-contained); the
        # store below keeps the first one in
        pyfn = build()
        note = self._note_trace

        def probe(*a, **k):
            note()                 # executes at TRACE time only
            return pyfn(*a, **k)

        jitted = (wrap or jax.jit)(probe)
        e = _Entry()

        def call(*a, **k):
            if e.compiled:
                return jitted(*a, **k)
            with span(SPAN_QUERY_COMPILE, kernel=kernel):
                out = jitted(*a, **k)
            e.compiled = True
            return out

        e.call = call
        with self._lock:
            cur = self._entries.get(full)
            if cur is not None:        # racing builder won: reuse its entry
                self._entries.move_to_end(full)
                self._hits.increment()
                return cur.call
            self._entries[full] = e
            self._misses.increment()
            self._evict_over_capacity_locked()
        return e.call

    def _evict_over_capacity_locked(self) -> None:
        while len(self._entries) > self.capacity:
            self._entries.popitem(last=False)
            self._evictions.increment()

    def resize(self, capacity: int) -> None:
        with self._lock:
            self.capacity = max(1, int(capacity))
            self._evict_over_capacity_locked()

    def clear(self) -> None:
        """Drop every compiled program (benchmarks use this to re-measure
        the cold path; not counted as evictions)."""
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def keys(self) -> list[tuple]:
        """The cached programs' full keys ``(kernel, *key)`` — fused-tier
        keys carry fusedgrid.kernel_tag's variant name, so what is compiled
        and what is interpreted can be read off the cache."""
        with self._lock:
            return list(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {"size": len(self._entries), "capacity": self.capacity,
                    "traces": self.traces,
                    "hits": self._hits.value, "misses": self._misses.value,
                    "evictions": self._evictions.value}


# one process-global cache, like the tracer and the metrics registry: the
# in-process, mesh, and remote-leaf (peer-side) paths all share it
plan_cache = CompiledPlanCache()


def warmup(shapes: list) -> dict:
    """Pre-trace the hot query shapes (config: ``query.warmup_shapes``) so
    the first dashboard load never eats a multi-second XLA compile.

    Each spec is a dict: ``fn`` (range function, default "rate"), ``op``
    (aggregation, default "sum"), ``series`` (selection width — padded to
    the same pow2 bucket the leaf gather uses; pass the store's padded row
    count for wide dashboards), ``samples`` (store capacity C), ``steps``
    (output step count), ``step_ms``, ``window_ms``, ``interval_ms`` (scrape
    interval — part of the FUSED kernel's static key), ``groups`` (by()
    cardinality), ``dtype`` ("float32"/"float64"), ``grid`` (False to
    warm only the general searchsorted path), ``buckets`` (>0 warms the
    fused hist-resident quantile variant for that bucket count too, with
    ``dd_dtype`` "int16"/"int8"), ``residency`` (a scalar decode-variant
    name — "quant16"/"delta16"/"delta8", ops/decodereg.py — to warm the
    narrow-streaming fused program for in ADDITION to the raw one, so a
    compressed-resident fleet's first dashboard hit compiles nothing; the
    mesh warm inherits it). Fused-tier shapes warm the variant the
    ACTIVE ``query.fused_kernels`` mode will serve (pallas or the XLA
    twin) — set_mode runs before warmup at server startup exactly so the
    warmed program is the serving program. ``mesh`` (True warms the mesh
    ``dist_*`` programs for the shape too — ``series`` then means rows PER
    SHARD; no-op on a single-device process). Returns
    ``{"programs": <new traces>, "ms": <wall>}``.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..ops import fusedgrid, fusedresident, gridfns, rangefns
    from .exec import _pad_steps, _pow2, _segment_partial
    t0 = time.perf_counter()
    before = plan_cache.traces
    # shard stores are device_put (COMMITTED) arrays; warm with the same
    # commitment or jax re-lowers/compiles the identical program at serve
    # time for the committed-argument signature
    dev = jax.devices()[0]
    for spec in shapes or ():
        fn = str(spec.get("fn", "rate"))
        op = str(spec.get("op", "sum"))
        R = _pow2(int(spec.get("series", 256)))
        C = int(spec.get("samples", 128))
        steps = int(spec.get("steps", 60))
        step_ms = int(spec.get("step_ms", 60_000))
        window = int(spec.get("window_ms", 300_000))
        iv = int(spec.get("interval_ms", 10_000))
        groups = int(spec.get("groups", 1))
        f64 = spec.get("dtype") == "float64"
        dtype = jnp.float64 if f64 else jnp.float32
        out_ts = (np.int64(window)
                  + np.arange(steps, dtype=np.int64) * step_ms)
        out_eval, T = _pad_steps(out_ts)
        val = jax.device_put(jnp.zeros((R, C), dtype), dev)
        n = jax.device_put(jnp.zeros(R, jnp.int32), dev)
        gids = np.zeros(R, np.int32)
        Gp = _pow2(groups)
        # general searchsorted path (off-grid shards, minority corrections)
        ts = jax.device_put(jnp.zeros((R, C), jnp.int64), dev)
        rangefns.periodic_samples(ts, val, n, out_eval, window, fn)
        fmode = fusedresident.mode()
        if spec.get("grid", True):
            # grid band-matmul path + the fused single-pass map phase when
            # the shape qualifies (the dashboard hot path)
            gridfns.periodic_samples_grid(val, n, out_eval, window, fn,
                                          0, iv)
            if (fmode != "off" and not f64
                    and fusedresident.scalar_shape_of(fn) is not None
                    and op in fusedgrid.FUSED_OPS
                    and fusedgrid.fusable(R, C, steps, groups)):
                # single-group warmups route gids through the same cached
                # device zeros the engine's fused path uses; the variant is
                # the ACTIVE mode's, so the warmed program is the serving one
                g_dev = (fusedgrid.zero_gids(R) if groups == 1
                         else np.zeros(R, np.int32))
                fusedgrid.fused_grid_aggregate(op, fn, val, n, g_dev,
                                               groups, out_ts, window, 0, iv,
                                               variant=fmode)
                res = str(spec.get("residency", "raw") or "raw")
                if res != "raw":
                    # narrow-streaming twin: zero blocks of the variant's
                    # dtype trace the same program the compressed store
                    # will serve through (kind rides the plan key)
                    from ..ops import decodereg
                    dvar = decodereg.variant(res)
                    blk = jax.device_put(
                        jnp.zeros((R, C), dvar.block_dtype), dev)
                    rows = tuple(jax.device_put(jnp.zeros(R, jnp.float32),
                                                dev)
                                 for _ in range(dvar.row_operands))
                    fusedgrid.fused_grid_aggregate(
                        op, fn, None, n, g_dev, groups, out_ts, window,
                        0, iv, narrow=(res, (blk,) + rows), variant=fmode)
        B = int(spec.get("buckets", 0) or 0)
        if spec.get("grid", True) and B and fmode != "off":
            # fused hist-resident quantile variant: serve-time shapes are
            # the engine's (out_eval steps, pow2 group bucket, dd dtype)
            Gp = _pow2(groups)
            if (fn in fusedresident.HIST_FUSED_FNS
                    and fusedresident.hist_fusable(R, C, len(out_eval), B,
                                                   Gp)):
                dd_dt = (jnp.int8 if spec.get("dd_dtype") == "int8"
                         else jnp.int16)
                dd = jax.device_put(jnp.zeros((R, C, B), dd_dt), dev)
                fd = jax.device_put(jnp.zeros((R, B), jnp.float32), dev)
                les = np.arange(1, B + 1, dtype=np.float64)
                les[-1] = np.inf
                fusedresident.fused_hist_quantile_resident(
                    0.9, les, dd, fd, n, np.zeros(R, np.int32), Gp,
                    out_eval, window, fn, 0, iv)
        # two-step reduce: PSM output is sliced back to the TRUE step count
        # before the segment partial, so warm the unpadded T
        _segment_partial(op, jnp.zeros((R, T), jnp.float64),
                         jnp.asarray(gids), Gp)
        if spec.get("mesh"):
            from ..parallel.distributed import warm_mesh_shape
            warm_mesh_shape(fn, op, R, C, steps, step_ms, window, iv,
                            groups, dtype, grid=bool(spec.get("grid", True)),
                            residency=str(spec.get("residency", "raw")
                                          or "raw"))
    return {"programs": plan_cache.traces - before,
            "ms": round((time.perf_counter() - t0) * 1000.0, 3)}
