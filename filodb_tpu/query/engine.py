"""QueryEngine facade: PromQL text -> LogicalPlan -> ExecPlan -> QueryResult.

Reference: coordinator/.../QueryActor.scala (processLogicalPlan2Query) +
queryengine2/QueryEngine.materialize — minus the actor layer: dispatch here is a
direct call. When a device mesh is configured, fusable aggregate plans route
through the shard_map/psum executor (parallel/distributed.py) the way the
reference's planner routes every query to per-shard dispatchers
(queryengine2/QueryEngine.scala:59-67,369); anything else falls back to the
in-process scatter-gather ExecPlan tree.
"""

from __future__ import annotations

import functools
import threading
import time

from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import NamedTuple, Sequence

import numpy as np

from ..core.memstore import TimeSeriesMemStore
from ..parallel import distributed
from ..parallel.shardmapper import ShardMapper
from ..utils.metrics import (FILODB_QUERY_LATENCY_MS,
                             FILODB_QUERY_NEGATIVE_CACHE_EVICTIONS,
                             FILODB_QUERY_NEGATIVE_CACHE_HITS,
                             FILODB_QUERY_RESULT_CACHE_EVICTIONS,
                             FILODB_QUERY_RESULT_CACHE_HITS,
                             FILODB_QUERY_RESULT_CACHE_INVALIDATIONS,
                             FILODB_QUERY_RESULT_CACHE_MISSES,
                             FILODB_QUERY_SLOW, registry)
from ..promql import parser as promql
from ..utils.diagnostics import (Dispatched, dispatching, lock_hold_ns,
                                 lock_wait_ns)
from ..utils.tracing import (SPAN_QUERY, SPAN_QUERY_ADMIT,
                             SPAN_QUERY_EXECUTE, SPAN_QUERY_FRAGMENT,
                             SPAN_QUERY_GROUPIDS, SPAN_QUERY_PARSE,
                             SPAN_QUERY_PLAN, SPAN_QUERY_SELECT, span,
                             tracer)
from . import logical as L
from .exec import (LeafFrame, QueryContext, SelectRawPartitionsExec,
                   check_sample_limit, count_groupids)
from .planner import QueryPlanner
from .rangevector import (QueryError, QueryResult, QueryStats,
                          RangeVectorKey, ResultMatrix)
from .scheduler import AdmissionController, AdmissionRejected

# aggregation operators whose partial state crosses the mesh collective
# (psum/pmin/pmax — ops/aggregators.py partial layout)
MESH_OPS = frozenset({"sum", "avg", "count", "group", "stddev", "stdvar",
                      "min", "max"})
# order statistics lowered onto the mesh: topk/bottomk gather fixed-size
# candidate blocks (parallel/distributed.dist_topk), quantile psums sketch
# counts. count_values stays on the host merge: its partial state is keyed
# by rendered value STRINGS — there is no fixed-size device layout to
# gather, and only [distinct values] rows cross shards anyway.
MESH_ORDER_OPS = frozenset({"topk", "bottomk", "quantile"})
# device-side per-group loops in dist_topk compile per group: cap G like the
# in-process order-stat map does (exec.AggregateMapReduce.ORDER_STAT_MAX_GROUPS)
MESH_TOPK_MAX_GROUPS = 16
# rows outside the selection: a group id no kernel's one-hot/segment scatter
# ever matches (OOB scatter updates drop; one-hot comparisons never equal it)
_EXCLUDED_GID = 1 << 30


class _Dispatch(NamedTuple):
    """What a device route's leaf (fused-hist, mesh) leaves its lock(s)
    with, for the fetch outside them."""
    route: str                  # the exec path; the mesh's program name
    group_keys: Sequence[RangeVectorKey]
    result: Dispatched | None   # None: an empty selection, nothing dispatched
    epochs: Sequence[int] = ()  # mesh: the shards' release epochs before it


def _walk_plans(plan):
    """Yield every node of an ExecPlan tree (children/lhs/rhs/inner/members
    links)."""
    stack = [plan]
    while stack:
        p = stack.pop()
        yield p
        for attr in ("children", "lhs", "rhs", "inner", "child", "members"):
            v = getattr(p, attr, None)
            if isinstance(v, list):
                stack.extend(v)
            elif v is not None and hasattr(v, "transformers"):
                stack.append(v)
    return


def _sel_quote(v: str) -> str:
    """PromQL double-quoted string: backslashes and quotes escape, so label
    values containing either round-trip through the peer's parser instead of
    silently failing the whole fan-out."""
    return '"' + v.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _filters_to_selector(filters) -> str:
    """Render column filters back into a PromQL selector string for peer
    metadata fan-out (the inverse of http/api._selector_to_filters)."""
    import re as _re

    from ..core import filters as F
    parts = []
    for f in filters:
        label = "__name__" if f.label == "_metric_" else f.label
        if isinstance(f, F.Equals):
            parts.append(f'{label}={_sel_quote(f.value)}')
        elif isinstance(f, F.NotEquals):
            parts.append(f'{label}!={_sel_quote(f.value)}')
        elif isinstance(f, F.EqualsRegex):
            parts.append(f'{label}=~{_sel_quote(f.pattern)}')
        elif isinstance(f, F.NotEqualsRegex):
            parts.append(f'{label}!~{_sel_quote(f.pattern)}')
        elif isinstance(f, F.In):
            # literal alternation: each member regex-escaped (an In value
            # like "1.5" must not match "125")
            alt = "|".join(_re.escape(v) for v in f.values)
            parts.append(f'{label}=~{_sel_quote(alt)}')
    return "{" + ",".join(parts) + "}"


@dataclass
class QueryConfig:
    """Ref: query/.../QueryConfig.scala (stale-sample-after, sample limits)."""
    stale_sample_after_ms: int = 5 * 60 * 1000
    sample_limit: int = 1_000_000
    # queries at or over this wall duration enter the slow-query ring
    # (served at /api/v1/debug/slow_queries); None disables the log
    slow_log_threshold_ms: float | None = 1000.0
    # step-aligned result cache entries per engine (0 disables — the library
    # default; FiloServer turns it on via query.result_cache_size)
    result_cache_size: int = 0
    # aggregate estimated cost admitted to execute concurrently
    # (query.max_concurrent_cost); None leaves the global budget unbounded
    # — admission still runs when tenant_quotas is set, and is fully off
    # only when both are unset
    max_concurrent_cost: float | None = None
    # tenant -> max concurrent cost (query.tenant_quotas); admission only
    tenant_quotas: dict = field(default_factory=dict)
    # Retry-After hint on an admission shed (query.shed_retry_after)
    shed_retry_after_s: float = 1.0
    # TTL+size-bounded NEGATIVE result cache for provably-empty selections
    # (query.negative_cache_size / query.negative_cache_ttl; 0 disables —
    # the library default; FiloServer turns it on from config)
    negative_cache_size: int = 0
    negative_cache_ttl_s: float = 30.0
    # incremental serving: per-step fragment cache entries per engine
    # (query.fragment_cache_size; 0 disables — the library default), with a
    # total byte bound (query.fragment_cache_*)
    fragment_cache_size: int = 0
    fragment_cache_bytes: int = 64 << 20


class QueryResultCache:
    """Step-aligned range-result cache, invalidated by ingest watermark
    (ref: the reference's repeated-dashboard serving posture — QueryEngine2
    materializes once, serves many).

    Entries are keyed on ``(promql, start, end, step, tenant)`` and record
    the cluster EPOCH VECTOR — every participating shard's ``data_epoch``
    mutation counter, local shards read directly and peer shards probed
    over ``/api/v1/epochs`` — captured BEFORE the query executed. A hit
    requires the current vector to EQUAL the recorded one, so any ingest,
    purge, eviction, compaction, or topology change since makes the entry
    unreachable (counted as an invalidation): a served hit is provably
    identical to re-execution, because the data it would re-read cannot
    have changed. Capacity-bounded LRU (query.result_cache_size) with an
    evictions metric — filolint's bounded-cache rule enforces both for
    every cache class in the package."""

    def __init__(self, capacity: int = 256, tags: dict | None = None):
        self.capacity = max(1, int(capacity))
        # per-cache metric identity (e.g. {"dataset": ...}): untagged,
        # every engine's cache would share one process-global counter set
        # and stats() would report the sum as if it were this cache's
        self.tags = dict(tags or {})
        # key -> (epoch vector, payload) where payload =
        # (matrix, result_type, warnings, stats_dict, exec_path)
        self._entries: OrderedDict[tuple, tuple] = OrderedDict()
        self._lock = threading.Lock()
        self._hits = registry.counter(FILODB_QUERY_RESULT_CACHE_HITS,
                                      self.tags)
        self._misses = registry.counter(FILODB_QUERY_RESULT_CACHE_MISSES,
                                        self.tags)
        self._evictions = registry.counter(
            FILODB_QUERY_RESULT_CACHE_EVICTIONS, self.tags)
        self._invalidations = registry.counter(
            FILODB_QUERY_RESULT_CACHE_INVALIDATIONS, self.tags)

    def get(self, key: tuple, current_epochs):
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self._misses.increment()
                return None
            epochs, payload = e
            if current_epochs is None:
                # unverifiable vector (a peer probe failed): never serve
                # what cannot be proven, but an unreadable watermark is not
                # evidence the data changed — keep the entry for when the
                # peer answers again
                self._misses.increment()
                return None
            if epochs != current_epochs:
                # the watermark moved: serving the entry could diverge
                # from re-execution — drop it
                del self._entries[key]
                self._invalidations.increment()
                self._misses.increment()
                return None
            self._entries.move_to_end(key)
            self._hits.increment()
            return payload

    def put(self, key: tuple, payload, epochs) -> None:
        if epochs is None:
            return                      # unverifiable vector: never cache
        with self._lock:
            self._entries[key] = (epochs, payload)
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self._evictions.increment()

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {"size": len(self._entries), "capacity": self.capacity,
                    "hits": self._hits.value, "misses": self._misses.value,
                    "evictions": self._evictions.value,
                    "invalidations": self._invalidations.value}


class NegativeResultCache:
    """TTL- and size-bounded cache of query texts whose selection came back
    EMPTY (0 series): a typo'd metric name on a dashboard refresh loop stops
    costing a full parse+plan+execute per tick (ROADMAP item 1 leftover).

    Unlike QueryResultCache this is deliberately NOT watermark-validated:
    an empty selection usually stays empty (the metric does not exist), and
    the TTL bounds how long a newly-appearing series can be masked — the
    documented freshness trade of negative caching. Keys are
    ``(promql, tenant)`` only, so a sliding dashboard window keeps hitting —
    but emptiness is only PROVEN for the executed time range (leaf
    selection is time-bounded: an existing series queried over a pre-ingest
    range matches zero series THERE, not everywhere). Each entry therefore
    records its proven ``[start, end]``, and a hit requires the requested
    range to stay inside it, extended forward by the wall time elapsed
    since the proof — exactly the window the TTL trade already concedes to
    newly-appearing data, enough for a sliding dashboard to keep hitting,
    while a query over a DIFFERENT (e.g. live vs historical) range misses
    and re-executes. Capacity-bounded LRU with TTL expiry, both counted as
    evictions (filolint's bounded-cache contract: visible bound + eviction
    accounting)."""

    def __init__(self, capacity: int = 256, ttl_s: float = 30.0,
                 tags: dict | None = None):
        self.capacity = max(1, int(capacity))
        self.ttl_s = float(ttl_s)
        self.tags = dict(tags or {})
        # key -> (expiry, proven start ms, proven end ms, proof monotonic s)
        self._entries: OrderedDict[tuple, tuple] = OrderedDict()
        self._lock = threading.Lock()
        self._hits = registry.counter(FILODB_QUERY_NEGATIVE_CACHE_HITS,
                                      self.tags)
        self._evictions = registry.counter(
            FILODB_QUERY_NEGATIVE_CACHE_EVICTIONS, self.tags)

    def hit(self, key: tuple, range_key: tuple,
            now: float | None = None) -> bool:
        """True when a recent execution proved this query empty over a
        range covering the requested ``(start, end, step)`` (see class
        docstring for the forward-extension rule; expired entries evict
        here). A non-covering range is a miss but keeps the entry — the
        proof still stands for ITS range."""
        now = time.monotonic() if now is None else now
        start, end, step = range_key
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                return False
            exp, p_start, p_end, t_proof = ent
            if now >= exp:
                del self._entries[key]
                self._evictions.increment()
                return False
            # the proven-empty range, slid forward by elapsed wall time
            # (+ one step of grid slack): the only unproven data a hit can
            # mask is data newer than the proof — the documented TTL trade
            if start < p_start \
                    or end > p_end + (now - t_proof) * 1000.0 + step:
                return False
            self._entries.move_to_end(key)
            self._hits.increment()
            return True

    def put(self, key: tuple, range_key: tuple,
            now: float | None = None) -> None:
        now = time.monotonic() if now is None else now
        start, end, _step = range_key
        with self._lock:
            self._entries[key] = (now + self.ttl_s, start, end, now)
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self._evictions.increment()

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {"size": len(self._entries), "capacity": self.capacity,
                    "ttl_s": self.ttl_s, "hits": self._hits.value,
                    "evictions": self._evictions.value}


class SlowQueryLog:
    """Bounded ring of slow-query records: promql text, duration, plan
    summary (the engine's exec path), per-query stats, and the trace id —
    the pivot from "this dashboard is slow" to the exact trace
    (/api/v1/debug/traces?trace_id=...). One process-global ring, like the
    tracer and the metrics registry."""

    def __init__(self, capacity: int = 128):
        self._ring: deque[dict] = deque(maxlen=capacity)
        self._lock = threading.Lock()

    def record(self, entry: dict) -> None:
        with self._lock:
            self._ring.append(entry)

    def entries(self, limit: int | None = None) -> list[dict]:
        """Newest first."""
        with self._lock:
            out = list(self._ring)
        out.reverse()
        return out[:limit] if limit else out

    def resize(self, capacity: int) -> None:
        with self._lock:
            self._ring = deque(self._ring, maxlen=max(1, int(capacity)))

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()


slow_query_log = SlowQueryLog()


class QueryEngine:
    def __init__(self, memstore: TimeSeriesMemStore, dataset: str,
                 shard_mapper: ShardMapper | None = None,
                 config: QueryConfig | None = None, mesh=None,
                 cluster=None, node: str | None = None,
                 endpoint_resolver=None, route_dataset: str | None = None):
        """``cluster``/``node``: the ShardManager's shard->node view and this
        node's name — leaves for peer-owned shards dispatch remotely
        (query/wire.py RemoteLeafExec; ref: PlanDispatcher.scala).
        ``endpoint_resolver(node) -> "host:port" | None`` maps a node name to
        its HTTP endpoint (registrar-published); None falls back to treating
        the node name itself as host:port."""
        self.memstore = memstore
        self.dataset = dataset
        num_shards = max(len(memstore.shards_of(dataset)), 1)
        pow2 = 1
        while pow2 < num_shards:
            pow2 *= 2
        self.mapper = shard_mapper or ShardMapper(pow2)
        # fresh per engine: a shared default instance would let one
        # engine's tuning (slow-log threshold, sample limit) leak into
        # every other engine constructed without an explicit config
        self.config = config if config is not None else QueryConfig()
        # jax.sharding.Mesh with one device per shard: aggregate queries
        # execute via shard_map + psum instead of the host scatter-gather
        self.mesh = mesh
        # what a mesh leaf needs ready as it takes every shard's lock
        self._mesh_memo = (distributed.MeshLeafMemo(mesh)
                           if mesh is not None else None)
        self.cluster = cluster
        self.node = node
        self.endpoint_resolver = endpoint_resolver
        # dataset name used for shard->node routing: a downsample-family
        # serving engine ("ds:ds_1m") routes by its RAW dataset's assignment
        self.route_dataset = route_dataset or dataset
        # serving fast path: step-aligned result cache + cost-based
        # admission (both off unless configured — QueryConfig defaults)
        self.result_cache = (QueryResultCache(self.config.result_cache_size,
                                              tags={"dataset": dataset})
                             if self.config.result_cache_size else None)
        self.admission = (AdmissionController(
            self.config.max_concurrent_cost, self.config.tenant_quotas,
            self.config.shed_retry_after_s, tags={"dataset": dataset})
            if (self.config.max_concurrent_cost is not None
                or self.config.tenant_quotas) else None)
        # TTL-bounded negative cache: empty selections short-circuit before
        # parse/plan/execute (typo'd dashboards; see NegativeResultCache)
        self.negative_cache = (NegativeResultCache(
            self.config.negative_cache_size,
            self.config.negative_cache_ttl_s, tags={"dataset": dataset})
            if self.config.negative_cache_size else None)
        # incremental serving: per-step fragment cache — a shifted dashboard
        # window extends its cached fragment (only new tail steps execute)
        # instead of recomputing the whole range (query/incremental.py)
        if self.config.fragment_cache_size:
            from .incremental import FragmentCache
            self.fragment_cache = FragmentCache(
                self.config.fragment_cache_size,
                self.config.fragment_cache_bytes, tags={"dataset": dataset})
        else:
            self.fragment_cache = None
        # a failed peer epoch probe arms this cooldown: until it passes,
        # _epoch_vector returns None without scattering (caching fail-opens
        # to miss), so a blackholed peer stalls at most one query per
        # cooldown window instead of every query
        self._epoch_probe_cooldown_s = 10.0
        self._epoch_probe_down_until = 0.0
        # downsample-aware routing (query/retention.py RetentionRouter),
        # installed by FiloServer on the RAW engine when retention.routing
        # is on; family serving engines never carry one (no re-routing)
        self.retention = None
        schema = memstore._dataset_schema.get(dataset)
        opts = schema.options if schema else None
        route = self._route_endpoint if cluster is not None else None
        kw = dict(route_fn=route, dataset=dataset)
        self.planner = (QueryPlanner(self.mapper, opts, **kw) if opts
                        else QueryPlanner(self.mapper, **kw))

    def _route_endpoint(self, shard: int) -> str | None:
        """HTTP endpoint of the peer owning ``shard``, or None when this node
        serves it locally (ref: queryengine2/QueryEngine.scala:506 —
        co-locate each leaf with its shard's node)."""
        if self.cluster is None or self.node is None:
            return None
        try:
            owner = self.cluster.node_of(self.route_dataset, shard)
        except KeyError:
            return None
        if owner is None or owner == self.node:
            return None
        if self.endpoint_resolver is not None:
            ep = self.endpoint_resolver(owner)
            if ep:
                return ep
        return owner

    def _ctx(self) -> QueryContext:
        return QueryContext(self.memstore, self.dataset,
                            sample_limit=self.config.sample_limit,
                            stale_ms=self.config.stale_sample_after_ms)

    def _set_path(self, ctx: QueryContext | None, path: str) -> None:
        """Record the exec route taken per-query (what the slow log and
        QueryResult.exec_path report — the engine-shared last_exec_path
        attribute this replaced was racy under concurrent queries)."""
        if ctx is not None:
            ctx.exec_path = path

    def _note_fused(self, ctx: QueryContext, base: str) -> None:
        """Name the fused-tier programs a local plan's leaves ran in the
        exec path (see QueryContext.kernels); a plan that ran none keeps
        the bare ``base``, or reads ``<base>-gather`` where all its leaves
        gathered narrow selections (QueryContext.leaf_routes)."""
        if not ctx.kernels:
            if ctx.leaf_routes == {"gather"}:
                # every local leaf gathered a narrow selection's rows and
                # the general kernels answered: told apart from a wide
                # selection's composed path, which stays the bare ``base``
                self._set_path(ctx, f"{base}-gather")
            return
        kinds = sorted({k for k, _ in ctx.kernels} - {"raw"})
        tags = sorted({t for _, t in ctx.kernels})
        self._set_path(ctx, f"{base}-fused{'-narrow' if kinds else ''}"
                            f"[{','.join(kinds + tags)}]")

    def query_range(self, promql_text: str, start_ms: int, end_ms: int,
                    step_ms: int, tenant: str | None = None,
                    resolution: str | None = None,
                    _skip_routing: bool = False,
                    min_window_ms: int | None = None) -> QueryResult:
        """``resolution`` (&resolution= / filo-cli --resolution) overrides
        the retention router's decision for the whole range; it requires
        routing to be configured (unknown values fail with the available
        list). ``_skip_routing`` is the router's own raw-tail leg.
        ``min_window_ms`` (the retention router's serving-resolution floor)
        auto-widens windowed functions narrower than the downsample family's
        resolution — without it they silently return empty/wrong data."""
        if self.retention is not None and not _skip_routing:
            routed = self.retention.route_range(
                self, promql_text, int(start_ms), int(end_ms), int(step_ms),
                tenant, resolution)
            if routed is not None:
                return routed
        elif resolution is not None and not _skip_routing:
            raise QueryError(
                "resolution override requires retention routing "
                "(retention.routing + downsample.enabled); none configured")
        res = self._query_traced(
            promql_text,
            lambda: promql.query_to_logical_plan(promql_text, start_ms,
                                                 end_ms, step_ms),
            range_key=(int(start_ms), int(end_ms), int(step_ms)),
            tenant=tenant, min_window_ms=min_window_ms)
        if self.retention is not None and res.stats is not None \
                and res.stats.resolution is None:
            res.stats.resolution = "raw"   # routing ran and chose raw
        return res

    def query_instant(self, promql_text: str, time_ms: int,
                      tenant: str | None = None,
                      resolution: str | None = None,
                      min_window_ms: int | None = None) -> QueryResult:
        if self.retention is not None:
            routed = self.retention.route_instant(self, promql_text,
                                                  int(time_ms), tenant,
                                                  resolution)
            if routed is not None:
                routed.result_type = "vector"
                return routed
        elif resolution is not None:
            raise QueryError(
                "resolution override requires retention routing "
                "(retention.routing + downsample.enabled); none configured")
        res = self._query_traced(
            promql_text,
            lambda: promql.query_to_logical_plan(promql_text, time_ms,
                                                 time_ms, 1),
            tenant=tenant, min_window_ms=min_window_ms,
            instant_ms=int(time_ms))
        res.result_type = "vector"
        return res

    def _query_traced(self, promql_text: str, to_plan,
                      range_key: tuple | None = None,
                      tenant: str | None = None,
                      min_window_ms: int | None = None,
                      instant_ms: int | None = None) -> QueryResult:
        """Shared query entry: ONE ``query`` span per query (every stage and
        every participating node's spans hang off its trace id; it carries
        the range asked for, so a device event can be tied to its query,
        and on close the route taken and the outcome), the
        end-to-end latency histogram (exemplar-tagged with that trace id),
        and the slow-query ring. Accounting runs in a FINALLY: the 30s
        query that then raises is exactly the one an operator opens the
        slow-query log to find, and tail latency must not under-report
        during incidents.

        Serving fast path, in order: (1) the result cache answers a
        repeated range query without parsing or executing when its ingest
        watermark vector still matches; (2) the fragment cache serves a
        SHIFTED range incrementally — the provably-valid overlap from
        cached per-step columns, only the head/tail delta executed; (3)
        cost-based admission sheds what the budget cannot afford BEFORE
        it executes; (4) execution populates both caches with the
        PRE-execution watermark vector, so a concurrent ingest
        invalidates the affected steps rather than racing them."""
        ctx = self._ctx()
        t0 = time.perf_counter_ns()
        err: BaseException | None = None
        waited, held = lock_wait_ns(), lock_hold_ns()
        start_ms, end_ms, step_ms = range_key or (instant_ms, instant_ms, 0)
        with span(SPAN_QUERY, dataset=self.dataset, promql=promql_text[:200],
                  start_ms=start_ms, end_ms=end_ms, step_ms=step_ms,
                  tenant=tenant or "") as qtags:
            tctx = tracer.current_context()
            try:
                neg_key = None
                if range_key is not None and self.negative_cache is not None:
                    # probed FIRST: a negative hit needs no epoch scatter,
                    # no parse, no plan — the typo'd-dashboard fast exit
                    neg_key = (promql_text, tenant)
                    if self.negative_cache.hit(neg_key, range_key):
                        return self._negative_hit(range_key, ctx)
                cache_key = epochs = elogs = frag_key = None
                frag = (self.fragment_cache if range_key is not None
                        else None)
                if range_key is not None and (self.result_cache is not None
                                              or frag is not None):
                    epochs, elogs = self._epoch_state(
                        with_logs=frag is not None)
                if range_key is not None and self.result_cache is not None:
                    # min_window rides every cache key: the router's widened
                    # plan and a direct family query share promql text but
                    # not semantics
                    cache_key = (promql_text, *range_key, tenant,
                                 min_window_ms)
                    hit = self._result_cache_probe(cache_key, epochs, ctx)
                    if hit is not None:
                        return hit
                if frag is not None and epochs is not None:
                    frag_key = (promql_text, range_key[2], tenant,
                                min_window_ms)
                    served = self._fragment_serve(
                        frag_key, promql_text, range_key, tenant,
                        min_window_ms, epochs, elogs, ctx)
                    if served is not None:
                        if cache_key is not None:
                            self.result_cache.put(
                                cache_key,
                                (served.matrix, served.result_type,
                                 list(served.warnings), ctx.stats.to_dict(),
                                 ctx.exec_path), epochs)
                        return served
                with span(SPAN_QUERY_PARSE), ctx.stats.stage("parse"):
                    plan = to_plan()
                plan, widen_warn = self._widen_plan(plan, min_window_ms, ctx)
                res = self._exec_admitted(plan, ctx, tenant)
                if widen_warn is not None and widen_warn not in res.warnings:
                    res.warnings.append(widen_warn)
                if cache_key is not None:
                    self.result_cache.put(
                        cache_key,
                        (res.matrix, res.result_type, list(res.warnings),
                         ctx.stats.to_dict(), ctx.exec_path), epochs)
                if frag_key is not None:
                    self._fragment_store(frag_key, plan, res, range_key,
                                         epochs)
                if (neg_key is not None and ctx.stats.series_matched == 0
                        and res.matrix.num_series == 0
                        and ctx.stats.recovering_shards == 0
                        and not self._any_recovering()):
                    # the SELECTION was provably empty cluster-wide (peer
                    # legs merge their series_matched into ctx.stats): the
                    # next refresh skips the whole pipeline until the TTL
                    # admits newly-appearing series. An empty seen while
                    # ANY shard is still RECOVERING proves nothing — local
                    # shards via the flag, peer shards via the
                    # recovering_shards stat riding the /exec wire — the
                    # series may simply not have loaded yet, and a cached
                    # empty would mask them for the whole TTL
                    self.negative_cache.put(neg_key, range_key)
                return res
            except BaseException as e:
                err = e                 # noted below, then re-raised
                raise
            finally:
                qtags["exec_path"] = ctx.exec_path
                qtags["status"] = ("ok" if err is None
                                   else type(err).__name__)
                # every wait of this thread for a shard lock, and every
                # hold it released: the leaf's (its own tags) and the epoch
                # probe's before it
                qtags["lock_wait_ms"] = (lock_wait_ns() - waited) / 1e6
                qtags["lock_hold_ms"] = (lock_hold_ns() - held) / 1e6
                self._note_query_done(promql_text, ctx,
                                      (time.perf_counter_ns() - t0) / 1e6,
                                      tctx, err)

    def _negative_hit(self, range_key: tuple,
                      ctx: QueryContext) -> QueryResult:
        """The synthesized empty result for a negative-cache hit: the step
        grid of THIS request (the key ignores the sliding window — empty is
        range-invariant while the entry lives), zero series."""
        start, end, step = range_key
        out_ts = np.arange(start, end + 1, max(step, 1), dtype=np.int64)
        ctx.stats.add("negative_cache_hits")
        self._set_path(ctx, "negative-cache")
        res = QueryResult(ResultMatrix(out_ts, np.zeros((0, len(out_ts))),
                                       []))
        res.stats = ctx.stats
        res.exec_path = ctx.exec_path
        return res

    def _result_cache_probe(self, cache_key: tuple, epochs,
                            ctx: QueryContext) -> QueryResult | None:
        """A validated cache entry as a fresh QueryResult, else None. The
        response carries the ORIGINAL execution's stats (they describe the
        work that produced these bytes) plus a result_cache_hits marker."""
        payload = self.result_cache.get(cache_key, epochs)
        if payload is None:
            return None
        matrix, result_type, warnings, stats_dict, exec_path = payload
        ctx.stats.merge(stats_dict)
        ctx.stats.add("result_cache_hits")
        self._set_path(ctx, f"result-cache[{exec_path}]")
        res = QueryResult(matrix, result_type, list(warnings))
        res.stats = ctx.stats
        res.exec_path = ctx.exec_path
        return res

    def _widen_plan(self, plan: L.LogicalPlan, min_window_ms: int | None,
                    ctx: QueryContext):
        """Auto-widen windowed functions narrower than the serving
        resolution (retention-routed family queries only — min_window_ms
        is the family's resolution): a window that cannot cover one
        downsample bucket silently returns empty/wrong data. Returns
        ``(plan, warning | None)``; the count lands in QueryStats and the
        per-dataset metric."""
        if not min_window_ms:
            return plan, None
        from ..utils.metrics import FILODB_QUERY_WINDOWS_WIDENED
        from .retention import resolution_label, widen_windows
        plan, n = widen_windows(plan, int(min_window_ms))
        if not n:
            return plan, None
        label = resolution_label(int(min_window_ms))
        ctx.stats.add("windows_widened", n)
        registry.counter(FILODB_QUERY_WINDOWS_WIDENED,
                         {"dataset": self.dataset,
                          "resolution": label}).increment(n)
        return plan, (f"{n} window(s) narrower than the {label} serving "
                      "resolution were widened to cover it")

    def _build_range_plan(self, promql_text: str, start_ms: int, end_ms: int,
                          step_ms: int, min_window_ms: int | None,
                          ctx: QueryContext):
        """Parse + widen one (sub-)range — the fragment path's delta legs
        build their head/tail plans through the same pipeline as the full
        execution, so extension is bit-identical by construction."""
        with span(SPAN_QUERY_PARSE), ctx.stats.stage("parse"):
            plan = promql.query_to_logical_plan(promql_text, start_ms,
                                                end_ms, step_ms)
        return self._widen_plan(plan, min_window_ms, ctx)

    def _fragment_serve(self, frag_key: tuple, promql_text: str,
                        range_key: tuple, tenant: str | None,
                        min_window_ms: int | None, epochs, elogs,
                        ctx: QueryContext) -> QueryResult | None:
        """Incremental (delta) evaluation off the fragment cache: reuse the
        entry's provably-valid per-step columns, execute ONLY the missing
        head/tail sub-ranges, stitch, and store the merged fragment back
        (recorded against the PRE-execution epoch vector — a concurrent
        ingest invalidates the affected steps on the next probe instead of
        racing this one). None => no usable fragment; caller executes the
        full range."""
        start, end, step = range_key
        hit = self.fragment_cache.probe(frag_key, start, end, step,
                                        epochs, elogs)
        if hit is None:
            return None
        from ..parallel.cluster import stitch_matrices
        with span(SPAN_QUERY_FRAGMENT, dataset=self.dataset,
                  reused=hit.reused_steps) as tags:
            parts = [ResultMatrix(hit.keep_ts, hit.keep_vals, hit.keys)]
            warnings = list(hit.warnings)
            n_new = 0
            for lo, hi in hit.missing:
                plan, widen_warn = self._build_range_plan(
                    promql_text, lo, hi, step, min_window_ms, ctx)
                sub = self._exec_admitted(plan, ctx, tenant)
                # dedup against the entry's recorded warnings: the SAME
                # widen warning re-arises on every extension and would
                # otherwise accumulate one copy per refresh in the stored
                # fragment (and in every response)
                if widen_warn is not None and widen_warn not in warnings:
                    warnings.append(widen_warn)
                for w in sub.warnings:
                    if w not in warnings:
                        warnings.append(w)
                m = sub.matrix.to_host()
                parts.append(ResultMatrix(
                    np.asarray(m.out_ts, np.int64),
                    np.asarray(m.values, np.float64), list(m.keys)))
                n_new += len(m.out_ts)
            tags["computed"] = n_new
            merged = stitch_matrices(parts) if len(parts) > 1 else parts[0]
            m_ts = np.asarray(merged.out_ts)
            mask = (m_ts >= start) & (m_ts <= end)
            served_m = ResultMatrix(m_ts[mask],
                                    np.asarray(merged.values)[:, mask],
                                    list(merged.keys))
            check_sample_limit(served_m.num_series, len(served_m.out_ts),
                               self.config.sample_limit)
            ctx.stats.add("fragment_steps_reused", hit.reused_steps)
            self._set_path(
                ctx,
                f"incremental[reused={hit.reused_steps},computed={n_new}]"
                if hit.missing else "fragment-cache[full]")
            # merged fragment replaces the entry: the evicted head trims via
            # the cache's per-entry step bound, the new tail extends it
            self.fragment_cache.store(
                frag_key, merged.out_ts, np.asarray(merged.values),
                merged.keys, warnings, epochs, step,
                extended=bool(hit.missing) and hit.reused_steps > 0)
        res = QueryResult(served_m, "matrix", warnings)
        res.stats = ctx.stats
        res.exec_path = ctx.exec_path
        return res

    def _fragment_store(self, frag_key: tuple, plan: L.LogicalPlan,
                        res: QueryResult, range_key: tuple, epochs) -> None:
        """Seed the fragment cache from a full execution — only plans whose
        steps are provably time-local (query/incremental.plan_cacheable)
        and scalar-columnar results qualify."""
        from .incremental import plan_cacheable
        if res.result_type != "matrix" or res.matrix.bucket_les is not None:
            return
        if not plan_cacheable(plan):
            return
        host = res.matrix.to_host()
        vals = np.asarray(host.values)
        if vals.ndim != 2:
            return
        if vals.shape[0] > len(host.keys):
            vals = vals[:len(host.keys)]   # padded leaf rows carry no series
        elif vals.shape[0] < len(host.keys):
            return
        self.fragment_cache.store(frag_key,
                                  np.asarray(host.out_ts, np.int64),
                                  np.asarray(vals, np.float64),
                                  list(host.keys), res.warnings, epochs,
                                  range_key[2])

    def _exec_admitted(self, plan: L.LogicalPlan, ctx: QueryContext,
                       tenant: str | None) -> QueryResult:
        """Execute under the admission gate when one is configured: the
        decision (cost estimate + reserve) runs under its own span; a shed
        raises AdmissionRejected (HTTP 503 + Retry-After) and lands in
        QueryStats and the slow-query ring before anything executes. A
        structurally-oversized cost (could never fit the budget/quota)
        raises plain QueryError instead — a 422 client error, not load."""
        if self.admission is None:
            return self.exec_logical(plan, ctx)
        with span(SPAN_QUERY_ADMIT, tenant=tenant or "") as tags:
            cost = self.estimate_cost(plan)
            tags["cost"] = round(cost, 1)
            try:
                got = self.admission.acquire(cost, tenant)
            except AdmissionRejected:
                tags["shed"] = True
                ctx.stats.add("admission_shed")
                raise
        try:
            return self.exec_logical(plan, ctx)
        finally:
            self.admission.release(got, tenant)

    def estimate_cost(self, plan: L.LogicalPlan) -> float:
        """Admission-control cost estimate: the planner walks the logical
        tree; this engine supplies the index probe (local series counts,
        scaled up by the owned-shard fraction when peers hold shards —
        the admission path must not pay a cluster round-trip)."""
        def series_of(filters, from_ms, to_ms):
            total = narrow = 0
            shards = self.memstore.shards_of(self.dataset)
            for sh in shards:
                with sh.lock:
                    pids = sh.part_ids_from_filters(list(filters), from_ms,
                                                    to_ms)
                total += len(pids)
                if sh.store is not None \
                        and (getattr(sh.store, "_narrow", None) is not None
                             or getattr(sh.store, "_nhist", None)
                             is not None):
                    # compressed residency (scalar i16 OR hist 2D-delta)
                    # halves the streamed bytes — and the fused-resident
                    # tier reads it in place, so cost discounts both
                    narrow += len(pids)
            if shards and self._has_remote_shards():
                scale = len(self.mapper.all_shards()) / len(shards)
                total, narrow = total * scale, narrow * scale
            return total, (narrow / total if total else 0.0)

        return self.planner.estimate_cost(
            plan, series_of, self.config.stale_sample_after_ms)

    def _any_recovering(self) -> bool:
        """True while any LOCAL shard is mid-recovery (partial data)."""
        return any(getattr(sh, "recovering", False)
                   for sh in self.memstore.shards_of(self.dataset))

    def _epoch_vector(self) -> tuple | None:
        """The cluster ingest-watermark vector (see :meth:`_epoch_state`)."""
        return self._epoch_state()[0]

    def _epoch_state(self, with_logs: bool = False):
        """``(vector, logs)`` of the cluster ingest-watermark state for this
        dataset: the vector is every shard's data_epoch mutation counter —
        local shards read directly, peer-owned topologies probed over
        /api/v1/epochs (one concurrent scatter; a hit served off a matching
        vector is provably identical to re-execution). With ``with_logs``
        each shard's recent (epoch, min affected ts) bump log rides along
        (``?log=1`` on the peer probe) — the substrate of PER-STEP fragment
        validity (query/incremental.stable_before). ``(None, None)`` when
        any peer is unreachable — callers then treat the lookup as a miss
        and skip caching — and a failure arms a cooldown during which the
        scatter is skipped entirely."""
        vec = []
        logs: dict = {}
        for sh in self.memstore.shards_of(self.dataset):
            if with_logs:
                ep, lg = sh.epoch_state()
                logs[("local", str(sh.shard_num))] = lg
            else:
                ep = sh.data_epoch
            vec.append(("local", sh.shard_num, ep))
        if self._has_remote_shards():
            if time.monotonic() < self._epoch_probe_down_until:
                return None, None
            import json as _json
            import urllib.request
            sfx = "&log=1" if with_logs else ""

            def fetch(ep: str) -> dict:
                url = (f"http://{ep}/promql/{self.dataset}/api/v1/epochs"
                       f"?local=1{sfx}")
                with urllib.request.urlopen(url, timeout=2.0) as r:
                    return _json.load(r).get("data") or {}

            for ep, res in self.peer_scatter_join(
                    self.peer_scatter_begin(fetch)):
                if isinstance(res, Exception):
                    self._epoch_probe_down_until = (
                        time.monotonic() + self._epoch_probe_cooldown_s)
                    return None, None
                for k, v in sorted(res.items()):
                    if isinstance(v, (list, tuple)):
                        # log form: [epoch, [[epoch_i, min_ts_i], ...]]
                        vec.append((ep, str(k), int(v[0])))
                        logs[(ep, str(k))] = [(int(a), int(b))
                                              for a, b in v[1]]
                    else:
                        vec.append((ep, str(k), int(v)))
        return tuple(sorted(vec, key=str)), logs

    def _note_query_done(self, promql_text: str, ctx: QueryContext,
                         dur_ms: float, tctx: dict | None,
                         error: BaseException | None) -> None:
        # only SAMPLED traces are recorded: an exemplar/slow-log entry
        # pointing at a sampled-out trace id would dead-end at
        # /api/v1/debug/traces
        trace_id = (tctx.get("trace_id")
                    if tctx and tctx.get("sampled") else None)
        registry.histogram(FILODB_QUERY_LATENCY_MS,
                           {"dataset": self.dataset}) \
            .record(dur_ms, trace_id=trace_id)
        thr = self.config.slow_log_threshold_ms
        shed = isinstance(error, AdmissionRejected)
        slow = thr is not None and dur_ms >= thr
        if slow and not shed:
            registry.counter(FILODB_QUERY_SLOW,
                             {"dataset": self.dataset}).increment()
        if slow or shed:
            # admission sheds enter the ring regardless of duration: the
            # operator diagnosing 503s needs the shed queries' text, cost
            # and tenant in the same place as the slow ones
            entry = {
                "promql": promql_text, "dataset": self.dataset,
                "duration_ms": round(dur_ms, 3),
                "plan": ctx.exec_path, "trace_id": trace_id,
                "stats": ctx.stats.to_dict(),
                # wall timestamp for operator display only — durations above
                # all come from the monotonic clock
                "ts": time.time(),
            }
            if shed:
                entry["shed"] = True
                entry["cost"] = round(error.cost, 1)
                if error.tenant is not None:
                    entry["tenant"] = error.tenant
            if error is not None:
                entry["error"] = f"{type(error).__name__}: {error}"
            slow_query_log.record(entry)

    def exec_logical(self, plan: L.LogicalPlan,
                     ctx: QueryContext | None = None) -> QueryResult:
        ctx = ctx if ctx is not None else self._ctx()
        with span(SPAN_QUERY_EXECUTE, dataset=self.dataset), \
                ctx.stats.stage("execute"):
            res = self._exec_logical(plan, ctx)
        m = res.matrix
        ctx.stats.add("result_cells", m.num_series * len(m.out_ts))
        res.stats = ctx.stats
        res.exec_path = ctx.exec_path
        return res

    def _exec_logical(self, plan: L.LogicalPlan,
                      ctx: QueryContext) -> QueryResult:
        if self.mesh is not None:
            res = self._try_mesh(plan, ctx)
            if res is not None:
                return res
        res = self._try_fused_hist(plan, ctx)
        if res is not None:
            return res
        self._set_path(ctx, "local")
        with span(SPAN_QUERY_PLAN), ctx.stats.stage("plan"):
            exec_plan = self.planner.materialize(plan)
        try:
            res = exec_plan.run(ctx)
            self._note_fused(ctx, "local")
            return res
        except Exception as e:
            from .wire import RemoteLeafExec, RemotePeerError
            if not isinstance(e, RemotePeerError) or self.cluster is None:
                raise
            # the peer died mid-query: re-materialize (the ShardManager may
            # already have reassigned its shards to a survivor) and retry
            # ONCE — but only if EVERY failed shard actually ROUTES
            # differently now; re-dispatching an identical batch to the same
            # dead endpoint would just double the timeout
            from .wire import _plan_shards
            failed = set(getattr(e, "shards", ()) or ((e.shard,)
                                                      if e.shard >= 0 else ()))
            retry = self.planner.materialize(plan)
            for node in _walk_plans(retry):
                if (isinstance(node, RemoteLeafExec)
                        and node.endpoint == e.endpoint
                        and failed & set(_plan_shards(node.inner))):
                    raise
            self._set_path(ctx, "local-replanned")
            # the retry re-executes every leg, the already-merged successful
            # ones included — drop the first attempt's counts so the
            # response stats stay cluster-total, not attempt-total
            ctx.stats.reset_counters()
            try:
                res = retry.run(ctx)
                self._note_fused(ctx, "local-replanned")
                return res
            except QueryError as e2:
                # e.g. the reassigned shard's takeover recovery still lags
                # the map update: name both failures, stay retryable
                raise QueryError(
                    f"retry after peer failure also failed: {e2} "
                    f"(first failure: {e})") from e2

    def _try_fused_hist(self, plan: L.LogicalPlan,
                        ctx: QueryContext | None = None) -> QueryResult | None:
        """histogram_quantile(q, sum by(...) (fn(m[w]))) on a single
        grid-aligned native-histogram shard runs as ONE device program
        (ops/gridfns.fused_hist_quantile_grid) — per-bucket rates, bucket-wise
        group sums, and the quantile never surface as separate dispatches.
        Anything off-pattern returns None and takes the general ExecPlan path
        (ref: HistogramQueryBenchmark.scala is the latency bar)."""
        if not (isinstance(plan, L.ApplyInstantFunction)
                and plan.function == "histogram_quantile"
                and isinstance(plan.vectors, L.Aggregate)):
            return None
        from ..ops import fusedresident
        if fusedresident.mode() == "off":
            # query.fused_kernels=off: the composed ExecPlan chain (PSM ->
            # bucket-wise reduce -> quantile as separate dispatches) is the
            # configured path — the fused tier's A/B baseline
            return None
        agg = plan.vectors
        if agg.operator != "sum" or agg.params:
            return None
        inner = agg.vectors
        if not isinstance(inner, L.PeriodicSeriesWithWindowing):
            return None
        from ..ops import gridfns
        fn, raw = inner.function, inner.series
        if fn not in gridfns.HIST_GRID_FNS or raw.columns:
            return None
        shards = self.memstore.shards_of(self.dataset)
        if len(shards) != 1 or self._has_remote_shards():
            return None
        sh = shards[0]
        if sh.store is None or getattr(sh, "bucket_les", None) is None:
            return None
        if sh.store.grid_info() is None:
            return None              # off-grid store: general path outright
        step = max(inner.step_ms, 1)
        out_ts = np.arange(inner.start_ms, inner.end_ms + 1, step,
                           dtype=np.int64)
        if len(out_ts) == 0:
            return None
        leaf = SelectRawPartitionsExec(
            shard=sh.shard_num, filters=tuple(raw.filters),
            start_ms=raw.range_selector.from_ms,
            end_ms=raw.range_selector.to_ms)
        from dataclasses import replace as _dc_replace
        ctx = ctx if ctx is not None else self._ctx()
        # probe accounting: the leaf select below counts series/blocks, but
        # an off-pattern outcome re-runs the SAME leaf on the general path
        # — commit the probe's stats only when the fused route serves (the
        # same only-when-committed rule as the mesh path)
        pctx = _dc_replace(ctx, stats=QueryStats())
        # the route's one leaf: select, group ids and the kernel's dispatch
        # inside it, as SelectRawPartitionsExec's. Rare off-pattern outcomes
        # (cold data, churn minority) re-run the leaf on the general path —
        # acceptable on the slow path; the common aligned case pays it once
        with LeafFrame(shard=sh.shard_num) as frame:
            got = frame.locked([sh], lambda: self._fused_hist_dispatch(
                leaf, pctx, ctx, plan, agg, inner, out_ts))
        if got is None:
            return None
        self._set_path(ctx, got.route)
        ctx.stats.merge(pctx.stats)             # committed: fused serves
        return self._fetched(got, out_ts)

    def _fetched(self, got: "_Dispatch", out_ts, present=None) -> QueryResult:
        """The blocking fetch of what a device route's leaf dispatched,
        outside its lock(s) (the in-process leaf's rule), as the answer."""
        if got.result is None:
            return QueryResult(ResultMatrix(
                out_ts, np.zeros((0, len(out_ts))), []))
        m = (present or ResultMatrix)(out_ts, got.result.resolve(),
                                      list(got.group_keys))
        check_sample_limit(m.num_series, len(out_ts), self.config.sample_limit)
        return QueryResult(m)

    def _fused_hist_dispatch(self, leaf, pctx, ctx, plan, agg, inner,
                             out_ts) -> "_Dispatch | None":
        """``_try_fused_hist`` under the shard lock: select, group ids and
        the dispatch of one of three programs, chosen from the store's
        shape — the tiled kernel over the 2D-delta state
        (``fused-hist-narrow[...]``), the tiled kernel over the raw f32
        block (``fused-hist[...]``), or the untiled composition (bare
        ``fused-hist``) for what neither gate takes. Returns the exec path,
        the group keys and the dispatch's handle, which answers with the
        host ``[G, T]`` values (``result`` None: an empty selection, nothing
        dispatched; the raw tier's handle also counts its correction
        matmuls, ``fall_tiles``) — or None: general path."""
        from ..ops import fusedresident, gridfns
        from .exec import (SeriesSelection, _grouping_for, _pad_steps,
                           _pow2)
        fn = inner.function
        q = float(plan.function_args[0])
        data = leaf.do_execute(pctx)
        if (not isinstance(data, SeriesSelection) or data.grid is None
                or data.bucket_les is None
                or (data.grid_minority is not None
                    and len(data.grid_minority))):
            return None          # cold/off-grid/churned: general path
        out_eval, T = _pad_steps(out_ts)
        window = inner.window_ms
        if (max(abs(int(out_ts[0]) - data.grid[0]),
                abs(int(out_ts[-1]) - data.grid[0])) + window >= 2**31):
            return None
        R = data.val.shape[0]
        gids, uniq, G, gids_dev = _grouping_for(data.keys, data.rows, R,
                                                agg.by, agg.without)
        if not uniq:
            return _Dispatch("fused-hist", uniq, None)
        base_ts, interval_ms = data.grid
        les = np.asarray(data.bucket_les, np.float64)
        Gp = _pow2(G)
        path, falls = "fused-hist", None
        # what the dispatch span says of the program it covers; a tiled
        # kernel adds its backend and column range
        ktags = {"kernel": "xla", "rows": R, "cols": data.val.shape[1],
                 "steps": T, "groups": G, "buckets": len(les),
                 "variant": "hist-untiled"}
        with dispatching() as result:
            if data.hist_narrow is not None:
                # hist-resident store: one fused program off the i8/i16
                # 2D-delta block — the [S, C, B] f32 temp never exists.
                # Cohort-pool rows are excluded from the stream and folded
                # back in as group partials from a row-wise decode.
                import jax.numpy as jnp
                from ..ops import rangefns
                from .exec import _gather_rows_padded, _segment_partial
                dd, first_d, bad = data.hist_narrow
                corr = None
                if len(bad):
                    bad_gids = gids[bad].copy()
                    gids = gids.copy()
                    gids[bad] = _EXCLUDED_GID
                    sub_ts, sub_val, sub_n, P = _gather_rows_padded(
                        data.ts, data.val, data.n, bad)
                    hc = rangefns.periodic_samples_hist(
                        sub_ts, sub_val, sub_n, out_eval, window, fn, 0.0)
                    Tp, B = hc.shape[1], hc.shape[2]
                    cg = np.full(P, _EXCLUDED_GID, np.int32)
                    cg[:len(bad)] = bad_gids
                    parts = _segment_partial(
                        "sum", hc.reshape(P, Tp * B), jnp.asarray(cg), Gp)
                    corr = (parts["sum"].astype(jnp.float32),
                            parts["count"].astype(jnp.float32))
                B = dd.shape[2]
                if (fn in fusedresident.HIST_FUSED_FNS
                        and fusedresident.hist_fusable(
                            dd.shape[0], dd.shape[1], len(out_eval), B,
                            max(Gp, 8))):
                    # the registry's hist_quantile shape: per-tile decode +
                    # window delta + group fold as ONE map program (Pallas
                    # or the XLA twin per query.fused_kernels), keyed as a
                    # distinct kernel variant in the plan cache
                    out = fusedresident.fused_hist_quantile_resident(
                        q, les, dd, first_d, data.n, gids, Gp, out_eval,
                        window, fn, base_ts, interval_ms, corr=corr)
                    path = f"fused-hist-narrow[{fusedresident.tag()}]"
                    ktags.update(kernel=fusedresident.tag(),
                                 variant=f"hist-{dd.dtype}")
                    ctx.stats.add("fused_kernels")
                    fusedresident.count_served("hist_quantile")
                else:
                    # fns/shapes outside the tiled tier keep the one-program
                    # XLA composition (bit-parity guaranteed by PR 1 rules)
                    fusedresident.count_fallback("hist_quantile")
                    out = gridfns.fused_hist_quantile_grid_narrow(
                        q, les, dd, first_d, data.n, gids, Gp, out_eval,
                        window, fn, base_ts, interval_ms,
                        stale_ms=ctx.stale_ms, corr=corr)
            elif (fn in fusedresident.HIST_FUSED_FNS
                    and str(data.val.dtype) == "float32"
                    and fusedresident.raw_hist_fusable(
                        R, data.val.shape[1], len(out_eval), len(les),
                        max(Gp, 8))):
                # raw f32 residency (the shipped default): the same shape
                # streamed over row tiles of the block itself, its own
                # kernel variant — no [S, C, B]-sized temporary exists
                # the selection memo's device copy of the group ids, where
                # it keeps one: no upload a query
                out, falls, ktags = fusedresident.fused_hist_quantile_raw(
                    q, les, data.val, data.n,
                    gids if gids_dev is None else gids_dev, Gp, out_eval,
                    window, fn, base_ts, interval_ms)
                ktags.update(steps=T, groups=G)
                path = f"fused-hist[{fusedresident.tag()}]"
                ctx.stats.add("fused_kernels")
                fusedresident.count_served("hist_quantile")
            else:
                # outside the tiled tier's gate (f64 stores, odd row
                # counts, more groups than its accumulators hold, the
                # other grid fns): ONE XLA program over the whole block,
                # with [S, C, B]-sized temporaries — the parity reference
                if fn in fusedresident.HIST_FUSED_FNS:
                    fusedresident.count_fallback("hist_quantile")
                out = gridfns.fused_hist_quantile_grid(
                    q, les, data.val, data.n, gids, Gp, out_eval, window,
                    fn, base_ts, interval_ms, stale_ms=ctx.stale_ms)
            result.tags.update(ktags)
        if falls is None:
            result.holds((out,), lambda outs: outs[0][:G, :T])
        else:       # the raw tier's count of correction matmuls rides along
            result.holds(
                (out, falls), lambda outs: outs[0][:G, :T],
                lambda k: {"fall_tiles": fusedresident.count_fall_tiles(k)})
        return _Dispatch(path, uniq, result)

    # -- mesh dispatch (ref: queryengine2/QueryEngine.scala:59-67 — the
    # planner routes every query through per-shard dispatchers; here the
    # per-shard dispatch IS the shard_map and the reduce IS the psum) --------

    def _mesh_executor(self, shards):
        """A MeshQueryExecutor when every shard's store lives on its
        round-robin mesh device (shard i on device i % ndev — standalone's
        placement; shards-per-device >= 1) with one common [S, C] shape,
        else None (host fallback). Narrow-resident gauge stores qualify: the
        fused mesh path streams their i16 state (or a transient per-shard
        decode feeds the general collectives) — compressed residency and the
        mesh are no longer mutually exclusive. Call under the shard locks: a
        flush's compress_commit between this check and dispatch would
        otherwise swap ``val`` out from under the arrays capture."""
        from ..parallel.distributed import DistributedStore, MeshQueryExecutor
        if self.mesh is None:
            return None
        ndev = self.mesh.devices.size
        if len(shards) < ndev or len(shards) % ndev:
            return None
        devs = list(self.mesh.devices.ravel())
        s0 = shards[0].store
        if s0 is None:
            return None
        for i, sh in enumerate(shards):
            st = sh.store
            if (st is None or getattr(sh, "bucket_les", None) is not None
                    or st.nbuckets or st.layout is not None
                    or (st.val is not None and st.val.ndim != 2)
                    or (st.val is None and st._narrow is None)
                    or (st.S, st.C) != (s0.S, s0.C)
                    # n is resident under every residency state; ts/val may
                    # be elided forms that derive on the same device
                    or list(st.n.devices())[0] != devs[i % ndev]):
                return None
        return MeshQueryExecutor(DistributedStore(self.mesh, shards),
                                 self._mesh_memo)

    def _try_mesh(self, plan: L.LogicalPlan,
                  ctx: QueryContext | None = None) -> QueryResult | None:
        """Execute ``op(fn(selector[w]))`` via the mesh when the plan shape,
        operator, and store layout allow; None => caller falls back. Basic
        aggregates reduce via psum; topk/bottomk all_gather candidate blocks
        and quantile psums sketch counts (ref: AggrOverRangeVectors.scala:244
        — every aggregation's map phase runs at the data)."""
        if not isinstance(plan, L.Aggregate):
            return None
        op = plan.operator
        if op in MESH_OPS:
            if plan.params:
                return None
        elif op in MESH_ORDER_OPS:
            if len(plan.params) != 1:
                return None
        else:
            return None
        inner = plan.vectors
        if isinstance(inner, L.PeriodicSeriesWithWindowing):
            raw, fn, window = inner.series, inner.function, inner.window_ms
            args = tuple(float(a) for a in (inner.function_args or ()))
        elif isinstance(inner, L.PeriodicSeries):
            raw, fn = inner.raw_series, "last_sample"
            window = self.config.stale_sample_after_ms
            args = (float(window),)
        else:
            return None
        if raw.columns or fn is None:
            return None
        shards = self.memstore.shards_of(self.dataset)
        if len(shards) < 2:
            return None
        if self.mesh is None or len(shards) % self.mesh.devices.size:
            return None          # cheap pre-checks before taking any locks
        step = max(inner.step_ms, 1)
        out_ts = np.arange(inner.start_ms, inner.end_ms + 1, step,
                           dtype=np.int64)
        if len(out_ts) == 0:
            return None
        from .exec import GATHER_THRESHOLD    # read at call time, as the leaf does
        filters = list(raw.filters)
        from_ms = raw.range_selector.from_ms
        to_ms = raw.range_selector.to_ms
        memo = self._mesh_memo
        # BEFORE the locks, what no sample decides. Staged rows land under
        # their own shard's lock, the flush's wait for the device with them
        # (the peek is unlocked, like ``flush()``'s own; each select below
        # flushes again under all the locks, and finds nothing but a
        # container that arrived in between: a query sees every row staged
        # before it took the locks, as it did). The window plan of this step
        # grid is built and placed on the mesh for the grid the last
        # dispatch saw
        for sh in shards:
            if sh._staged:
                sh.flush()
        prepared = memo.prepare_plan(fn, op, out_ts, window)
        # all shard locks held across eligibility, the selects, array
        # capture AND the dispatch: a concurrent ingest flush donates
        # (invalidates) any shard's store buffers mid-stream otherwise
        # (same rule as the in-process leaf) — and a flush's
        # compress_commit landing between an unlocked eligibility check and
        # dispatch would swap the raw blocks for compressed state mid-plan
        # (the 500s VERDICT flagged). Every lock is held for the whole of
        # it, so what is done under them is kept to validation, handles and
        # ONE pjit call: four memo-hit selects, the group-id rows from the
        # engine's memo (built, uploaded and kept here only for a selector
        # or grouping it has not seen in this index state), the globals'
        # assembly (no program), the epochs' capture, the call
        def dispatch(leaf_tags: dict) -> "_Dispatch | None":
            ex = self._mesh_executor(shards)
            if ex is None:
                return None      # residency/shape changed: host path
            picks = []
            for sh in shards:
                with span(SPAN_QUERY_SELECT, shard=sh.shard_num) as sel:
                    picked, sel["memo"] = sh.selection(
                        filters, from_ms, to_ms, GATHER_THRESHOLD)
                    sel["series"] = len(picked.pids)
                    paging = sh.needs_paging(picked.pids, from_ms)
                if paging:
                    # cold data: host ODP path handles it
                    distributed.count_mesh_fallback("paging")
                    return None
                picks.append(picked)
            # committed to ctx.stats only when the mesh path actually serves
            # (a later fallback to the host path must not double-count its
            # own leaf counts)
            matched_total = sum(len(p.pids) for p in picks)
            if not matched_total:
                return _Dispatch("empty", (), None)
            with span(SPAN_QUERY_GROUPIDS, keys=matched_total,
                      route="index") as tags:
                kept = memo.gids(picks, plan.by, plan.without)
                if kept is not None:
                    how = "memo"
                    group_keys, gids_list = kept
                else:
                    group_keys, rows = self._mesh_group_rows(
                        picks, plan.by, plan.without)
                    # each row straight to its own shard's device, once
                    gids_list = ex.dstore.place_gids(rows)
                    how = ("built" if memo.keep_gids(
                        picks, plan.by, plan.without, group_keys, gids_list)
                        else "bypass")
                tags["memo"] = leaf_tags["gids"] = how
                tags["groups"] = len(group_keys)
            distributed.count_mesh_prepared("gids", how)
            count_groupids("index")
            G = len(group_keys)
            a0 = args[0] if len(args) > 0 else 0.0
            a1 = args[1] if len(args) > 1 else 0.0
            # any partition release invalidates (shard, row) -> key
            # resolution after the fetch: capture the coarse release epochs
            # BEFORE any kernel dispatch (the read-side epoch contract —
            # a capture taken after dispatch could already include a
            # release that re-assigned rows between the gid build above
            # and the capture, and the post-fetch validation in
            # _present_mesh_topk would then pass vacuously)
            epochs = [sh._release_epoch for sh in shards]
            # dispatch under the locks; the blocking host fetch happens after
            # they release (same rule as the in-process leaf) so a slow
            # collective never stalls ingest across every shard. The FIRST
            # query of a new (fn, op, G-bucket, T-bucket) shape still traces
            # and compiles here — step-count bucketing inside the executor
            # bounds that compile space exactly like the in-process path.
            # A return without a result below (a cap) drops the handle, and
            # its place in the in-flight count with it
            with dispatching(steps=len(out_ts),
                             rows=sum(sh.store.S for sh in shards),
                             groups=G) as result:
                if op == "quantile":
                    # same safety gates as the in-process order-stat map:
                    # group cap + dense-sketch memory cap (every device
                    # allocates the [Gp, W, T] counts; the host route falls
                    # back to the exact matrix instead of dying in HBM)
                    from ..ops import aggregators as _agg
                    from .exec import (_SKETCH_BYTES_CAP, AggregateMapReduce,
                                       _pow2)
                    if (G > AggregateMapReduce.ORDER_STAT_MAX_GROUPS
                            or _pow2(G) * _agg.SKETCH_WIDTH
                            * (len(out_ts) + 31) * 4 > _SKETCH_BYTES_CAP):
                        distributed.count_mesh_fallback("order_stat_caps")
                        return None
                    held = ex.quantile(fn, out_ts, window, gids_list, G,
                                       float(plan.params[0]), args=(a0, a1))
                elif op in ("topk", "bottomk"):
                    k = max(int(plan.params[0]), 0)
                    if k == 0 or G > MESH_TOPK_MAX_GROUPS:
                        distributed.count_mesh_fallback("topk_caps")
                        return None
                    held = ex.topk(fn, out_ts, window, gids_list, G, k,
                                   op == "bottomk", args=(a0, a1))
                else:
                    held = ex.aggregate(fn, op, out_ts, window, gids_list,
                                        G, args=(a0, a1), fetch=False,
                                        prepared=prepared)
                result.holds(*held)
                # the program that ran and, for a fused one, its column
                # block: what ties a device event to this query — and
                # whether its window plan was ready as the locks were taken
                result.tags["kernel"] = f"pjit-{ex.last_path}"
                if ex.last_block is not None:
                    result.tags["c0"], result.tags["cols"] = ex.last_block
                    leaf_tags["plan"] = ex.last_plan
                if ctx is not None:     # committed: the mesh path serves this
                    ctx.stats.add("series_matched", matched_total)
                    if ex.last_path.startswith("fused"):
                        # stats symmetry with the in-process fused route
                        # (exec.py): cluster stats equal the single-node
                        # oracle
                        ctx.stats.add("fused_kernels")
            return _Dispatch(ex.last_path, group_keys, result, epochs)

        # the mesh route's one leaf: every shard's lock, taken in order
        with LeafFrame(shard="all", route="mesh", locks=len(shards)) as leaf:
            got = leaf.locked(shards, lambda: dispatch(leaf.tags))
        if got is None:
            return None
        if got.result is None:
            self._set_path(ctx, "mesh-empty")
            return self._fetched(got, out_ts)
        # the strings the ledger, chip_smoke.MESH_PREFIX and the benchmark's
        # expected routes read
        self._set_path(ctx, f"mesh[pjit]-{got.route}")
        distributed.count_mesh_served(got.route)
        present = None
        if op in ("topk", "bottomk"):
            present = functools.partial(self._present_mesh_topk, shards,
                                        got.epochs)
        return self._fetched(got, out_ts, present)

    @staticmethod
    def _mesh_group_rows(picks, by, without):
        """(group keys, one dense ``[S]`` int32 row a shard) of a mesh leaf:
        the groups numbered over ALL shards in first-appearance order (shard
        order), rows outside a shard's selection excluded. A function of the
        shards' selections and the grouping alone — what
        ``distributed.MeshLeafMemo`` keeps."""
        uniq: dict[RangeVectorKey, int] = {}
        rows = []
        for picked in picks:
            pids = picked.pids
            g = np.full(picked.shard.store.S, _EXCLUDED_GID, np.int32)
            if len(pids):
                if not by and not without:
                    g[pids] = uniq.setdefault(RangeVectorKey(()), 0)
                else:
                    # the shard's own groups from its label columns (vid
                    # pools are per shard; once per index state: the
                    # selection memo), then G keys — not the series —
                    # mapped onto the shared numbering
                    local, _how = picked.grouping(by, without)
                    shared = np.fromiter(
                        (uniq.setdefault(gk, len(uniq)) for gk in local.keys),
                        np.int32, count=len(local.keys))
                    g[pids] = shared[local.gids]
            rows.append(g)
        return tuple(uniq), rows

    def _present_mesh_topk(self, shards, epochs, out_ts, winners,
                           group_keys) -> ResultMatrix:
        """Map the mesh topk's fetched (shard, row) winners back to series keys
        and present them Prometheus-style (union of selected series, values at
        steps where each made the cut). Key resolution re-takes each winner
        shard's lock and validates its release epoch — a purge/eviction
        since dispatch could have re-assigned the row to a new series."""
        from .exec import TopKPartial, _present_topk
        vals, shard_ids, rows, ok = winners
        G, k, T = vals.shape
        flat_ok = ok.ravel()
        pairs = (shard_ids.ravel()[flat_ok].astype(np.int64) << 32) \
            | rows.ravel()[flat_ok].astype(np.int64)
        upairs = np.unique(pairs)
        key_table = []
        pair_slot = {}
        for pr in upairs.tolist():
            si, row = pr >> 32, pr & 0xFFFFFFFF
            sh = shards[si]
            with sh.lock:
                if sh._release_epoch != epochs[si]:
                    raise QueryError(
                        "selection invalidated by concurrent partition "
                        "release (eviction/purge); retry the query")
                key_table.append(sh.rv_key_of(int(row)))
            pair_slot[pr] = len(key_table) - 1
        key_ref = np.full(G * k * T, -1, np.int64)
        if len(upairs):
            idx = np.nonzero(flat_ok)[0]
            key_ref[idx] = [pair_slot[int(p)] for p in pairs.tolist()]
        return _present_topk(TopKPartial(
            k, False, out_ts, group_keys, vals,
            key_ref.reshape(G, k, T), key_table))

    # -- cross-node helpers ---------------------------------------------------

    def _has_remote_shards(self) -> bool:
        if self.cluster is None or self.node is None:
            return False
        return any(self._route_endpoint(s) is not None
                   for s in self.mapper.all_shards())

    def _peer_endpoints(self) -> list[str]:
        """Distinct HTTP endpoints of peers owning shards of this dataset."""
        eps: dict[str, None] = {}
        for s in self.mapper.all_shards():
            ep = self._route_endpoint(s)
            if ep is not None:
                eps.setdefault(ep)
        return list(eps)

    def peer_scatter_begin(self, fetch):
        """Start ``fetch(ep)`` for every peer endpoint concurrently; returns
        an opaque handle for :meth:`peer_scatter_join` (None when no peers).
        Begin/join are split so callers can overlap their LOCAL work with the
        peer round-trips (the shared scatter scaffold for metadata and
        remote-read fan-outs)."""
        from concurrent.futures import ThreadPoolExecutor
        eps = self._peer_endpoints()
        if not eps:
            return None
        # scatter legs run on pool threads: adopt the caller's trace context
        # so their spans (and anything the peer records) join its trace
        run = tracer.wrap(fetch)
        pool = ThreadPoolExecutor(max_workers=min(len(eps), 16))
        futs = [(ep, pool.submit(run, ep)) for ep in eps]
        return (pool, futs)

    @staticmethod
    def peer_scatter_join(handle) -> list:
        """[(endpoint, result-or-Exception)] for a begun scatter."""
        if handle is None:
            return []
        pool, futs = handle
        out = []
        for ep, f in futs:
            try:
                out.append((ep, f.result()))
            except Exception as e:  # noqa: BLE001 — caller decides severity
                out.append((ep, e))
        pool.shutdown(wait=False)
        return out

    def _peer_metadata(self, path: str) -> list:
        """Fan a metadata request out to all peers concurrently (local=1
        stops recursion); an unreachable peer is skipped — its shards are
        mid-reassignment and metadata is best-effort (ref: the coordinator's
        metadata scatter). Raw DATA reads are NOT best-effort — they use the
        same scatter but raise on peer failure (promql/remote.py)."""
        import json as _json
        import logging
        import urllib.request

        def fetch(ep: str) -> list:
            sep = "&" if "?" in path else "?"
            url = f"http://{ep}/promql/{self.dataset}{path}{sep}local=1"
            with urllib.request.urlopen(url, timeout=10.0) as r:
                return _json.load(r).get("data") or []

        out: list = []
        for ep, res in self.peer_scatter_join(self.peer_scatter_begin(fetch)):
            if isinstance(res, Exception):
                logging.getLogger("filodb_tpu.query").warning(
                    "metadata fan-out to peer %s failed; partial result", ep)
            else:
                out.extend(res)
        return out

    # -- metadata queries (ref: QueryActor label-values / series paths) -------

    @staticmethod
    def _match_suffix(filters) -> str:
        if not filters:
            return ""
        from urllib.parse import quote
        return "?match[]=" + quote(_filters_to_selector(filters))

    def label_value_counts(self, label: str, filters=None, top_k=None,
                           local_only: bool = False):
        """value -> series count across local shards and (unless local_only)
        peers — the substrate for cluster-wide top-k ranking. The peer leg
        forwards ``top_k`` (each node prunes to its local top-k candidates)
        and asks for counted pairs (``counts=1``), so the merge re-ranks by
        SUMMED count instead of trusting any one node's ordering."""
        from collections import Counter
        counts: Counter = Counter()
        # local shards contribute FULL counts — pruning per shard here would
        # reintroduce the dominance bug this method fixes cross-node (a value
        # ranked k+1 in every shard can be #1 by summed count); only the
        # remote leg prunes, per NODE, where exact merge is not free
        for shard in self.memstore.shards_of(self.dataset):
            for v, c in shard.label_value_counts(label, filters):
                counts[v] += c
        if not local_only:
            sfx = self._match_suffix(filters)
            sep = "&" if sfx else "?"
            path = f"/api/v1/label/{label}/values{sfx}{sep}counts=1"
            if top_k is not None:
                path += f"&top_k={int(top_k)}"
            for row in self._peer_metadata(path):
                if isinstance(row, (list, tuple)) and len(row) == 2:
                    counts[str(row[0])] += int(row[1])
                elif isinstance(row, str):   # uncounted peer: presence only
                    counts[row] += 1
        return counts

    def label_values(self, label: str, filters=None, top_k=None,
                     local_only: bool = False) -> list[str]:
        if top_k is not None:
            # the k limit re-applies AFTER the cross-node merge: per-node
            # top-k lists are candidates, the summed counts decide
            counts = self.label_value_counts(label, filters, top_k=top_k,
                                             local_only=local_only)
            return [v for v, _ in counts.most_common(top_k)]
        vals: dict[str, None] = {}
        for shard in self.memstore.shards_of(self.dataset):
            for v in shard.label_values(label, filters):
                vals[v] = None
        if not local_only:
            for v in self._peer_metadata(
                    f"/api/v1/label/{label}/values"
                    + self._match_suffix(filters)):
                vals[v] = None
        return sorted(vals)

    def label_names(self, filters=None, local_only: bool = False) -> list[str]:
        names: set[str] = set()
        for shard in self.memstore.shards_of(self.dataset):
            names.update(shard.label_names(filters))
        if not local_only:
            # peers answer on the Prometheus surface (__name__); fold back
            # to the internal metric label so the merge stays canonical
            names.update("_metric_" if n == "__name__" else n
                         for n in self._peer_metadata(
                             "/api/v1/labels" + self._match_suffix(filters)))
        return sorted(names)

    def series(self, filters, start_ms: int, end_ms: int,
               local_only: bool = False) -> list[dict[str, str]]:
        out = []
        for shard in self.memstore.shards_of(self.dataset):
            # ids and labels under one lock: a concurrent purge reuses slots
            with shard.lock:
                pids = shard.part_ids_from_filters(list(filters), start_ms, end_ms)
                out.extend(shard.index.labels_of(int(p)) for p in pids)
        if not local_only and self._has_remote_shards():
            from ..core import filters as F
            sfx = self._match_suffix(
                filters or [F.EqualsRegex("_metric_", ".*")])
            path = (f"/api/v1/series{sfx}"
                    f"&start={start_ms / 1000.0}&end={end_ms / 1000.0}")
            for d in self._peer_metadata(path):
                if "__name__" in d:
                    d = dict(d)
                    d["_metric_"] = d.pop("__name__")
                out.append(d)
        return out

    def raw_series(self, filters, start_ms: int, end_ms: int):
        """Yield (labels, ts[int64], vals[f64]) of raw samples in range — the
        remote-read path (ref: PrometheusModel remote-read conversion reads raw
        chunks, not periodic samples). Scalar schemas only."""
        import numpy as np
        for shard in self.memstore.shards_of(self.dataset):
            if shard.schema.is_histogram:
                continue   # remote-read protocol carries scalar samples
            # resolve ids, capture arrays, AND read labels under one lock
            # acquisition: a concurrent purge reuses freed slots, which would
            # attribute a new series' samples to the old series' labels (same
            # reason SelectRawPartitionsExec holds the lock across both steps)
            with shard.lock:
                pids = shard.part_ids_from_filters(list(filters), start_ms, end_ms)
                if len(pids) == 0 or shard.store is None:
                    continue
                labels = [shard.index.labels_of(int(p)) for p in pids]
                if shard.needs_paging(pids, start_ms):
                    ts_a, val_a, n_a = shard.read_with_paging(pids, start_ms, end_ms)
                    rows = [(ts_a[i, :n_a[i]], val_a[i, :n_a[i]])
                            for i in range(len(pids))]
                else:
                    # one block materialization for the whole selection — a
                    # compressed-resident store must not decode per series
                    tsrc, vsrc = shard.store.snapshot_arrays()
                    nh = shard.store.samples_host
                    rows = [(np.asarray(tsrc[int(p), :nh[int(p)]]),
                             np.asarray(vsrc[int(p), :nh[int(p)]]))
                            for p in pids]
            for lbl, (t, v) in zip(labels, rows):
                keep = (t >= start_ms) & (t <= end_ms)
                if keep.any():
                    yield (lbl, np.asarray(t[keep]), np.asarray(v[keep], np.float64))
